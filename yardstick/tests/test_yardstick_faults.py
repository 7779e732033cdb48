"""``correct`` comes out false when the timed path is broken underneath: a
whole run of the pending rehearsal through ``harness.run`` (a rehearsal
configuration skips the look for a chip), with the program's answers
altered where they are produced. A scheduler that runs no model has two
such faults: an answer altered (a pod bound somewhere else than the device
program decided) and a guarantee of the configuration broken (a pod that
fits nowhere is placed)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

DRIVE = """
import argparse, sys, time
T0 = time.monotonic()
sys.path.insert(0, {root!r})
{fault}
from yardstick import harness
sys.exit(harness.run(argparse.Namespace(
    workload="rehearsal-pending.burst", seed={seed}, seconds=30.0, trace=0),
    T0))
"""

FAULTS = {
    # every bind of the run goes to one node, whatever was decided: that
    # node ends over its allocatable
    "an answer altered where it is produced": ("""
from kubernetes_tpu.sched import runner
decided = runner.SchedulerRunner._bind_many
runner.SchedulerRunner._bind_many = lambda self, pairs: decided(
    self, [(pod, "node-0") for pod, _node in pairs])
""", {"end_state.capacity"}),
    # after set-up has seen the pool parked, a pod of 9 CPU is bound to a
    # node of 4 all the same
    "a pod that fits nowhere is placed": ("""
from yardstick import harness
awaited = harness.Deployment.warm_up
def warm_up(self):
    awaited(self)
    meta = self.pending[0]["metadata"]
    self.client.pods(meta["namespace"]).bind(meta["name"], "node-1")
harness.Deployment.warm_up = warm_up
""", {"left_pending", "end_state.capacity"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_run_is_not_correct(fault):
    code, names = FAULTS[fault]
    proc = subprocess.run(
        [sys.executable, "-c",
         DRIVE.format(root=ROOT, fault=code, seed=2147483700)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 0, (lines[-5:], proc.stderr[-2000:])
    last = json.loads(lines[-1])
    assert last["correct"] is False, lines[-3:]
    wrong = {name for name, number in last["compared"].items()
             if number["value"] > number["limit"]}
    # the program's own judges may object as well: the benchmark's must
    assert names <= wrong, last["compared"]
    for name in names:
        assert any(ln.startswith(f"VERDICT FAILED {name}: ")
                   for ln in lines), lines[-8:]
    # the numbers compared are the last lines of standard error too
    tail = proc.stderr.strip().splitlines()[-len(last["compared"]):]
    assert [ln.split(":")[0] for ln in tail] == [
        f"compared {name}" for name in last["compared"]]
