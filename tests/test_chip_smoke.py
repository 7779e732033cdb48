"""chip_smoke.py's gates, on the CPU at a tiny size.

The smoke exists to fail when the chip did not do the work; these pin
that it does — a clean run holds every gate yet cannot produce the pass
line, a CPU backend without the rehearsal argument is refused by name, a
device-failure burst the breaker absorbs (every pod still binds, on the
host oracle) is caught, and a mesh wider than the backend fails instead
of degrading to one device.
"""

import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (top level is free of backend calls)

TINY = dict(n_pods=96, n_nodes=24, batch_size=16, drain_batches=2,
            timeout=120.0)


def _opts(**kw):
    return dict({"seed": 0, "mesh": None, "rehearsal": True,
                 "bind_timeout": 120.0}, **kw)


def _pass_lines(text: str) -> list:
    out = []
    for line in text.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and doc.get("ok"):
            out.append(line)
    return out


def test_clean_run_holds_the_gate_but_a_rehearsal_never_passes():
    from benchmarks.connected import check_served_on_device, run_connected
    res = run_connected(**TINY, cfg_extra={"parity_sample_every": 1})
    assert check_served_on_device(res) == [], res
    assert res["parity"]["samples"]["drain"] >= res["drains_dispatched"] >= 3
    assert res["compile"]["window"]["realCompiles"] == 0, res["compile"]
    # the same clean result through the smoke's verdict, every way a
    # rehearsal could end: no pass line, never exit code 0
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = chip_smoke.finish(_opts(), [], res["device"])
        rc_tpu_dev = chip_smoke.finish(
            _opts(), [], {"platform": "tpu", "kind": "x", "count": 1})
        rc_cpu = chip_smoke.finish(_opts(rehearsal=False), [],
                                   res["device"])
    assert rc == rc_tpu_dev == chip_smoke.EXIT_REHEARSAL != 0
    assert rc_cpu == chip_smoke.EXIT_FAILED
    assert _pass_lines(buf.getvalue()) == []
    assert all(line.startswith("REHEARSAL platform=cpu ")
               for line in buf.getvalue().splitlines()[:2])
    # and the one branch that does pass prints exactly the contract's line
    buf = io.StringIO()
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with redirect_stdout(buf):
        assert chip_smoke.finish(_opts(rehearsal=False), [], dev) == 0
    assert json.loads(buf.getvalue().splitlines()[-1]) == {"ok": True,
                                                           "device": dev}


def test_device_failure_the_breaker_absorbs_is_caught():
    """Every drain and every per-batch gang dispatch raises: the breaker
    walks to the numpy oracle, which binds every pod — and the gate names
    what happened."""
    from benchmarks.connected import check_served_on_device, run_connected
    from kubernetes_tpu.chaos import Fault, FaultSchedule
    # installed after the warm ladder, so op 0 is the first served drain
    burst = FaultSchedule([Fault("device.drain", "runtime", 0, 10_000),
                           Fault("device.gang", "runtime", 0, 10_000)])
    res = run_connected(**TINY, fault_schedule=burst)
    assert res["bound"] == res["pods"] == TINY["n_pods"], res
    assert res["invariant_violations"] == 0
    failures = check_served_on_device(res)
    text = " | ".join(failures)
    assert "device_drain" in text and "device_gang" in text, failures
    assert "degraded mode 'oracle'" in text and "breaker trips" in text
    assert res["resilience"]["degradedMode"] == "oracle"


def test_cpu_backend_without_the_rehearsal_argument_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode not in (0, chip_smoke.EXIT_REHEARSAL)
    assert "platform is 'cpu', not 'tpu'" in proc.stdout, proc.stdout
    assert _pass_lines(proc.stdout) == []


def test_mesh_wider_than_the_backend_fails_instead_of_degrading():
    import jax
    from benchmarks.connected import check_served_on_device
    wide = (1, 2 * jax.device_count())
    buf = io.StringIO()
    with redirect_stdout(buf):
        _dev, failures = chip_smoke._device_report(_opts(mesh=wide))
    assert any("refusing the single-device degrade" in f
               for f in failures), failures
    # and should the scheduler degrade anyway (its constructor logs and
    # carries on), the residency gate refuses the result
    degraded = {"pods": 1, "bound": 1, "invariant_violations": 0,
                "loop_errors": {}, "schedule_attempts": {},
                "resilience": {"degradedIndex": 0, "breakerTrips": 0},
                "ctx_stats": {"rebuilds": 0}, "batch_size": 1,
                "drain_batches": 1, "drains_dispatched": 1,
                "device": {"platform": "cpu"}, "mesh_shape": [1, 4],
                "residency": {"armed": True, "mesh": None,
                              "platforms": ["cpu"], "devices": [0],
                              "allocatable_shape": [128, 3],
                              "allocatable_shards": [
                                  {"device": 0, "rows": [0, 128]}]}}
    assert any("mesh 1x4 not live" in f
               for f in check_served_on_device(degraded))
    replicated = dict(degraded, residency=dict(
        degraded["residency"],
        mesh={"shape": [1, 4], "platforms": ["cpu"],
              "devices": [0, 1, 2, 3]},
        devices=[0, 1, 2, 3],
        allocatable_shards=[{"device": d, "rows": [0, 128]}
                            for d in range(4)]))
    assert any("not split 4 ways" in f
               for f in check_served_on_device(replicated))


def test_the_width_is_fixed_and_a_carried_on_error_fails_the_smoke():
    """No argument changes the size a pass is a pass at, and what the
    product logged or counted while carrying on fails the served gate
    although the shared device gate (chaos runs use it too) holds."""
    assert (chip_smoke.FULL["nodes"], chip_smoke.FULL["pods"]) \
        == (5000, 10000) and chip_smoke.REDUCED == []
    for flag in ("--pods", "--nodes"):
        with pytest.raises(SystemExit):
            chip_smoke._parse([flag, "48"])
    assert chip_smoke._size(_opts(rehearsal=False)) is chip_smoke.FULL
    ok = {"pods": 4, "bound": 4, "invariant_violations": 0,
          "loop_errors": {}, "schedule_attempts": {"scheduled": 4.0},
          "resilience": {"degradedIndex": 0, "breakerTrips": 0},
          "ctx_stats": {"rebuilds": 0}, "batch_size": 2,
          "drain_batches": 1, "drains_dispatched": 2,
          "device": {"platform": "cpu"}, "mesh_shape": None,
          "residency": {"armed": True, "platforms": ["cpu"]},
          "parity": {"every": 1, "samples": {"drain": 2}, "divergences": 0,
                     "pending": 0}}
    assert chip_smoke.served_gate(_opts(), ok, [], []) == []
    logged = chip_smoke.served_gate(
        _opts(), ok, ["kubernetes_tpu.sched.preemption: wave failed"], [])
    assert logged == ["logged and carried on: "
                      "kubernetes_tpu.sched.preemption: wave failed"]
    counted = chip_smoke.served_gate(
        _opts(), dict(ok, loop_errors={"run_once": 1.0}), [], [])
    assert counted == ["loop error at run_once: 1"]


def test_every_jitted_entry_point_is_declared():
    assert chip_smoke._declared_vs_found() == []


@pytest.mark.parametrize("missing", ["loop_errors", "resilience",
                                     "residency", "drains_dispatched",
                                     "invariant_violations"])
def test_a_missing_number_fails_like_a_bad_one(missing):
    from benchmarks.connected import check_served_on_device
    ok = {"pods": 4, "bound": 4, "invariant_violations": 0,
          "loop_errors": {}, "schedule_attempts": {"scheduled": 4.0},
          "resilience": {"degradedIndex": 0, "breakerTrips": 0},
          "ctx_stats": {"rebuilds": 0}, "batch_size": 2,
          "drain_batches": 1, "drains_dispatched": 2,
          "device": {"platform": "cpu"}, "mesh_shape": None,
          "residency": {"armed": True, "platforms": ["cpu"]}}
    assert check_served_on_device(ok) == []
    broken = {k: v for k, v in ok.items() if k != missing}
    assert check_served_on_device(broken) != []


def test_count_above_256_on_one_node_answers_as_the_oracle():
    """More than 256 matching pods on one node (past bfloat16's exact
    integers, which a TPU's default matmul precision rounded to): a CPU
    was always exact, so this pins only that the case runs and agrees
    with the oracle — the chip run is what proves the rounding is gone."""
    assert chip_smoke.check_count_precision()["problems"] == []
