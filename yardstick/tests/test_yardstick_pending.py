"""Pods that stay pending, through run.py on the CPU, and pods that leave,
against the real apiserver and watcher processes (PR 36).

``rehearsal-pending`` is a deployment in the shape of upstream's
Unschedulable workload: 8 pods of 9 CPU onto nodes of 4, created in set-up
and not awaited, then 64 default pods measured. Its back-off is 0.02 s, so
the pending pods come round again inside a window of some 50 ms and the
unschedulable attempts are there to read; the explainer explains a pod once
in two seconds (``sched/explainer.REEXPLAIN_INTERVAL_S``), so its spans are
read in a window that is three seconds long by construction, the arrivals
kind's. No preemption rehearsal: the rule for pods that leave is held here
with a client that creates, binds and deletes the pods itself."""

import json
import multiprocessing as mp
import time

from conftest import KEYS, run_cell

from yardstick import procs, program, verdicts
from yardstick.generators._objects import node, pod


def counters_of(lines) -> dict:
    line, = [ln for ln in lines if ln.startswith("counters: ")]
    return json.loads(line[len("counters: "):])


def held(last, lines, attempted) -> dict:
    """What every run of the rehearsal must print; -> its counters."""
    assert KEYS <= set(last)
    assert last["correct"] is True and last["failed"] == 0, lines[-4:]
    assert last["attempted"] == attempted
    assert list(last)[-1] == "compared"
    assert last["compared"]["left_pending"] == {"value": 0, "limit": 0}
    beside = counters_of(lines)
    assert (beside["pending_pods"], beside["pending_bound"],
            beside["left"]) == (8, 0, 0)
    return beside


def test_the_pending_pods_are_not_awaited_and_not_attempted():
    rc, lines, last = run_cell("rehearsal-pending.burst", 2147483659, 30)
    assert rc == 0, lines[-5:]
    held(last, lines, 64)
    assert set(last["metrics"]) == {"bound_rate", "setup_s"}


def test_a_traced_burst_reads_the_attempts_called_unschedulable():
    rc, lines, last = run_cell("rehearsal-pending.burst", 3000000019, 30,
                               trace=1)
    assert rc == 0, lines[-5:]
    held(last, lines, 64)
    got = last["metrics"]
    assert got["unschedulable_per_kpod.burst"]["value"] > 0.0
    assert got["pods_per_drain.burst"]["value"] > 0.0
    # the explainer's pass falls inside a 50 ms window by chance only
    assert got.get("explain_ms_per_drain.burst", {"value": 1.0})[
        "value"] > 0.0


def test_a_traced_window_of_three_seconds_reads_the_explainer():
    rc, lines, last = run_cell("rehearsal-pending.rehearsal-arrivals",
                               3000000021, 3, trace=1)
    assert rc == 0, lines[-5:]
    held(last, lines, 240)
    got = last["metrics"]
    assert got["unschedulable_per_kpod.arrivals"]["value"] > 0.0
    assert got["explain_ms_per_drain.arrivals"]["value"] > 0.0
    assert not any(name.endswith(".burst") for name in got)


def test_the_watcher_stamps_the_pods_that_leave():
    """The apiserver and the watcher as a run starts them; this test is
    the scheduler and the evictor: it binds three pods and deletes one
    bound and one never bound."""
    ctx = mp.get_context("spawn")
    children = []

    def spawn(target, *args):
        mine, theirs = ctx.Pipe()
        proc = ctx.Process(target=target, args=(*args, theirs), daemon=True)
        proc.start()
        children.append(proc)
        return mine

    server = spawn(procs.serve)
    try:
        url = f"http://127.0.0.1:{server.recv()}"
        client = program.client(url)
        client.nodes().create_many(
            [node("n0", {"cpu": "4", "memory": "32Gi", "pods": "110"})])
        _, rv0 = client.resource("pods", None).list_rv()
        count, stop = ctx.Value("i", 0), ctx.Event()
        watch = spawn(procs.watch, url, rv0, count, stop)
        assert watch.poll(60.0) and watch.recv() == "ready"
        pods = client.pods("default")
        names = ("victim", "stays", "measured", "never-bound")
        pods.create_many([pod(n, {"cpu": "100m"}) for n in names])
        for n in names[:3]:
            pods.bind(n, "n0")
        assert program.wait_until(lambda: count.value == 3, 30.0)
        pods.delete("victim")
        pods.delete("measured")
        pods.delete("never-bound")
        assert program.wait_until(
            lambda: len(client.resource("pods", None).list()) == 1, 30.0)
        # the watcher reads the stream's events in order: give it the
        # time to reach the three DELETED ones, then ask
        time.sleep(1.0)
        stop.set()
        assert watch.poll(30.0)
        seen = watch.recv()
        after = client.resource("pods", None).list()
    finally:
        server.send("stop")
        for proc in children:
            proc.join(timeout=10.0)
            if proc.is_alive():
                proc.kill()
                proc.join()

    assert set(seen) == {"binds", "gone", "restarts"}
    assert {k: v[1] for k, v in seen["binds"].items()} == {
        f"default/{n}": "n0" for n in names[:3]}
    assert set(seen["gone"]) == {"default/victim", "default/measured",
                                 "default/never-bound"}
    for k, (t, obj) in seen["gone"].items():
        assert obj["metadata"]["name"] == k.split("/")[1]
        if k in seen["binds"]:
            # the object as the event carried it: the pod's last state
            assert obj["spec"]["nodeName"] == "n0"
            assert t >= seen["binds"][k][0]
    # only the victim's phase may leave: the measured pod that is gone is
    # unconfirmed, the victim and the pod that stayed are confirmed
    verdict, wrong = verdicts.read_back(seen["binds"], after, seen["gone"],
                                        {"default/victim", "default/stays"})
    assert wrong == {"default/measured"} and verdict[1] is False
    # and without ``leavers`` both deleted pods are, as before PR 36
    _, wrong = verdicts.read_back(seen["binds"], after)
    assert wrong == {"default/victim", "default/measured"}
