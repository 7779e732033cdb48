"""The unschedulable-5000n deployment at a small size, on the CPU: upstream
scheduler_perf Unschedulable's own pods (the benchmark's generator,
namespaces as its configuration names them) through the served path. Pods
of 9 CPU fit on no node of 4: they are called unschedulable, explained,
backed off and tried again, round after round, while every default pod
created beside them binds. The end state is judged by the benchmark's plain
reference and by ``verdicts.left_pending``; the failure path's spans and
series (PERF.md section 3) are held to their threads, parents and counts,
and the metric files that read them to the numbers they must give."""

import copy
import importlib
import json
import os
import time

import pytest

from kubernetes_tpu.api import Node, Pod
from kubernetes_tpu.config.types import SchedulerConfiguration
from kubernetes_tpu.sched.cache import SchedulerCache
from kubernetes_tpu.sched.fleet import FleetQueue
from kubernetes_tpu.sched.queue import EVENT_NODE_ADD, SchedulingQueue
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.utils.tracing import TRACER
from yardstick import harness, verdicts
from yardstick.generators import upstream_unschedulable as gen
from yardstick.readers import (module_device_ms, series_ratio,
                               span_ms_per_drain)
from yardstick.reference import capacity, key
from yardstick.reference._quantity import amount

from test_antiaffinity_deployment import Served, wait_for
from test_host_account import series
from test_topologyspread_deployment import QueueClock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "unschedulable-5000n.burst"
OLDER_CELLS = ["mixed-5000n.burst", "antiaffinity-5000n.burst",
               "topologyspread-5000n.burst"]
# the six metric files of this deployment and the cells each lists. None:
# the file waits, because its reader finds nothing to read in the cell as
# it is sized (a window of ~2 s: the first retry, 1 s after the first
# attempt, is inside the explainer's re-explain interval of 2 s and is not
# captured, and a judge of ~1,000 pods ends inside the window in one
# traced run of three; PERF.md section 7)
NEW_METRICS = {
    "explain_ms_per_drain.burst": None,
    "handle_failures_ms_per_drain.burst": [CELL],
    "explain_capture_ms_per_drain.burst": None,
    "explain_skipped_share.burst": [CELL],
    "retry_pop_share.burst": OLDER_CELLS + [CELL],
    "explain_step_device_ms.burst": [CELL],
}
LISTED = [n for n, cells in NEW_METRICS.items() if cells is not None]
# entries later PRs appended after this deployment's, each over all four
# cells (PR 38: the encoder's template store; PR 39: the loop's, binders'
# and events sink's self time and spans)
LATER = ["pod_template_hit_share.burst",
         "cycle_self_ms_per_drain.burst",
         "cycle_self_blocked_ms_per_drain.burst",
         "batch_head_ms_per_drain.burst",
         "resolve_head_ms_per_drain.burst",
         "flight_ms_per_drain.burst",
         "bind_events_ms_per_drain.burst",
         "events_flush_ms_per_drain.burst",
         "stage_release_ms_per_drain.burst"]
UNSCHEDULABLE = 'scheduler_schedule_attempts_total{result="unschedulable"}'
DRAINS = "scheduler_pipeline_depth_count"


def incoming(queue: str, event: str) -> str:
    return ("scheduler_queue_incoming_pods_total"
            f'{{event="{event}",queue="{queue}"}}')


def captures(result: str) -> str:
    return f'scheduler_explain_captures_total{{result="{result}"}}'


def load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


CONFIG = load("yardstick", "configs", "unschedulable-5000n.json")
NAMESPACES = CONFIG["namespaces"]


def metric(name: str) -> dict:
    return load("yardstick", "layer_metrics", name + ".json")


def phases(n_nodes: int, **counts) -> tuple:
    """The generator's nodes and pods by phase, each pod in the namespace
    the configuration gives its phase."""
    nodes, by_phase = gen.generate_phases(0, n_nodes, counts)
    for phase, group in by_phase.items():
        for p in group:
            p["metadata"]["namespace"] = NAMESPACES[phase]
    return nodes, by_phase


def rose(before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in series().items()}


def span_facts(spans: list, counters: dict) -> dict:
    """What a traced window hands the readers, from ``spans``."""
    out: dict = {}
    for sp in spans:
        tot = out.setdefault(sp.name, {"ms": 0.0, "n": 0})
        tot["ms"] += (sp.end - sp.start) * 1000.0
        tot["n"] += 1
    return {"spans": out, "counters": counters}


# ------------------------------------------------------- the served path

@pytest.mark.parametrize("n_nodes,n_pending,n_measure", [(12, 20, 24),
                                                         (48, 24, 96)])
def test_pods_of_nine_cpu_stay_pending_while_default_pods_bind(
        n_nodes, n_pending, n_measure):
    nodes, by_phase = phases(n_nodes, measure=n_measure, init=0, warmup=8,
                             pending=n_pending)
    before = series()
    ring_was, TRACER.max_spans = TRACER.max_spans, 100_000
    TRACER.reset()
    try:
        with Served(nodes, NAMESPACES.values()) as dep:
            # the harness's order: the awaited phase, then the pending one,
            # waited for only until each pod was called unschedulable once
            dep.create(NAMESPACES["warmup"], by_phase["warmup"])
            assert wait_for(lambda: dep.bound() == 8), dep.bound()
            dep.create(NAMESPACES["pending"], by_phase["pending"])
            assert wait_for(
                lambda: rose(before)[UNSCHEDULABLE] >= n_pending)
            dep.create(NAMESPACES["measure"], by_phase["measure"])
            assert wait_for(lambda: dep.bound() == 8 + n_measure), dep.bound()
            # three back-off rounds at least (0.05, 0.1, 0.2 s): every
            # pending pod has come back from back-off three times and been
            # turned away a fourth
            assert wait_for(lambda: rose(before)[incoming(
                "active", "BackoffComplete")] >= 3 * n_pending)
            assert wait_for(
                lambda: rose(before)[UNSCHEDULABLE] >= 4 * n_pending)
            dep.runner.scheduler.wait_for_bindings(10.0)
            dep.runner.scheduler.explainer.drain(60.0)
            explained = dep.runner.scheduler.explainer.explanations()
            listed, node_objs = dep.pods(), dep.client.nodes().list()
            # read before the runner stops: stop() settles the drain in
            # flight on the caller's thread
            after, spans = rose(before), TRACER.spans()
    finally:
        TRACER.max_spans = ring_was
        TRACER.reset()
    # the end state, by the benchmark's own judges
    assert len(listed) == 8 + n_pending + n_measure
    assert capacity.check(node_objs, listed) == []
    seen = {key(p): (0.0, p["spec"]["nodeName"]) for p in listed
            if p["spec"].get("nodeName")}
    assert len(seen) == 8 + n_measure
    name, ok, detail, wrong = verdicts.left_pending(
        [key(p) for p in by_phase["pending"]], listed, seen)
    assert (name, ok, wrong) == ("left_pending", True, 0), detail
    # every pending pod was explained by the tensor judge, none by the
    # numpy oracle; upstream's words for what turned it away
    assert after['scheduler_explainer_pods_total{mode="tensor"}'] >= n_pending
    assert after.get('scheduler_explainer_pods_total{mode="oracle"}', 0) == 0
    for p in by_phase["pending"]:
        verdict = explained[key(p)]
        assert verdict["filters"] == {"NodeResourcesFit": n_nodes}, verdict
        assert verdict["message"].startswith(
            f"0/{n_nodes} nodes are available"), verdict
    # a capture in the retry rounds falls inside the re-explain interval
    assert after[captures("accepted")] >= 1
    assert after[captures("throttled")] >= 1
    assert after[captures("skipped")] == 0
    # each new span on the loop's thread, under the parent PERF.md names:
    # a pop deeper than one batch (every pop of the cell) is a drain, and
    # its failures are handled inside scheduler/resolve_tail; a shorter one
    # (a wave whose back-offs ended a moment apart) is one gang batch, whose
    # failures are handled under the cycle itself
    ids = {sp.span_id: sp for sp in spans}
    handled = [sp for sp in spans if sp.name == "scheduler/handle_failures"]
    captured = [sp for sp in spans if sp.name == "explain/capture"]
    assert len(handled) >= 4 and len(captured) == after[captures("accepted")]
    parents = [ids[sp.parent_id].name for sp in handled]
    assert set(parents) <= {"scheduler/resolve_tail", "scheduler/cycle"}
    assert "scheduler/resolve_tail" in parents
    for sp in handled + captured:
        assert sp.thread == "scheduler-loop", sp
        assert sp.attributes["pods"] >= 1
    assert all(ids[sp.parent_id].name == "scheduler/handle_failures"
               for sp in captured)
    assert all(sp.attributes["nodes"] == n_nodes
               and sp.attributes["bound"] >= 8 for sp in captured)
    assert sum(sp.attributes["pods"] for sp in handled) == after[
        UNSCHEDULABLE]
    # a drain that placed everything opened no span
    tails = [sp for sp in spans if sp.name == "scheduler/resolve_tail"]
    assert parents.count("scheduler/resolve_tail") == sum(
        sp.attributes["failed"] > 0 for sp in tails) < len(tails)


# -------------------------------------------------- what the judges catch

def end_state() -> tuple:
    """Two nodes, two pending pods of 9 CPU without a node and six default
    pods bound, three a node: valid, and both pending pods left pending."""
    nodes, by_phase = phases(2, measure=6, init=0, warmup=0, pending=2)
    for i, p in enumerate(by_phase["measure"]):
        p["spec"]["nodeName"] = f"node-{i % 2}"
    return nodes, by_phase["measure"], by_phase["pending"]


def judge(nodes: list, listed: list, pending: list) -> tuple:
    """-> (capacity's problems, left_pending's count of what is wrong)."""
    seen = {key(p): (0.0, p["spec"]["nodeName"]) for p in listed
            if p["spec"].get("nodeName")}
    return (capacity.check(nodes, listed),
            verdicts.left_pending([key(p) for p in pending], listed,
                                  seen)[3])


@pytest.mark.parametrize("mutation,n_capacity,n_pending_wrong", [
    ("none", 0, 0),
    ("a pending pod given a node", 1, 1),
    ("a node overcommitted", 1, 0),
    ("a pending pod deleted", 0, 1),
])
def test_the_judges_catch_what_they_must(mutation, n_capacity,
                                         n_pending_wrong):
    nodes, bound, pending = end_state()
    listed = bound + pending
    kept = copy.deepcopy((nodes, listed))
    if mutation == "a pending pod given a node":
        listed = bound + copy.deepcopy(pending)
        listed[-1]["spec"]["nodeName"] = "node-1"
    elif mutation == "a node overcommitted":
        # 3 + 38 pods of 100m on node-0: 4.1 CPU on a node of 4
        _, more = phases(0, measure=0, init=38, warmup=0, pending=0)
        for p in more["init"]:
            p["spec"]["nodeName"] = "node-0"
        listed = listed + more["init"]
    elif mutation == "a pending pod deleted":
        listed = listed[:-1]
    problems, wrong = judge(nodes, listed, pending)
    assert (len(problems), wrong) == (n_capacity, n_pending_wrong), problems
    assert (nodes, bound + pending) == kept  # the judges write to nothing


# ----------------------------------------------------------- the generator

def test_the_generator_gives_the_configurations_counts_at_full_size():
    """What the harness builds for the cell, before anything runs: 5,000
    nodes, 2,000 pending, 5,000 measured in two bulk creates, 1,024 warm-up
    pods; and the configuration's own statement, 'fits nowhere', held on
    the objects: every pending pod asks for more CPU than any node has."""
    params = load("yardstick", "traffic", "burst.json")
    driver = importlib.import_module("yardstick.drivers.burst")
    world = harness.build(CONFIG, driver, params, 2**31 + 11, 20.0)
    got = {phase: len(group) for phase, group in world["phases"].items()}
    assert (len(world["nodes"]), got) == (5000, {
        "measure": 5000, "init": 0, "warmup": 1024, "pending": 2000})
    assert world["plan"]["groups"] == [(0.0, 2500), (0.0, 2500)]
    assert world["constraints"] == ("capacity",) and world["leavers"] == ()
    for phase, group in world["phases"].items():
        assert {p["metadata"]["namespace"] for p in group} <= {
            NAMESPACES[phase]}
    keys = [key(p) for group in world["phases"].values() for p in group]
    assert len(set(keys)) == len(keys) == 8024

    def cpu(pod):
        return amount("cpu", pod["spec"]["containers"][0]["resources"][
            "requests"]["cpu"])

    roomiest = max(amount("cpu", n["status"]["allocatable"]["cpu"])
                   for n in world["nodes"])
    assert all(cpu(p) > roomiest for p in world["phases"]["pending"])
    # the default pods fit forty to a node: 6,024 of them on 5,000 nodes
    assert all(cpu(p) * 40 <= roomiest for p in world["phases"]["measure"]
               + world["phases"]["warmup"])
    # and the template reaches the program's API types whole
    typed = Pod.from_dict(world["phases"]["pending"][0])
    assert typed.resource_requests()["cpu"] == 9000 > Node.from_dict(
        world["nodes"][0]).allocatable_canonical()["cpu"] == 4000


# --------------------------------------------------------------- the queue

@pytest.mark.parametrize("queue_cls", [SchedulingQueue, FleetQueue])
def test_a_retried_wave_pops_ahead_of_pods_added_after_its_failure(
        monkeypatch, queue_cls):
    """The order the cell measures, pinned: a failed pod draws its place in
    line when it FAILS, so a wave whose back-off ends pops behind the pods
    added before that failure and ahead of every pod added since. And the
    back-off doubles from 1 s to the 10 s cap, by the attempt count."""
    clock = QueueClock()
    monkeypatch.setattr("kubernetes_tpu.sched.queue.time", clock)
    nodes, by_phase = phases(1, measure=2, init=0, warmup=1, pending=2)
    wave = [Pod.from_dict(p) for p in by_phase["pending"]]
    earlier, = [Pod.from_dict(p) for p in by_phase["warmup"]]
    later = [Pod.from_dict(p) for p in by_phase["measure"]]
    q = queue_cls()  # the product's back-off: 1 s, doubling, 10 s at most
    before = series()
    for p in wave:
        q.add(p)
    assert [p.key for p, _ in q.pop_batch(8, wait=0.01)] == [
        p.key for p in wave]
    q.add(earlier)
    for p in wave:
        q.add_unschedulable(p, 1)
    for p in later:
        q.add(p)
    assert q.stats() == {"active": 3, "backoff": 2, "unschedulable": 0}
    clock.ahead += 1.01
    order = [(p.key, attempts) for p, attempts in q.pop_batch(8, wait=0.01)]
    assert order == ([(earlier.key, 0)] + [(p.key, 1) for p in wave]
                     + [(p.key, 0) for p in later])
    # the ladder: the n-th failure waits min(2 ** (n - 1), 10) s
    for attempts, delay in ((2, 2.0), (3, 4.0), (4, 8.0), (5, 10.0),
                            (6, 10.0)):
        for p in wave:
            q.add_unschedulable(p, attempts)
        clock.ahead += delay - 0.05
        assert q.pop_batch(8, wait=0.01) == []
        clock.ahead += 0.06
        assert [a for _, a in q.pop_batch(8, wait=0.01)] == [attempts] * 2
    # each series by the counts the scenario fixes
    got = rose(before)
    assert got[incoming("active", "PodAdd")] == 5
    assert got[incoming("backoff", "ScheduleAttemptFailure")] == 12
    assert got[incoming("active", "BackoffComplete")] == 12
    assert got[incoming("active", "UnschedulableTimeout")] == 0
    spec = metric("retry_pop_share.burst")
    assert series_ratio.read({"counters": got}, spec["args"]) == \
        pytest.approx(12 / 17)
    # the parked map's two ways out, under their own events
    q.park_unschedulable(wave[0], 7)
    q.move_all_to_active_or_backoff(EVENT_NODE_ADD)
    q.park_unschedulable(wave[1], 7)
    clock.ahead += q.unschedulable_timeout + 1.0
    assert len(q.pop_batch(8, wait=0.01)) == 2
    got = rose(before)
    assert got[incoming("unschedulable", "ScheduleAttemptFailure")] == 2
    assert got[incoming("active", "NodeAdd")] == 1
    assert got[incoming("active", "UnschedulableTimeout")] == 1
    assert series_ratio.read({"counters": got}, spec["args"]) == \
        pytest.approx(12 / 19)


# ------------------------------------------- the explainer's three answers

class ExplainerClock:
    """``sched/explainer.py``'s clock in the test's hand: it stands still
    until the test moves it, so a capture is inside or outside the
    re-explain interval because the test says so."""

    def __init__(self):
        self.now = time.time()

    sleep = staticmethod(time.sleep)

    def time(self) -> float:
        return self.now


class Events:
    def __init__(self):
        self.messages = []

    def event(self, pod, kind, reason, message):
        self.messages.append((pod.key, reason, message))


def test_a_capture_is_accepted_throttled_or_skipped_and_counted(monkeypatch):
    """Five pods of 9 CPU and five default pods on four nodes, a bare
    scheduler at batchSize 4 x 2 whose queue and explainer keep the test's
    time. Drain 1: five captured (accepted), the default pods placed. Drain 2, inside the re-explain
    interval: throttled, no capture span. Drain 3, outside it with the
    backlog full: skipped, and the generic event is the fallback. Drain 4:
    accepted again. Every count, span and reader on exactly that."""
    from kubernetes_tpu.sched.explainer import REEXPLAIN_INTERVAL_S
    queue_clock, explainer_clock = QueueClock(), ExplainerClock()
    monkeypatch.setattr("kubernetes_tpu.sched.queue.time", queue_clock)
    monkeypatch.setattr("kubernetes_tpu.sched.explainer.time",
                        explainer_clock)
    nodes, by_phase = phases(4, measure=5, init=0, warmup=0, pending=5)
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(Node.from_dict(n))
    queue = SchedulingQueue(backoff_initial=3600.0, backoff_max=3600.0)
    sched = Scheduler(SchedulerConfiguration(batch_size=4,
                                             max_drain_batches=2),
                      cache, queue, lambda pod, node: True)
    sched.recorder = events = Events()
    before = series()
    TRACER.reset()

    def drain() -> dict:
        while sched.run_once(wait=0.01) or sched._pending:
            pass
        sched.wait_for_bindings(10.0)
        return rose(before)

    try:
        for p in by_phase["pending"] + by_phase["measure"]:
            queue.add(Pod.from_dict(p))
        got = drain()
        assert len(cache.bound_pods(include_assumed=True)) == 5
        assert [got[captures(r)] for r in (
            "accepted", "throttled", "skipped")] == [1, 0, 0]
        queue_clock.ahead += 3601.0          # the back-off is over
        got = drain()
        assert [got[captures(r)] for r in (
            "accepted", "throttled", "skipped")] == [1, 1, 0]
        queue_clock.ahead += 3601.0
        explainer_clock.now += REEXPLAIN_INTERVAL_S + 0.1
        backlog_was = sched.explainer._max_backlog
        sched.explainer._max_backlog = 0     # full, whatever it holds
        got = drain()
        sched.explainer._max_backlog = backlog_was
        assert [got[captures(r)] for r in (
            "accepted", "throttled", "skipped")] == [1, 1, 1]
        assert sched.explainer.skipped == 1
        generic = [m for m in events.messages
                   if m[2].startswith("no node satisfied")]
        assert sorted(k for k, _r, _m in generic) == sorted(
            key(p) for p in by_phase["pending"])
        queue_clock.ahead += 3601.0
        explainer_clock.now += REEXPLAIN_INTERVAL_S + 0.1
        got = drain()
        assert [got[captures(r)] for r in (
            "accepted", "throttled", "skipped")] == [2, 1, 1]
        sched.explainer.drain(60.0)
        got = rose(before)
        spans = TRACER.spans()
    finally:
        sched.close()
        TRACER.reset()
    # five drains: the ten pods pop as 8 and 2, then the three retries
    assert got[UNSCHEDULABLE] == 20 and got[DRAINS] == 5
    assert got[incoming("active", "PodAdd")] == 10
    assert got[incoming("backoff", "ScheduleAttemptFailure")] == 20
    assert got[incoming("active", "BackoffComplete")] == 15
    assert got['scheduler_explainer_pods_total{mode="tensor"}'] == 10
    assert got.get('scheduler_explainer_pods_total{mode="oracle"}', 0) == 0
    assert got.get('scheduler_loop_errors_total{site="explainer"}', 0) == 0
    # the explainer's own events carry upstream's words, one a pod judged
    told = [m for m in events.messages if m[1] == "FailedScheduling"
            and m[2].startswith("0/4 nodes are available")]
    assert len(told) == 10, events.messages
    by_name: dict = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    assert [sp.attributes["pods"] for sp in by_name[
        "scheduler/handle_failures"]] == [5, 5, 5, 5]
    assert [(sp.attributes["pods"], sp.attributes["nodes"],
             sp.attributes["bound"]) for sp in by_name["explain/capture"]
            ] == [(5, 4, 3), (5, 4, 5)]  # the first drain held 5 + 3
    ids = {sp.span_id: sp for sp in spans}
    assert all(ids[sp.parent_id].name == "scheduler/handle_failures"
               for sp in by_name["explain/capture"])
    assert all(ids[sp.parent_id].name == "scheduler/resolve_tail"
               for sp in by_name["scheduler/handle_failures"])
    # each metric file's reader on those facts
    facts = span_facts(spans, got)
    assert series_ratio.read(facts, metric(
        "explain_skipped_share.burst")["args"]) == pytest.approx(1 / 4)
    assert series_ratio.read(facts, metric(
        "retry_pop_share.burst")["args"]) == pytest.approx(15 / 25)
    for name, span in (("handle_failures_ms_per_drain.burst",
                        "scheduler/handle_failures"),
                       ("explain_capture_ms_per_drain.burst",
                        "explain/capture")):
        assert metric(name)["args"]["spans"] == [span]
        assert span_ms_per_drain.read(facts, metric(name)["args"]) == \
            pytest.approx(facts["spans"][span]["ms"] / 5)
    assert span_ms_per_drain.read(facts, metric(
        "explain_ms_per_drain.burst")["args"]) == pytest.approx(
        (facts["spans"]["explain/judge"]["ms"]
         + facts["spans"].get("explain/publish", {"ms": 0.0})["ms"]) / 5)
    # the capture lies inside handle_failures: the two are never summed
    assert facts["spans"]["explain/capture"]["ms"] <= facts["spans"][
        "scheduler/handle_failures"]["ms"]


# ------------------------------------------------------------ the benchmark

@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_file_agrees_with_its_entry_and_reads(name):
    spec = metric(name)
    entries = [m for m in load("BENCHMARK.json")["per_layer"]
               if m["name"] == name]
    if NEW_METRICS[name] is None:
        assert entries == []  # the file waits for a cell
    else:
        entry, = entries
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert entry[k] == spec[k], k
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["workloads"] == NEW_METRICS[name]
    assert spec["kinds"] == ["burst"] and spec["moves"] == "bound_rate"
    reader = importlib.import_module(f"yardstick.readers.{spec['reader']}")
    # a window in which nothing moved, and a program that has no such span
    # or series (the parent): nothing, never a number and never an error
    assert reader.read({"counters": {}, "spans": {}, "trace": None},
                       spec["args"]) is None
    if spec["reader"] == "series_ratio":
        exposed = series()
        for s in spec["args"]["num"]:
            assert s in exposed, s  # from import, at 0
        assert set(spec["args"]["num"]) <= set(spec["args"]["den"])
    if spec["reader"] == "module_device_ms":
        trace = {"chips": 1, "modules": {
            "jit_explain_step(1234)": {"s": 0.024, "n": 8},
            "jit_drain_step(99)": {"s": 0.3, "n": 9}}}
        assert module_device_ms.read({"trace": trace}, spec["args"]) == \
            pytest.approx(3.0)


def test_retry_pop_share_reads_zero_where_nothing_backs_off():
    """The three older cells: pods are added and none comes back."""
    spec = metric("retry_pop_share.burst")
    window = dict.fromkeys(spec["args"]["den"], 0.0)
    window[incoming("active", "PodAdd")] = 10000.0
    assert series_ratio.read({"counters": window}, spec["args"]) == 0.0
    # and the explainer's share has no denominator there: nothing
    assert series_ratio.read(
        {"counters": dict.fromkeys(metric(
            "explain_skipped_share.burst")["args"]["den"], 0.0)},
        metric("explain_skipped_share.burst")["args"]) is None


def test_the_benchmark_lists_the_cell_the_configuration_and_its_metrics():
    bench = load("BENCHMARK.json")
    assert [w["name"] for w in bench["workloads"]] == OLDER_CELLS + [CELL]
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "unschedulable-5000n", "burst", 1)
    entry = bench["configs"][-1]
    assert entry["name"] == "unschedulable-5000n"
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == []
    assert load(entry["file"]) == CONFIG
    rate, = [m for m in bench["end_to_end"] if m["name"] == "bound_rate"]
    assert rate["workloads"] == OLDER_CELLS + [CELL]
    listed = {m["name"]: m for m in bench["per_layer"]}
    # the new entries came last, in the order ISSUE 37 gives them; what
    # later PRs appended comes after them
    assert [m["name"] for m in bench["per_layer"]][26:] == LISTED + LATER
    # every metric the benchmark had reads something in this cell, and so
    # does every later one
    older = [m for name, m in listed.items() if name not in NEW_METRICS]
    assert len(older) == 26 + len(LATER)
    assert all(m["workloads"] == OLDER_CELLS + [CELL] for m in older)
    # and the burst files that still wait for a cell are the two above
    burst = {p[:-len(".json")] for p in os.listdir(os.path.join(
        ROOT, "yardstick", "layer_metrics")) if p.endswith(".burst.json")}
    assert burst - set(listed) == {
        n for n, cells in NEW_METRICS.items() if cells is None}


def test_the_configuration_states_what_the_cell_is_held_to():
    assert (CONFIG["nodes"], CONFIG["initPods"], CONFIG["pendingPods"],
            CONFIG["measurePods"], CONFIG["warmupPods"]) == (
        5000, 0, 2000, 5000, 1024)
    assert CONFIG["generator"] == "upstream_unschedulable"
    # the product's back-off, explainer and sampling: nothing set here
    assert CONFIG["scheduler"] == load(
        "yardstick", "configs", "mixed-5000n.json")["scheduler"] == {
        "batchSize": 512, "maxDrainBatches": 2, "pipelineDepth": 2}
    assert NAMESPACES == {"measure": "sched-1", "init": "sched-0",
                          "warmup": "warmup", "pending": "sched-0"}
    assert len(CONFIG["guarantees"]) == 6
    assert any("was never seen bound" in g for g in CONFIG["guarantees"])
    assert any("one left pending is failed" in g
               for g in CONFIG["guarantees"])
    assert len(CONFIG["source"]) <= 200 and "Unschedulable" in CONFIG[
        "source"] and "5000Nodes/2000InitPods" in CONFIG["source"]
    assert CONFIG["reduced"] == [] and "leavers" not in CONFIG
    assert {"warmupPods", "measurePods", "templates", "node",
            "scheduler"} <= set(CONFIG["assumed"])
