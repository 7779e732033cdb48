"""The topologyspread-5000n deployment at a small size, on the CPU: upstream
scheduler_perf TopologySpreading's own pods (the benchmark's generator,
namespaces as its configuration names them) through the served path, judged
by the benchmark's plain reference. Every measured pod is ``color: blue``
and must stay within ``maxSkew: 5`` of the others over three zones
(``DoNotSchedule``); the initial pods carry no ``color`` label and count
for nothing. The gang step commits into a zone what ``maxSkew`` leaves room
for a round (PR 35), so at ``maxSkew: 5`` these batches place everything
inside the round cap. A batch that does need more rounds than
``maxGangRounds`` — ``maxSkew: 1``, one pod a zone a round — still hands pods
that fit to the unschedulable, explain, back-off, retry loop: the counter
for that, the loop and its spans are held here at ``maxSkew: 1``."""

import copy
import json
import os
import time

import pytest

from kubernetes_tpu.api import Node, Pod
from kubernetes_tpu.config.types import Profile, SchedulerConfiguration
from kubernetes_tpu.metrics.registry import (GANG_ROUNDS_EXHAUSTED,
                                             SCHEDULE_ATTEMPTS)
from kubernetes_tpu.sched.cache import SchedulerCache
from kubernetes_tpu.sched.queue import SchedulingQueue
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.utils.tracing import TRACER
from yardstick.generators import upstream_topology_spreading as gen
from yardstick.generators._objects import ZONE, node
from yardstick.reference import capacity, topologyspread

from test_antiaffinity_deployment import Served, wait_for
from test_host_account import series

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "topologyspread-5000n.burst"
NEW_METRICS = ("gang_rounds_exhausted_share.burst",
               "unschedulable_per_kpod.burst", "explain_ms_per_drain.burst")
UNSCHEDULABLE = {"result": "unschedulable"}


def load(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


CONFIG = load("yardstick", "configs", "topologyspread-5000n.json")
NAMESPACES = CONFIG["namespaces"]


def in_namespace(pods: list, ns: str) -> list:
    for p in pods:
        p["metadata"]["namespace"] = ns
    return pods


def with_max_skew(pods: list, max_skew: int) -> list:
    """The generator's pods (never edited: it is the benchmark's) with
    ``maxSkew`` rewritten on their dicts."""
    for p in pods:
        for c in p["spec"].get("topologySpreadConstraints", ()):
            c["maxSkew"] = max_skew
    return pods


def blue_by_zone(listed: list, node_objs: list) -> dict:
    zone_of = {n["metadata"]["name"]: n["metadata"]["labels"].get(ZONE)
               for n in node_objs}
    out = dict.fromkeys(gen.MOONS, 0)
    for p in listed:
        if (p["spec"].get("nodeName")
                and (p["metadata"].get("labels") or {}) == gen.BLUE):
            out[zone_of[p["spec"]["nodeName"]]] += 1
    return out


# ------------------------------------------------------- the served path

@pytest.mark.parametrize("n_nodes,n_blue", [(12, 24), (48, 96)])
def test_blue_pods_keep_the_skew_through_the_served_path(n_nodes, n_blue):
    nodes, pods = gen.build(n_nodes, n_blue, n_nodes)
    measure, init = pods[:n_blue], pods[n_blue:]
    with Served(nodes, NAMESPACES.values()) as dep:
        # initial pods are scheduled, not pre-bound (upstream's createPods)
        dep.create(NAMESPACES["init"], init)
        assert wait_for(lambda: dep.bound() == len(init)), dep.bound()
        dep.create(NAMESPACES["measure"], measure)
        assert wait_for(lambda: dep.bound() == len(pods)), dep.bound()
        dep.runner.scheduler.wait_for_bindings(10.0)
        listed, node_objs = dep.pods(), dep.client.nodes().list()
    # the benchmark's plain reference, every bound pod
    assert capacity.check(node_objs, listed) == []
    assert topologyspread.check(node_objs, listed) == []
    zones = blue_by_zone(listed, node_objs)
    assert sum(zones.values()) == n_blue
    assert max(zones.values()) - min(zones.values()) <= gen.MAX_SKEW, zones


@pytest.mark.parametrize("disabled,valid", [((), True),
                                            (("PodTopologySpread",), False)])
def test_the_reference_sees_the_filter_left_out(disabled, valid):
    """moon-2's and moon-3's nodes hold four plain pods each, moon-1's are
    empty and score best. With the filter, 18 blue pods still end within
    the skew; with ``PodTopologySpread`` left out of the profile's filters
    they follow the score into moon-1, and the reference says so: the
    comparison is tight enough to see the mechanism left out."""
    nodes, pods = gen.build(12, 18, 32)
    measure, plain = pods[:18], pods[18:]
    busy = [n["metadata"]["name"] for n in nodes
            if n["metadata"]["labels"][ZONE] != "moon-1"]
    for i, p in enumerate(plain):
        p["spec"]["nodeName"] = busy[i % len(busy)]
    with Served(nodes, NAMESPACES.values(), profiles=[
            Profile(disabled_filters=list(disabled))]) as dep:
        dep.create(NAMESPACES["init"], plain)
        assert wait_for(lambda: dep.bound() == len(plain))
        dep.create(NAMESPACES["measure"], measure)
        assert wait_for(lambda: dep.bound() == len(pods)), dep.bound()
        dep.runner.scheduler.wait_for_bindings(10.0)
        listed, node_objs = dep.pods(), dep.client.nodes().list()
    assert capacity.check(node_objs, listed) == []
    problems = topologyspread.check(node_objs, listed)
    zones = blue_by_zone(listed, node_objs)
    if valid:
        assert problems == [], problems
        assert max(zones.values()) - min(zones.values()) <= gen.MAX_SKEW
    else:
        assert len(problems) == 1 and "maxSkew 5" in problems[0], problems
        assert zones["moon-1"] > min(zones.values()) + gen.MAX_SKEW, zones


# ----------------------------------------------------------- the reference

def end_state(counts=(4, 3, 3)) -> tuple:
    """Six nodes over three zones, one more without the key, and bound blue
    pods of sched-1, ``counts`` of them a zone: valid under maxSkew 5."""
    nodes, _ = gen.build(6, 0, 0)
    nodes.append(node("bare", {"cpu": "4", "memory": "32Gi", "pods": "110"}))
    _, pods = gen.build(0, sum(counts), 0)
    at = iter(in_namespace(pods, "sched-1"))
    for zone, n in enumerate(counts):
        for _ in range(n):
            next(at)["spec"]["nodeName"] = f"node-{zone}"
    return nodes, pods


def more_blue(n: int, ns: str, node_name=None) -> list:
    """``n`` further constrained blue pods of ``ns``, on ``node_name``."""
    _, pods = gen.build(0, n, 0)
    for i, p in enumerate(in_namespace(pods, ns)):
        p["metadata"]["name"] = f"more-{ns}-{i}"
        if node_name is not None:
            p["spec"]["nodeName"] = node_name
    return pods


def test_the_end_state_the_mutations_start_from_is_valid():
    nodes, pods = end_state()
    kept = copy.deepcopy((nodes, pods))
    assert topologyspread.check(nodes, pods) == []
    assert capacity.check(nodes, pods) == []
    assert (nodes, pods) == kept  # the reference writes to nothing it gets


@pytest.mark.parametrize("n,ns,where,n_problems", [
    pytest.param(4, "sched-1", "node-0", 0, id="moon-1 up to the skew"),
    pytest.param(5, "sched-1", "node-0", 1, id="moon-1 one past the skew"),
    pytest.param(1, "sched-1", "bare", 1, id="on a node without the key"),
    # (4, 3, 3) in sched-1 and (5, 0, 0) elsewhere: each within its own skew
    pytest.param(5, "elsewhere", "node-0", 0, id="five in another namespace"),
    pytest.param(5, "sched-1", "node-3", 1, id="the same five in sched-1"),
    pytest.param(6, "sched-1", None, 0, id="six unbound"),
    pytest.param(6, "sched-1", "node-0", 1, id="the same six bound"),
])
def test_the_reference_catches_what_it_must(n, ns, where, n_problems):
    """From (4, 3, 3): ``n`` more blue pods of ``ns`` on ``where``."""
    nodes, pods = end_state()
    problems = topologyspread.check(nodes, pods + more_blue(n, ns, where))
    assert len(problems) == n_problems, problems


@pytest.mark.parametrize("how", ["a matched pod without the constraint",
                                 "minDomains", "a node selector"])
def test_the_reference_refuses_what_an_end_state_cannot_settle(how):
    nodes, pods = end_state()
    if how == "minDomains":
        pods[0]["spec"]["topologySpreadConstraints"][0]["minDomains"] = 3
    elif how == "a node selector":
        pods[0]["spec"]["nodeSelector"] = {ZONE: "moon-1"}
    else:
        del pods[0]["spec"]["topologySpreadConstraints"]
    with pytest.raises(ValueError, match="topologyspread.py"):
        topologyspread.check(nodes, pods)


# ----------------------------------------------------------- the generator

@pytest.mark.parametrize("config", ["topologyspread-5000n",
                                    "rehearsal-topologyspread"])
def test_the_generator_gives_the_configurations_split(config):
    """The harness slices one list measure | init | warmup: the first
    ``measurePods`` carry the constraint, the rest nothing, and the count
    comes from the configuration that names this generator."""
    c = load("yardstick", "configs", config + ".json")
    assert c["generator"] == "upstream_topology_spreading"
    n_measure, n_init = int(c["measurePods"]), int(c["initPods"])
    total = n_measure + n_init + int(c["warmupPods"])
    nodes, pods = gen.generate(7, int(c["nodes"]), total)
    assert (nodes, pods) == gen.generate(8, int(c["nodes"]), total)
    assert len(nodes) == c["nodes"] and len(pods) == total
    assert [n["metadata"]["labels"][ZONE] for n in nodes[:4]] == [
        "moon-1", "moon-2", "moon-3", "moon-1"]
    assert len({n["metadata"]["name"] for n in nodes}) == len(nodes)
    constraint = {"maxSkew": 5, "topologyKey": ZONE,
                  "whenUnsatisfiable": "DoNotSchedule",
                  "labelSelector": {"matchLabels": {"color": "blue"}}}
    for p in pods[:n_measure]:
        assert p["metadata"]["labels"] == {"color": "blue"}
        assert p["spec"]["topologySpreadConstraints"] == [constraint]
    for p in pods[n_measure:]:
        assert "labels" not in p["metadata"]
        assert "topologySpreadConstraints" not in p["spec"]
    requests = {"cpu": "100m", "memory": "500Mi"}
    assert all(p["spec"]["containers"][0]["resources"]["requests"]
               == requests for p in pods)
    assert gen.CONSTRAINTS == ("capacity", "topologyspread")


def test_sizes_no_configuration_has_are_refused():
    with pytest.raises(ValueError, match="one configuration's sizes"):
        gen.generate(0, 40, 90)


def test_the_template_reaches_the_programs_api_types():
    _, pods = gen.build(0, 1, 0)
    c, = Pod.from_dict(pods[0]).to_dict()["spec"][
        "topologySpreadConstraints"]
    assert (c["maxSkew"], c["topologyKey"], c["whenUnsatisfiable"]) == (
        5, ZONE, "DoNotSchedule")
    assert c["labelSelector"]["matchLabels"] == {"color": "blue"}


# ------------------------------------------------------------- the counter

UNSCHEDULABLE_SERIES = 'scheduler_schedule_attempts_total{result="unschedulable"}'


class QueueClock:
    """``sched/queue.py``'s clock in the test's hand: the real one plus
    ``ahead``. A back-off ends when the test says so, not when a loaded
    machine takes longer over a drain than the back-off lasts."""

    def __init__(self):
        self.ahead = 0.0

    def time(self) -> float:
        return time.time() + self.ahead


class NineBluePods:
    """Six nodes over three zones and nine blue pods at ``max_skew`` in the
    queue of a bare scheduler at batchSize 8 x 2 and maxGangRounds 2: one
    drain of two batches, 8 and 1. The back-off is an hour on ``clock``."""

    def __init__(self, monkeypatch, max_skew: int):
        self.clock = QueueClock()
        monkeypatch.setattr("kubernetes_tpu.sched.queue.time", self.clock)
        nodes, pods = gen.build(6, 9, 0)
        self.cache = SchedulerCache()
        for n in nodes:
            self.cache.add_node(Node.from_dict(n))
        self.queue = SchedulingQueue(backoff_initial=3600.0,
                                     backoff_max=3600.0)
        self.cfg = SchedulerConfiguration(batch_size=8, max_drain_batches=2,
                                          max_gang_rounds=2)
        self.sched = Scheduler(self.cfg, self.cache, self.queue,
                               lambda pod, node: True)
        self.before = series()
        for p in with_max_skew(in_namespace(pods, "sched-1"), max_skew):
            self.queue.add(Pod.from_dict(p))

    def rose(self) -> dict:
        return {k: v - self.before.get(k, 0.0) for k, v in series().items()}

    def placed(self) -> int:
        return len(self.cache.bound_pods(include_assumed=True))

    def drain_what_is_due(self) -> int:
        """Run the loop until the queue has nothing due and no drain is in
        flight. -> pods placed so far."""
        while self.sched.run_once(wait=0.01) or self.sched._pending:
            pass
        self.sched.wait_for_bindings(10.0)
        return self.placed()


def test_a_batch_out_of_rounds_is_counted_once_and_its_pods_bind_later(
        monkeypatch):
    """At maxSkew 1 a round commits one blue pod a zone, so maxGangRounds 2
    over three zones commits at most 6 a batch (fewer when a zone gets no
    proposal in a round). A drain of 9 in batches of 8 and 1: the first
    ends out of rounds with pods unplaced and is counted, once; the second
    places its pod and is not, though it too reads maxGangRounds rounds (its
    placing round and the dead one). The pods left over are unschedulable
    attempts, come back from back-off and bind in later drains.

    Unsteady under six workers until PR 35. The back-off was 0.5 s of the
    wall clock, and the ``run_once`` that resolves the first drain hands the
    failures back first and then goes on (apply, bind) for 0.35 s alone and
    0.56 s beside ten busy processes: past 0.5 s the next pop found the
    three pods due, a second drain placed them inside the first loop, and
    ``placed`` read 9 and the counters two drains. The back-off now ends
    when the test moves the queue's clock."""
    from yardstick.readers import series_ratio
    dep = NineBluePods(monkeypatch, max_skew=1)
    # exposed from import, at whatever earlier tests of this process left
    assert "scheduler_gang_rounds_exhausted_total" in dep.before
    assert UNSCHEDULABLE_SERIES in dep.before
    try:
        placed = dep.drain_what_is_due()
        assert 4 <= placed <= 7, placed  # 3 a round at most, and the 1
        first = dep.rose()
        assert first["scheduler_gang_rounds_exhausted_total"] == 1
        assert first["scheduler_gang_rounds_count"] == 2
        assert first["scheduler_gang_rounds_sum"] == 2 * dep.cfg.max_gang_rounds
        assert first[UNSCHEDULABLE_SERIES] == 9 - placed
        share = load("yardstick", "layer_metrics",
                     "gang_rounds_exhausted_share.burst.json")
        assert series_ratio.read({"counters": first}, share["args"]) == 0.5
        # a program that keeps no such series (the parent) reads nothing
        assert series_ratio.read(
            {"counters": {k: v for k, v in first.items()
                          if "exhausted" not in k}}, share["args"]) is None
        for _ in range(9):       # a pass places one pod at least
            if dep.placed() == 9:
                break
            dep.clock.ahead += 3601.0   # every back-off is over
            dep.drain_what_is_due()
        assert dep.placed() == 9
    finally:
        dep.sched.close()
    after = dep.rose()
    assert after["scheduler_gang_rounds_exhausted_total"] >= 1
    assert after[UNSCHEDULABLE_SERIES] >= 9 - placed
    per_k = load("yardstick", "layer_metrics",
                 "unschedulable_per_kpod.burst.json")
    assert series_ratio.read({"counters": after}, per_k["args"]) == \
        pytest.approx(after[UNSCHEDULABLE_SERIES] / 9 * 1000.0)


def test_at_the_cells_maxskew_the_same_batches_place_everything(monkeypatch):
    """The twin at maxSkew 5, the cell's own: the same nine pods, the same
    two rounds. A zone takes five a round, so the batch of 8 is placed in its
    first round and nothing is out of rounds, nothing is called
    unschedulable, nothing waits out a back-off."""
    dep = NineBluePods(monkeypatch, max_skew=gen.MAX_SKEW)
    try:
        assert dep.drain_what_is_due() == 9
    finally:
        dep.sched.close()
    rose = dep.rose()
    assert rose["scheduler_gang_rounds_exhausted_total"] == 0
    assert rose[UNSCHEDULABLE_SERIES] == 0
    # two batches, each its placing round and the dead one
    assert rose["scheduler_gang_rounds_count"] == 2
    assert rose["scheduler_gang_rounds_sum"] == 4


def test_pods_cut_by_the_round_limit_are_explained_backed_off_and_bound():
    """The loop a batch out of rounds works, at a small size: batches of 16
    blue pods at maxSkew 1 (one a zone a round) and maxGangRounds 2 place 6
    at most and call the rest unschedulable though they fit; the explainer
    judges them on its own thread, they back off, come round again and every
    one is bound within the skew. (At the cell's maxSkew 5 these batches
    place everything and none of this is reached: the twin above.)"""
    from yardstick.readers import span_ms_per_drain
    nodes, pods = gen.build(12, 36, 0)
    with_max_skew(pods, 1)
    exhausted0 = GANG_ROUNDS_EXHAUSTED.get()
    unsched0 = SCHEDULE_ATTEMPTS.get(UNSCHEDULABLE)
    ring_was, TRACER.max_spans = TRACER.max_spans, 100_000
    TRACER.reset()
    try:
        with Served(nodes, NAMESPACES.values(), max_gang_rounds=2) as dep:
            dep.create(NAMESPACES["measure"], pods)
            assert wait_for(lambda: dep.bound() == len(pods)), dep.bound()
            dep.runner.scheduler.wait_for_bindings(10.0)
            assert wait_for(lambda: TRACER.spans("explain/publish"), 30.0)
            dep.runner.scheduler.recorder.flush()
            failed = [e for e in dep.client.resource("events", None).list()
                      if e.get("reason") == "FailedScheduling"]
            listed, node_objs = dep.pods(), dep.client.nodes().list()
        spans = TRACER.spans()
    finally:
        TRACER.max_spans = ring_was
        TRACER.reset()
    assert GANG_ROUNDS_EXHAUSTED.get() > exhausted0
    assert SCHEDULE_ATTEMPTS.get(UNSCHEDULABLE) > unsched0
    assert failed
    assert topologyspread.check(node_objs, listed) == []
    assert capacity.check(node_objs, listed) == []
    # the explainer's spans, on its thread, under the names the metric reads
    spec = load("yardstick", "layer_metrics", "explain_ms_per_drain.burst.json")
    by_name: dict = {}
    for sp in spans:
        if sp.name.startswith("explain/"):
            by_name.setdefault(sp.name, []).append(sp)
    assert set(spec["args"]["spans"]) <= set(by_name), sorted(by_name)
    # (explain/capture, PR 37, is the scheduling thread's half: the cache
    # snapshots a capture takes on the loop, or on the thread that stops it)
    captured = by_name.pop("explain/capture")
    assert all(sp.thread in ("scheduler-loop", "MainThread")
               for sp in captured)
    assert all(sp.thread == "sched-explainer"
               for group in by_name.values() for sp in group)
    # explain/encode and explain/dispatch lie inside explain/judge: the
    # metric reads judge and publish, and counts no millisecond twice
    # (a judge the runner's stop cut short has closed children and is not
    # in the ring itself: at maxSkew 1 the explainer is rarely idle)
    judged = by_name["explain/judge"]
    last_judged = max(j.end for j in judged)
    for inner in by_name.get("explain/encode", []) + by_name.get(
            "explain/dispatch", []):
        assert inner.start >= last_judged or any(
            j.start <= inner.start and inner.end <= j.end
            for j in judged), inner.name
    facts = {"counters": {"scheduler_pipeline_depth_count": 4.0},
             "spans": {name: {"ms": sum(s.end - s.start for s in group)
                              * 1000.0, "n": len(group)}
                       for name, group in by_name.items()}}
    want = (facts["spans"]["explain/judge"]["ms"]
            + facts["spans"]["explain/publish"]["ms"]) / 4.0
    assert span_ms_per_drain.read(facts, spec["args"]) == pytest.approx(want)


# ------------------------------------------------------------ the benchmark

def test_the_benchmark_lists_the_cell_where_it_reports():
    bench = load("BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "topologyspread-5000n", "burst", 1)
    entry, = [c for c in bench["configs"]
              if c["name"] == "topologyspread-5000n"]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == []
    assert load(entry["file"]) == CONFIG
    rate, = [m for m in bench["end_to_end"] if m["name"] == "bound_rate"]
    assert CELL in rate["workloads"]
    burst = {os.path.basename(p)[:-len(".json")]
             for p in os.listdir(os.path.join(ROOT, "yardstick",
                                              "layer_metrics"))
             if p.endswith(".burst.json")}
    listed = {m["name"]: m for m in bench["per_layer"]}
    # the contract's rule (yardstick/tests/test_yardstick_contract.py): an
    # entry lists the cells in which its reader finds something to read,
    # and a metric file without an entry waits for a cell
    assert set(listed) <= burst
    for name in ("gang_rounds_exhausted_share.burst",
                 "unschedulable_per_kpod.burst"):
        assert CELL in listed[name]["workloads"]
    # since PR 35 every blue pod is placed on its first attempt, so the
    # failure path and the explainer sleep here as in the two older cells
    sleeping = [m for m in listed.values() if CELL not in m["workloads"]]
    assert all(m["layer"] in ("explain", "dispatch") for m in sleeping)
    # its metric lists no cell: a judge outlasts the one window that holds
    # pods that fit nowhere (tests/test_unschedulable_deployment.py)
    assert "explain_ms_per_drain.burst" in burst - set(listed)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_file_loads_and_agrees_with_its_entry(name):
    import importlib
    spec = load("yardstick", "layer_metrics", name + ".json")
    # a file without an entry waits for a cell; one with an entry agrees
    # with it, and lists this cell only if its reader finds something here
    # (the explainer's does not since PR 35)
    for entry in load("BENCHMARK.json")["per_layer"]:
        if entry["name"] == name:
            for k in ("name", "unit", "better", "source", "layer", "moves"):
                assert entry[k] == spec[k], k
            assert (CELL in entry["workloads"]) == (
                name != "explain_ms_per_drain.burst")
    assert spec["kinds"] == ["burst"] and spec["moves"] == "bound_rate"
    reader = importlib.import_module(f"yardstick.readers.{spec['reader']}")
    # on a window in which nothing moved: a ratio over 0 drains or 0
    # batches is nothing, never a number
    assert reader.read({"counters": {}, "spans": {}}, spec["args"]) is None
    if spec["reader"] == "series_ratio":
        exposed = series()
        for s in spec["args"]["num"]:
            assert s in exposed, s  # from import, at 0


def test_the_configuration_states_what_the_cell_is_held_to():
    assert (CONFIG["nodes"], CONFIG["initPods"], CONFIG["measurePods"],
            CONFIG["warmupPods"]) == (5000, 5000, 2000, 0)
    assert CONFIG["scheduler"] == load(
        "yardstick", "configs", "antiaffinity-5000n.json")["scheduler"]
    assert len(CONFIG["guarantees"]) == 4 and "maxSkew" in CONFIG[
        "guarantees"][1]
    assert len(CONFIG["source"]) <= 200 and CONFIG["reduced"] == []
    assert {"measurePods", "template", "node"} <= set(CONFIG["assumed"])
