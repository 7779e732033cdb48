"""The host's account of a served window: which thread held which span,
which thread had the CPU, how long pods waited in the queue and the loop
for pods — the series and spans the benchmark's per-layer metrics read
(PERF.md section 3, table "span or counter -> thread -> metric")."""

import gc
import re
import threading
import time

import pytest

from kubernetes_tpu.client.clientset import HTTPClient
from kubernetes_tpu.config.types import SchedulerConfiguration
from kubernetes_tpu.metrics.registry import (QUEUE_WAIT, REGISTRY, Histogram,
                                             Registry, series_lines)
from kubernetes_tpu.sched.fleet import FleetQueue
from kubernetes_tpu.sched.queue import SchedulingQueue
from kubernetes_tpu.sched.runner import SchedulerRunner
from kubernetes_tpu.store.apiserver import APIServer
from kubernetes_tpu.testing.wrappers import make_node, make_pod
from kubernetes_tpu.utils.tracing import FLIGHT, TRACER

# table C of ISSUE 25: span -> thread it runs on, for every span that can
# fire on one device
SPAN_THREADS = {
    "scheduler/pop_wait": "scheduler-loop",
    "scheduler/cycle": "scheduler-loop",
    "scheduler/drain_gate": "scheduler-loop",
    "scheduler/stack_batch": "scheduler-loop",
    "scheduler/parity_capture": "scheduler-loop",
    "scheduler/parity_submit": "scheduler-loop",
    "scheduler/resolve_tail": "scheduler-loop",
    "scheduler/encode_pods": "scheduler-loop",
    "scheduler/stage_batch": "scheduler-loop",
    "scheduler/gang_dispatch": "scheduler-loop",
    "scheduler/resolve_wait": "scheduler-loop",
    "scheduler/apply": "scheduler-loop",
    "scheduler/resolver_fetch": "drain-resolver",
    "scheduler/bind_bulk": "binder-",
    "scheduler/bind_call": "binder-",
    # the failure path (PR 37): only a drain that left a pod unplaced
    "scheduler/handle_failures": "scheduler-loop",
    "explain/capture": "scheduler-loop",
    # the loop's and the binders' work that had no span (PR 39)
    "scheduler/batch_head": "scheduler-loop",
    "scheduler/resolve_head": "scheduler-loop",
    "scheduler/stage_release": "scheduler-loop",
    "scheduler/flight": ("scheduler-loop", "binder-"),
    "scheduler/bind_events": "binder-",
    "events/flush": "events/",
}

_SERIES = re.compile(r"^([^#\s]+)\s+(\S+)$")
# pods of the served run that fit nowhere: more than a batch, so that they
# are part of a drain whatever the pop that takes them
STUCK = 10
UNSCHEDULABLE = 'scheduler_schedule_attempts_total{result="unschedulable"}'


def series() -> dict:
    """The exposition as the benchmark reads it (yardstick/program.py)."""
    out = {}
    for line in REGISTRY.expose_text().splitlines():
        m = _SERIES.match(line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def wait_for(pred, timeout=60.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return bool(pred())


# ---------------------------------------------------------------- registry

def test_registry_collector_is_called_at_exposition_only():
    r = Registry()
    calls = []

    @r.collector
    def lines():
        calls.append(1)
        return series_lines("thing_total", "counter", "help", "who",
                            {"a b": 1.5, 'q"uote': 2})

    r.collector(lines)  # registering twice exposes once
    r.counter("plain_total").inc()
    assert calls == []
    text = r.expose_text()
    assert calls == [1]
    assert 'thing_total{who="a b"} 1.5' in text
    assert 'thing_total{who="q\\"uote"} 2' in text
    assert text.count("# TYPE thing_total counter") == 1
    assert "plain_total 1.0" in text

    # a collector that raises costs its own lines, not the scrape
    r.collector(lambda: 1 / 0)
    assert "plain_total 1.0" in r.expose_text()


def test_observe_many_equals_observe_one_by_one():
    one, many = Histogram("h"), Histogram("h")
    values = [0.0, 0.001, 0.0015, 0.3, 0.31, 1.0, 59.0, 500.0]
    for v in values:
        one.observe(v)
    many.observe_many(values)
    many.observe_many([])
    assert one.expose() == many.expose()
    assert many.count() == len(values)


def test_cpu_series_name_every_live_thread():
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(1000))

    t = threading.Thread(target=spin, name="spinner-under-test", daemon=True)
    t.start()
    try:
        key = 'scheduler_thread_cpu_seconds_total{thread="spinner-under-test"}'
        first = series()
        assert wait_for(lambda: series().get(key, 0.0) > first.get(key, 0.0)
                        + 0.02, timeout=10.0)
        second = series()
        assert second["process_cpu_seconds_total"] > \
            first["process_cpu_seconds_total"]
        assert second[key] <= second["process_cpu_seconds_total"]
    finally:
        stop.set()
        t.join(5.0)
    assert not t.is_alive()
    # a thread that is gone takes its line along: absent, never 0
    assert key not in series()


# ------------------------------------------------------------------- queue

@pytest.mark.parametrize("queue_cls", [SchedulingQueue, FleetQueue])
def test_queue_wait_counts_one_observation_a_popped_pod(queue_cls):
    q = queue_cls()
    for i in range(5):
        q.add(make_pod(f"p{i}").obj())
    q.add(make_pod("gone").obj())
    q.delete_key("default/gone")  # lazily deleted: never popped, never seen
    time.sleep(0.05)
    n0, s0 = QUEUE_WAIT.count(), series().get(
        "scheduler_queue_wait_seconds_sum", 0.0)
    assert len(q.pop_batch(3, wait=0.1)) == 3
    assert QUEUE_WAIT.count() == n0 + 3
    assert len(q.pop_batch(10, wait=0.1)) == 2
    assert QUEUE_WAIT.count() == n0 + 5
    assert q.pop_batch(10, wait=0.01) == []
    assert QUEUE_WAIT.count() == n0 + 5
    waited = series()["scheduler_queue_wait_seconds_sum"] - s0
    assert 5 * 0.05 <= waited < 5 * 5.0


# ------------------------------------------------------------- served path

@pytest.fixture(scope="module")
def served():
    """A small served run through the drain path with every drain sampled
    by the sentinel: 4 nodes, 40 pods in two bulk creates, the second with
    ten more that fit nowhere (100 CPU on nodes of 16): the failure path
    runs once, and the pods' back-off outlasts the test."""
    server = APIServer().start()
    client = HTTPClient(server.url)
    for i in range(4):
        client.nodes().create(
            make_node(f"n{i}").capacity(
                {"cpu": "16", "memory": "32Gi", "pods": "64"})
            .label("kubernetes.io/hostname", f"n{i}").obj().to_dict())
    runner = SchedulerRunner(HTTPClient(server.url), SchedulerConfiguration(
        batch_size=8, max_drain_batches=2, parity_sample_every=1,
        backoff_initial_s=600.0, backoff_max_s=600.0))
    # the informer and encoder collectors sum over weakly held objects: one
    # that an earlier test of this process left unreachable must drop out
    # BEFORE the first scrape, not between the two
    gc.collect()
    before = series()
    TRACER.reset()
    flight_was, FLIGHT.enabled = FLIGHT.enabled, True
    runner.start()
    try:
        pods = client.pods("default")
        for wave in range(2):
            pods.create_many([
                make_pod(f"w{wave}-p{i}").req({"cpu": "100m"})
                .obj().to_dict() for i in range(20)] + [
                make_pod(f"stuck-p{i}").req({"cpu": "100"})
                .obj().to_dict() for i in range(STUCK * wave)])
            assert wait_for(lambda: sum(
                1 for p in pods.list() if p["spec"].get("nodeName"))
                == 20 * (wave + 1)), "pods never bound"
        assert wait_for(lambda: series().get(UNSCHEDULABLE, 0.0)
                        - before.get(UNSCHEDULABLE, 0.0) == STUCK)
        runner.scheduler.wait_for_bindings(10.0)
        runner.scheduler.explainer.drain(60.0)
        yield {"runner": runner, "before": before, "after": series(),
               "spans": TRACER.spans(), "dropped": TRACER.dropped,
               "threads": {t.name for t in threading.enumerate()}}
    finally:
        FLIGHT.enabled = flight_was
        runner.stop()
        server.stop()


def test_every_span_of_the_served_path_fires_on_its_thread(served):
    by_name: dict = {}
    for sp in served["spans"]:
        by_name.setdefault(sp.name, set()).add(sp.thread)
    for name, thread in SPAN_THREADS.items():
        assert name in by_name, (name, sorted(by_name))
        assert all(t.startswith(thread) for t in by_name[name]), (
            name, by_name[name])
    ids = {sp.span_id: sp for sp in served["spans"]}
    for sp in served["spans"]:
        if sp.thread != "scheduler-loop":
            continue
        # the loop thread is pop_wait + cycle and nothing beside them
        if sp.name in ("scheduler/pop_wait", "scheduler/cycle"):
            assert sp.parent_id == 0, sp
        elif sp.name.startswith("scheduler/"):
            root = sp
            while root.parent_id in ids:
                root = ids[root.parent_id]
            assert root.name == "scheduler/cycle", (sp.name, root.name)
    staged = [sp for sp in served["spans"]
              if sp.name == "scheduler/stage_batch"]
    assert all(sp.attributes["path"] == "inline"
               and sp.attributes["bytes"] > 0 and sp.attributes["leaves"] > 0
               for sp in staged)
    assert all(0.0 <= sp.cpu_s <= (sp.end - sp.start) + 0.05
               for sp in served["spans"])
    # the four stages of the flight recorder's per-pod loops, each its own
    # span: three on the loop, one on the binders
    stages = {sp.attributes["stage"]: sp.thread for sp in served["spans"]
              if sp.name == "scheduler/flight"}
    assert sorted(stages) == ["bind", "dispatch", "drain_fill", "resolve"]
    assert stages.pop("bind").startswith("binder-")
    assert set(stages.values()) == {"scheduler-loop"}


def test_a_cycle_is_its_children_and_its_self_time(served):
    """Every served scheduler/cycle span's wall is the sum of its direct
    children's wall plus its self share, and the self shares sum to what
    the self series grew by over the run."""
    spans = served["spans"]
    assert served["dropped"] == 0
    kids: dict = {}
    for sp in spans:
        kids.setdefault(sp.parent_id, []).append(sp)
    cycles = [sp for sp in spans if sp.name == "scheduler/cycle"]
    assert cycles
    self_sum = 0.0
    for sp in cycles:
        wall = sp.end - sp.start
        children = sum(c.end - c.start for c in kids.get(sp.span_id, ()))
        assert sp.child_wall_s == pytest.approx(children, abs=1e-6)
        self_share = wall - sp.child_wall_s
        assert 0.0 <= self_share <= wall + 1e-9
        self_sum += self_share
    before, after = served["before"], served["after"]
    key = 'scheduler_span_self_seconds_total{span="scheduler/cycle"}'
    assert after[key] - before.get(key, 0.0) == pytest.approx(
        self_sum, abs=1e-6 * len(cycles))
    blocked = 'scheduler_span_self_blocked_seconds_total{span="scheduler/cycle"}'
    assert 0.0 <= after[blocked] - before.get(blocked, 0.0) <= self_sum + 1e-6


def test_served_threads_have_names_and_their_cpu_series_grow(served):
    threads = served["threads"]
    for name in ("scheduler-loop", "informer-pods", "informer-nodes",
                 "drain-resolver", "parity-sentinel", "invariant-auditor"):
        assert name in threads, sorted(threads)
    assert any(t.startswith("binder-") for t in threads)
    before, after = served["before"], served["after"]
    for name in ("scheduler-loop", "informer-pods"):
        key = f'scheduler_thread_cpu_seconds_total{{thread="{name}"}}'
        assert after[key] > before.get(key, 0.0), key
    assert after["process_cpu_seconds_total"] > \
        before["process_cpu_seconds_total"]


def test_span_and_informer_series_cover_the_served_run(served):
    before, after = served["before"], served["after"]

    def grew(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    wall: dict = {}
    for sp in served["spans"]:
        wall[sp.name] = wall.get(sp.name, 0.0) + sp.end - sp.start
    for name in SPAN_THREADS:
        key = f'scheduler_span_blocked_seconds_total{{span="{name}"}}'
        assert key in after, name
        assert 0.0 <= grew(key) <= wall[name] + 1e-9, name
    # a wait is all blocked time; it holds no CPU
    assert grew('scheduler_span_blocked_seconds_total'
                '{span="scheduler/pop_wait"}') > \
        0.8 * wall["scheduler/pop_wait"] > 0.0
    # 40 ADDED events at least, and their bind confirmations
    events = grew('scheduler_informer_events_total{resource="pods"}')
    assert events >= 40
    assert grew('scheduler_informer_handler_seconds_total{resource="pods"}') \
        > 0.0
    assert grew("scheduler_queue_wait_seconds_count") == 40 + STUCK
    # the e2e SLI saw every pod once, from a first stamp that was there
    assert grew("scheduler_e2e_scheduling_duration_seconds_count") == 40
    assert grew("scheduler_e2e_scheduling_duration_seconds_sum") > 0.0


def test_an_idle_loop_does_not_turn_the_ring_over():
    """A stretch of empty waits is ONE scheduler/pop_wait, grown in place:
    however long the loop idles, the last drain's spans stay in the ring
    for /debug/traces and `ktpu trace dump`."""
    from kubernetes_tpu.sched.cache import SchedulerCache
    from kubernetes_tpu.sched.scheduler import Scheduler
    cache = SchedulerCache()
    cache.add_node(make_node("n0").capacity(
        {"cpu": "8", "memory": "16Gi", "pods": "32"}).obj())
    queue = SchedulingQueue()
    sched = Scheduler(SchedulerConfiguration(batch_size=4), cache, queue,
                      lambda pod, node: True)
    ring_was = TRACER.max_spans
    TRACER.reset()
    try:
        for i in range(3):
            queue.add(make_pod(f"idle-p{i}").req({"cpu": "100m"}).obj())
        while sched.run_once(wait=0.01) or sched._pending:
            pass
        sched.wait_for_bindings(10.0)
        # the loop's last, empty wait opened the idle stretch
        drain = TRACER.spans()
        assert "scheduler/cycle" in [sp.name for sp in drain]
        idle, end_was = drain[-1], drain[-1].end
        assert idle.name == "scheduler/pop_wait"
        assert idle.attributes["got"] == 0
        TRACER.max_spans = len(drain) + 8
        for _ in range(3 * TRACER.max_spans):
            assert sched.run_once(wait=0.001) == 0
        assert TRACER.spans() == drain and TRACER.dropped == 0
        assert idle.end - end_was >= 3 * TRACER.max_spans * 0.001
        # pods end the stretch: their wait and the next idle one are new
        queue.add(make_pod("idle-late").req({"cpu": "100m"}).obj())
        while sched.run_once(wait=0.01) or sched._pending:
            pass
        assert sched.run_once(wait=0.001) == 0
        waits = TRACER.spans("scheduler/pop_wait")
        assert idle in waits and waits[-1] is not idle
        assert waits[-1].attributes["got"] == 0
        assert any(sp.attributes["got"] == 1 for sp in waits)
    finally:
        sched.close()
        TRACER.max_spans = ring_was
        TRACER.reset()


def test_a_parked_fragment_is_not_held_for_the_idle_wait():
    """A short pop right after a full one is parked once to merge with
    later arrivals (_run_batch). When none come and nothing is in flight,
    the next pop waits the short wait of a busy loop, not the idle one:
    the burst's tail is dispatched, not held."""
    from kubernetes_tpu.sched.cache import SchedulerCache
    from kubernetes_tpu.sched.scheduler import Scheduler
    cache = SchedulerCache()
    for i in range(4):
        cache.add_node(make_node(f"n{i}").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": "32"}).obj())
    queue = SchedulingQueue()
    sched = Scheduler(SchedulerConfiguration(batch_size=4,
                                             max_drain_batches=2),
                      cache, queue, lambda pod, node: True)
    try:
        for i in range(10):
            queue.add(make_pod(f"tail-p{i}").req({"cpu": "100m"}).obj())
        assert sched.run_once(wait=0.01) == 0  # a full pop of 8, in flight
        assert len(sched._pending) == 1
        assert sched.run_once(wait=0.01) == 8  # the tail is parked,
        assert len(sched._staged) == 2         # the drain before it settled
        assert not sched._pending
        TRACER.reset()
        sched.run_once(wait=30.0)
        assert not sched._staged
        wait, = TRACER.spans("scheduler/pop_wait")
        assert wait.attributes["got"] == 0 and wait.end - wait.start < 1.0
        while sched.run_once(wait=0.01) or sched._pending:
            pass
        sched.wait_for_bindings(10.0)
        assert len(cache.bound_pods(include_assumed=True)) == 10
    finally:
        sched.close()
        TRACER.reset()


def test_every_span_and_series_of_the_account_feeds_a_metric_file():
    """The rule that keeps the account honest: a span or series of the
    served path that no per-layer metric reads does not stay. Every span
    of the table above and every series the collectors add is named in the
    args of a file under yardstick/layer_metrics/."""
    import glob
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    read = set()
    for path in glob.glob(os.path.join(root, "yardstick", "layer_metrics",
                                       "*.json")):
        with open(path) as f:
            args = json.load(f)["args"]
        for names in args.values():
            if isinstance(names, list):
                read.update(n.split("{")[0] for n in names)
    assert set(SPAN_THREADS) <= read, sorted(set(SPAN_THREADS) - read)
    collected = {line.split()[2] for line in REGISTRY.expose_text()
                 .splitlines() if line.startswith("# TYPE ")} - {
        m.name for m in REGISTRY._metrics.values()}
    assert collected and collected <= read, sorted(collected - read)
    assert "scheduler_queue_wait_seconds_sum" in read
    # the tracer's self time (utils/tracing.py), the collector's two
    # series (sched/gcpolicy.py) and the encoder's template store's
    # (encode/snapshot.py), each by its own file
    for series_name, metric in (
            ("scheduler_span_self_seconds_total",
             "cycle_self_ms_per_drain.burst"),
            ("scheduler_span_self_blocked_seconds_total",
             "cycle_self_blocked_ms_per_drain.burst"),
            ("scheduler_gc_pause_seconds_total",
             "gc_pause_us_per_pod_event.burst"),
            ("scheduler_gc_collections_total",
             "gc_full_collections_per_kevent.burst"),
            ("scheduler_encode_pod_template_total",
             "pod_template_hit_share.burst")):
        assert series_name in collected
        with open(os.path.join(root, "yardstick", "layer_metrics",
                               metric + ".json")) as f:
            spec = json.load(f)
        assert spec["args"]["num"][0].split("{")[0] == series_name
        assert spec["reader"] == "series_ratio"  # absent reads None, not 0


# ------------------------------------------------------------- bulk bind

def test_bind_bulk_handles_every_result_before_the_records_and_events(
        monkeypatch):
    """_bind_bulk's three passes (result, flight record, event) keep what
    each pod gets: a pod bound stays assumed, gets one flight record and
    one Scheduled event; a pod gone is forgotten quietly; a pod whose bind
    failed is forgotten, requeued and counted an error. Every result is
    handled before the first record or event."""
    from kubernetes_tpu.metrics.registry import SCHEDULE_ATTEMPTS
    from kubernetes_tpu.sched import scheduler as scheduler_mod
    from kubernetes_tpu.sched.cache import SchedulerCache
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.utils.tracing import FlightRecorder
    cache = SchedulerCache()
    cache.add_node(make_node("n0").capacity(
        {"cpu": "8", "memory": "16Gi", "pods": "32"}).obj())
    queue = SchedulingQueue(backoff_initial=600.0, backoff_max=600.0)
    pods = [make_pod(f"bulk-p{i}").req({"cpu": "100m"}).obj()
            for i in range(3)]
    keys = [p.key for p in pods]
    sched = Scheduler(SchedulerConfiguration(batch_size=4), cache, queue,
                      lambda pod, node: True,
                      bulk_binder=lambda pairs: [True, None, False])
    flight = FlightRecorder(enabled=True)
    monkeypatch.setattr(scheduler_mod, "FLIGHT", flight)
    for k in keys:
        flight.record(k, "informer")
    events = []

    class Recorder:
        def event(self, obj, type_, reason, message):
            # the results pass is over: the failed pod is already queued
            events.append((obj.key, type_, reason, set(queue._entries)))

    sched.recorder = Recorder()
    pairs = [(p, "n0") for p in pods]
    cache.assume_many(pairs)
    errors = SCHEDULE_ATTEMPTS.get({"result": "error"})
    TRACER.reset()
    try:
        sched._bind_bulk(pairs)
    finally:
        sched.close()
    assert [cache.is_assumed_or_bound(k) for k in keys] == [
        True, False, False]
    assert set(queue._entries) == {keys[2]}
    assert queue._entries[keys[2]].attempts == 1
    assert queue.stats()["backoff"] == 1
    assert SCHEDULE_ATTEMPTS.get({"result": "error"}) == errors + 1
    assert events == [(keys[0], "Normal", "Scheduled", {keys[2]})]
    assert [[st["stage"] for st in flight.timeline(k)] for k in keys] == [
        ["informer", "bind"], ["informer"], ["informer"]]
    spans = {sp.name: sp for sp in TRACER.spans()}
    bulk = spans["scheduler/bind_bulk"]
    for name in ("scheduler/bind_call", "scheduler/flight",
                 "scheduler/bind_events"):
        assert spans[name].parent_id == bulk.span_id, name
    assert spans["scheduler/flight"].attributes == {"stage": "bind",
                                                    "pods": 1}
    assert spans["scheduler/bind_events"].attributes == {"pods": 1}
    TRACER.reset()


# ------------------------------------------------------------- gang rounds

def test_gang_rounds_are_observed_once_a_batch_that_held_pods():
    """scheduler_gang_rounds is what its help text and buckets say: one
    observation a gang batch, with that batch's rounds. A drain is padded
    to max_drain_batches; the padding batches are not observed. Every pod
    repels every other on hostname (the antiaffinity deployment's pods),
    so a batch takes more than its one placing round."""
    import json
    import os

    import numpy as np
    from kubernetes_tpu.api import Node, Pod
    from kubernetes_tpu.metrics.registry import GANG_ROUNDS
    from kubernetes_tpu.sched.cache import SchedulerCache
    from kubernetes_tpu.sched.scheduler import Scheduler
    from yardstick.generators import upstream_pod_anti_affinity
    from yardstick.readers import series_ratio
    assert GANG_ROUNDS.help == "Conflict-resolution rounds per gang batch"
    nodes, pods = upstream_pod_anti_affinity.generate(0, 12, 9)
    for p in pods:
        p["metadata"]["namespace"] = "sched-1"
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(Node.from_dict(n))
    queue = SchedulingQueue()
    cfg = SchedulerConfiguration(batch_size=4, max_drain_batches=2)
    sched = Scheduler(cfg, cache, queue, lambda pod, node: True)
    drains = []
    submit = sched._submit_resolve

    def spy(pend):
        drains.append(pend)
        submit(pend)

    sched._submit_resolve = spy
    before = series()
    at_most = dict(GANG_ROUNDS.bucket_counts())
    try:
        # 6 pods: a drain of two batches, 4 and 2; then 3 more: one batch
        # of 3 and a padding batch
        for wave in (pods[:6], pods[6:]):
            for p in wave:
                queue.add(Pod.from_dict(p))
            while sched.run_once(wait=0.01) or sched._pending:
                pass
        sched.wait_for_bindings(10.0)
    finally:
        sched.close()
    after = series()
    held = [[len(c) for c in d["chunks"]] for d in drains]
    assert held == [[4, 2], [3]], held
    rounds = [np.asarray(d["rounds"]) for d in drains]
    assert all(r.shape == (cfg.max_drain_batches,) for r in rounds)
    want = [int(r[b]) for r, h in zip(rounds, held) for b in range(len(h))]
    assert all(2 <= r <= cfg.max_gang_rounds for r in want), want
    assert max(want) > 2  # a batch whose members contend takes more rounds
    rose = {k: after[k] - before.get(k, 0.0) for k in after
            if k.startswith("scheduler_gang_rounds")}
    assert rose["scheduler_gang_rounds_count"] == len(want) == 3
    assert rose["scheduler_gang_rounds_sum"] == sum(want)
    # every observation lies in 1..max_gang_rounds, and a bound is inclusive
    grew = {b: c - at_most.get(b, 0) for b, c in GANG_ROUNDS.bucket_counts()}
    assert grew[cfg.max_gang_rounds] == 3
    for bound in (1, 2, 3, 4):
        assert grew[bound] == sum(r <= bound for r in want), (bound, want)
    # the metric file that reads the series finds them in the exposition
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "layer_metrics",
                           "gang_rounds_per_batch.burst.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "series_ratio"
    for name in spec["args"]["num"] + spec["args"]["den"]:
        assert name in after, name
    assert series_ratio.read({"counters": rose}, spec["args"]) == \
        pytest.approx(sum(want) / 3)


# ----------------------------------------------------------- device scopes

def test_drain_step_carries_its_named_scopes():
    """Metadata only (tests/test_bulk_and_drain.py holds the placements
    bit-equal): the stages of the resident program and of a round are
    findable by name in the lowered module, so in a profiler trace."""
    import jax
    import numpy as np
    from kubernetes_tpu.models.gang import (build_drain_context, drain_step,
                                            unify_batches)
    from kubernetes_tpu.sched.cache import SchedulerCache
    cache = SchedulerCache()
    for i in range(4):
        cache.add_node(make_node(f"n{i}").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": "32"}).obj())
    pods = [make_pod(f"d{i}").req({"cpu": "500m"}).obj() for i in range(8)]
    _, ct, meta = cache.snapshot(pending_pods=pods[:4], slot_headroom=32)
    pbs = [cache.encode_pods(pods[i * 4:(i + 1) * 4], meta, min_p=4)
           for i in range(2)]
    ct_dev, e0, fill = build_drain_context(ct, pbs)
    pb_stack = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *unify_batches(pbs))
    text = drain_step.lower(
        ct_dev, pb_stack, fill, e0=e0, seed=0,
        fit_strategy="LeastAllocated", topo_keys=meta.topo_keys,
        weights=(), enabled_filters=(), max_rounds=8
    ).as_text(debug_info=True)
    for scope in ("drain/extend", "drain/converge", "drain/fold",
                  "gang/evaluate", "gang/accept", "gang/veto",
                  "gang/commit"):
        assert scope in text, scope
    assert "drain/patch" not in text  # no churn patch rode this dispatch
