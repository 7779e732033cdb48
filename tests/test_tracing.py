"""Tracing hardening: ring-buffer truncation, id-based span linkage,
sampling, Chrome trace-event export, and the per-pod flight recorder."""

import json
import threading
import time

import pytest

from kubernetes_tpu.utils.tracing import (
    FlightRecorder,
    Tracer,
    export_otlp_json,
    validate_chrome_trace,
)


# ------------------------------------------------------------------ ring

def test_ring_buffer_drops_oldest_and_counts():
    tr = Tracer(max_spans=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6
    tr.reset()
    assert tr.spans() == [] and tr.dropped == 0


def test_max_spans_resize_preserves_newest():
    tr = Tracer(max_spans=8)
    for i in range(8):
        with tr.span(f"s{i}"):
            pass
    tr.max_spans = 3
    assert [s.name for s in tr.spans()] == ["s5", "s6", "s7"]
    # growing back keeps content and the new cap
    tr.max_spans = 100
    with tr.span("new"):
        pass
    assert [s.name for s in tr.spans()] == ["s5", "s6", "s7", "new"]


# ------------------------------------------------------------- id linkage

def test_span_ids_unique_and_parent_by_id():
    tr = Tracer()
    with tr.span("outer") as outer:
        with tr.span("mid") as mid:
            with tr.span("inner") as inner:
                pass
        with tr.span("sibling") as sib:
            pass
    ids = [s.span_id for s in tr.spans()]
    assert len(ids) == len(set(ids)) == 4
    assert inner.parent_id == mid.span_id
    assert mid.parent_id == outer.span_id
    assert sib.parent_id == outer.span_id
    assert outer.parent_id == 0
    # one trace: every span shares the root's trace id
    assert {s.trace_id for s in tr.spans()} == {outer.span_id}
    # every span knows the thread it ran on
    assert {s.thread for s in tr.spans()} == {
        threading.current_thread().name}


def test_same_name_spans_link_to_the_right_parent():
    """The old name-based linkage guessed; ids don't. Two same-named
    parents must each claim their own child."""
    tr = Tracer()
    parents = []
    for _ in range(2):
        with tr.span("cycle") as p:
            parents.append(p)
            with tr.span("child"):
                pass
    children = tr.spans("child")
    assert [c.parent_id for c in children] == [p.span_id for p in parents]
    assert children[0].trace_id != children[1].trace_id


def test_separate_roots_get_separate_traces():
    tr = Tracer()
    with tr.span("a") as a:
        pass
    with tr.span("b") as b:
        pass
    assert a.trace_id != b.trace_id


def test_nesting_is_per_thread():
    tr = Tracer()
    seen = {}

    def worker():
        with tr.span("worker-root") as sp:
            seen["worker"] = sp

    with tr.span("main-root") as main_sp:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # the worker's span must NOT have picked up main's stack as a parent
    assert seen["worker"].parent_id == 0
    assert seen["worker"].trace_id != main_sp.trace_id


def test_nesting_is_per_thread_with_cycle_as_root():
    """Two threads, each with its own root: children hang under their own
    thread's root (``scheduler/cycle`` on the loop), never the other's."""
    tr = Tracer()
    go, done = threading.Event(), threading.Event()

    def binder():
        go.wait(5.0)
        with tr.span("scheduler/bind_bulk"):
            with tr.span("scheduler/bind_call"):
                pass
        done.set()

    t = threading.Thread(target=binder, name="binder-0")
    t.start()
    with tr.span("scheduler/cycle") as root:
        with tr.span("scheduler/drain_gate"):
            go.set()
            assert done.wait(5.0)
        with tr.span("scheduler/encode_pods"):
            pass
    t.join(5.0)
    assert not t.is_alive()
    by = {s.name: s for s in tr.spans()}
    me = threading.current_thread().name
    for child in ("scheduler/drain_gate", "scheduler/encode_pods"):
        assert by[child].parent_id == root.span_id
        assert by[child].trace_id == root.trace_id
        assert by[child].thread == me
    assert by["scheduler/bind_bulk"].parent_id == 0
    assert by["scheduler/bind_bulk"].trace_id != root.trace_id
    assert by["scheduler/bind_call"].parent_id == \
        by["scheduler/bind_bulk"].span_id
    assert by["scheduler/bind_call"].thread == "binder-0"


@pytest.mark.parametrize("kind", ["sleeping", "spinning"])
def test_span_records_thread_and_cpu_time(kind):
    tr = Tracer()
    # a spin can lose its core to another xdist worker's compile threads
    # for most of 0.2 s: the best of a few attempts says what the span
    # records when the thread does have the CPU
    for _attempt in range(5):
        with tr.span(kind) as sp:
            if kind == "sleeping":
                time.sleep(0.2)
            else:
                end = time.perf_counter() + 0.2
                while time.perf_counter() < end:
                    pass
        wall = sp.end - sp.start
        if kind == "sleeping" or sp.cpu_s > 0.5 * wall:
            break
    assert sp.thread == threading.current_thread().name
    assert wall >= 0.19
    if kind == "sleeping":
        assert sp.cpu_s < 0.25 * wall       # wall >> CPU: it stood
        assert sp.blocked_s > 0.75 * wall
    else:
        assert sp.cpu_s > 0.5 * wall        # it had the CPU (shared box)
        assert sp.blocked_s == pytest.approx(max(wall - sp.cpu_s, 0.0))


def test_blocked_totals_match_the_rings_sums():
    tr = Tracer()
    for i in range(7):
        with tr.span("a" if i % 2 else "b"):
            time.sleep(0.002)
    ring = {}
    for sp in tr.spans():
        ring[sp.name] = ring.get(sp.name, 0.0) + sp.blocked_s
        assert 0.0 <= sp.blocked_s <= sp.end - sp.start
    totals = tr.blocked_totals()
    assert set(totals) == {"a", "b"}
    for name, blocked in totals.items():
        assert blocked == pytest.approx(ring[name])
    # the ring turns over and resets; the totals are since process start
    tr.reset()
    with tr.span("a"):
        time.sleep(0.002)
    assert tr.blocked_totals()["a"] > totals["a"]
    assert tr.blocked_totals()["b"] == totals["b"]


def test_a_discarded_span_reaches_neither_the_ring_nor_the_totals():
    tr = Tracer()
    with tr.span("kept"):
        with tr.span("gone") as sp:
            sp.discard = True
            with tr.span("child") as child:
                pass
    assert [s.name for s in tr.spans()] == ["child", "kept"]
    assert child.parent_id == sp.span_id      # nesting is untouched
    assert "gone" not in tr.blocked_totals()
    assert tr.dropped == 0


def test_annotate_hook_once_a_sampled_span_never_an_unsampled_one():
    entered, exited = [], []

    class Mirror:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            exited.append(self.name)

    tr = Tracer(ratio=0.25)
    tr.annotate = Mirror
    kept = 0
    for i in range(40):
        with tr.span(f"s{i}") as sp:
            kept += sp is not None
    assert kept == 10 == len(tr.spans())
    assert entered == exited == [s.name for s in tr.spans()]
    # the mirror closes when the body raises, too
    tr2 = Tracer()
    tr2.annotate = Mirror
    with pytest.raises(KeyError):
        with tr2.span("boom"):
            raise KeyError("x")
    assert entered[-1] == exited[-1] == "boom"
    assert [s.name for s in tr2.spans()] == ["boom"]


def test_tracing_imports_and_records_with_jax_unimportable():
    import subprocess
    import sys
    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"   # any 'import jax' now raises
        "sys.modules['jaxlib'] = None\n"
        "from kubernetes_tpu.utils.tracing import TRACER, FLIGHT\n"
        "from kubernetes_tpu.metrics.registry import REGISTRY\n"
        "with TRACER.span('apiserver/request') as sp:\n"
        "    pass\n"
        "assert sp.thread == 'MainThread' and TRACER.annotate is None\n"
        "FLIGHT.record('ns/p', 'informer')\n"
        "text = REGISTRY.expose_text()\n"
        "assert 'scheduler_span_blocked_seconds_total{span=\"apiserver/"
        "request\"} ' in text\n"
        "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n"
        "print('ok')\n")
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


# ---------------------------------------------------------------- sampling

def test_sampling_ratio_keeps_a_fraction():
    tr = Tracer(ratio=0.25)
    kept = 0
    for _ in range(100):
        with tr.span("s") as sp:
            kept += sp is not None
    assert kept == len(tr.spans()) == 25


# ------------------------------------------------------------- otlp export

def test_otlp_export_links_by_id_and_orphans_evicted_parents():
    tr = Tracer(max_spans=2)
    with tr.span("outer"):
        with tr.span("inner1"):
            pass
        with tr.span("inner2"):
            pass
    # ring holds [inner2, outer]; inner1 was evicted
    doc = export_otlp_json(tr)
    spans = {s["name"]: s for s in
             doc["resourceSpans"][0]["scopeSpans"][0]["spans"]}
    assert set(spans) == {"inner2", "outer"}
    assert spans["inner2"]["parentSpanId"] != ""
    assert spans["outer"]["parentSpanId"] == ""
    assert len(spans["outer"]["spanId"]) == 16
    assert len(spans["outer"]["traceId"]) == 32
    # a child exported while its parent is still OPEN (parent not yet in
    # the finished ring) must come out a root, not dangle a broken link
    tr2 = Tracer()
    with tr2.span("outer"):
        with tr2.span("inner"):
            pass
        doc2 = export_otlp_json(tr2)
    (only,) = doc2["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert only["name"] == "inner" and only["parentSpanId"] == ""


# ----------------------------------------------------------- chrome export

def test_export_chrome_schema_and_content(tmp_path):
    tr = Tracer()
    fl = FlightRecorder(enabled=True)
    with tr.span("scheduler/gang_dispatch", pods=3) as sp:
        fl.record("default/p0", "dispatch", span=sp)
    fl.record("default/p0", "bind", node="n0")
    doc = tr.export_chrome(path=str(tmp_path / "t.json"), flight=fl)
    assert validate_chrome_trace(doc) == []
    on_disk = json.loads((tmp_path / "t.json").read_text())
    assert validate_chrome_trace(on_disk) == []
    names = [e["name"] for e in doc["traceEvents"]]
    assert "scheduler/gang_dispatch" in names
    assert "dispatch" in names and "bind" in names
    # the pod's dispatch slice links back to the batch span by id
    ev = next(e for e in doc["traceEvents"] if e["name"] == "dispatch")
    assert ev["args"]["span_id"] == sp.span_id
    # per-pod track is named after the pod key
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["args"].get("name") == "default/p0" for e in meta)


def test_exports_carry_thread_lanes_and_cpu_time():
    tr = Tracer()

    def worker():
        with tr.span("scheduler/resolver_fetch"):
            pass

    with tr.span("scheduler/cycle"):
        with tr.span("scheduler/apply"):
            pass
    t = threading.Thread(target=worker, name="drain-resolver")
    t.start()
    t.join(5.0)
    doc = tr.export_chrome(flight=FlightRecorder(enabled=False))
    assert validate_chrome_trace(doc) == []
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    lanes = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["name"] == "thread_name" and e["pid"] == 1}
    # one lane a thread, named after it; a child shares its parent's lane
    assert xs["scheduler/cycle"]["tid"] == xs["scheduler/apply"]["tid"]
    assert lanes[xs["scheduler/cycle"]["tid"]] == \
        threading.current_thread().name
    assert lanes[xs["scheduler/resolver_fetch"]["tid"]] == "drain-resolver"
    assert len(lanes) == 2
    for e in xs.values():
        assert e["args"]["cpu_ms"] >= 0.0 and e["args"]["trace_id"]
    otlp = export_otlp_json(tr)
    for sp in otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]:
        attrs = {a["key"]: a["value"] for a in sp["attributes"]}
        assert attrs["thread.name"]["stringValue"] in lanes.values()
        assert attrs["thread.cpu_time_s"]["doubleValue"] >= 0.0


def test_export_chrome_max_events_keeps_newest():
    tr = Tracer()
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    doc = tr.export_chrome(flight=FlightRecorder(enabled=False),
                           max_events=3)
    xs = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs == ["s7", "s8", "s9"]


def test_export_chrome_bounds_flight_tracks():
    tr = Tracer()
    fl = FlightRecorder(enabled=True)
    for i in range(10):
        fl.record(f"ns/p{i}", "informer")
    doc = tr.export_chrome(flight=fl, max_flight_pods=2)
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["name"] == "thread_name" and e["pid"] == 2}
    assert tracks == {"ns/p8", "ns/p9"}  # newest-inserted kept


def test_validate_chrome_trace_rejects_garbage():
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [{"ph": "X", "name": "x", "ts": 1.0, "pid": 1}]}
    assert any("dur" in p for p in validate_chrome_trace(bad))
    assert validate_chrome_trace({"traceEvents": [
        {"ph": "i", "name": "x", "ts": 0.0, "s": "t", "pid": 2}]}) == []


# --------------------------------------------------------- flight recorder

@pytest.mark.parametrize("case", ["open_past_hard_cap", "closed_first",
                                  "window_of_10000"])
def test_flight_recorder_per_pod_ring_and_pod_eviction(case):
    if case == "open_past_hard_cap":
        # nothing is closed: the recorder grows to OPEN_FACTOR x max_pods,
        # then the oldest OPEN timeline goes, counted
        fl = FlightRecorder(max_pods=1, max_events=3, enabled=True)
        cap = fl.OPEN_FACTOR * fl.max_pods
        for i in range(5):
            fl.record("ns/a", f"stage{i}")
        tl = fl.timeline("ns/a")
        assert [e["stage"] for e in tl] == ["stage2", "stage3", "stage4"]
        for i in range(1, cap):
            fl.record(f"ns/b{i}", "informer")
        assert len(fl.keys()) == cap and fl.stats()["droppedPods"] == 0
        fl.record("ns/c", "informer")  # evicts ns/a (oldest inserted)
        assert fl.timeline("ns/a") == []
        assert fl.stats()["droppedPods"] == 1
        assert len(fl.keys()) == cap and "ns/c" in fl.keys()
    elif case == "closed_first":
        # a bound timeline makes room before any open one does, however
        # old the open one is; that is turnover, not a drop
        fl = FlightRecorder(max_pods=2, enabled=True)
        fl.record("ns/old-open", "informer")
        fl.record("ns/bound", "informer")
        fl.record("ns/bound", "bind", node="n0")
        fl.record("ns/new", "informer")
        assert set(fl.keys()) == {"ns/old-open", "ns/new"}
        assert fl.stats()["droppedPods"] == 0
        # with nothing closed left the recorder grows past max_pods
        fl.record("ns/newer", "informer")
        assert set(fl.keys()) == {"ns/old-open", "ns/new", "ns/newer"}
        assert fl.stats()["droppedPods"] == 0
    else:
        # the benchmark's burst: 10,000 pods open at once fit the DEFAULT
        # recorder and keep their first stamp through 10,000 later pods
        # that come and bind around them
        fl = FlightRecorder(enabled=True)
        for i in range(10_000):
            fl.record(f"burst/p{i}", "informer")
        first = fl.timeline("burst/p0")[0]["ts"]
        for i in range(10_000):
            fl.record(f"later/p{i}", "informer")
            fl.record(f"later/p{i}", "bind", node="n0")
        assert fl.stats()["droppedPods"] == 0
        assert fl.timeline("burst/p0")[0]["ts"] == first
        assert sum(k.startswith("burst/") for k in fl.keys()) == 10_000
        assert fl.stats()["pods"] <= fl.OPEN_FACTOR * fl.max_pods


def test_flight_recorder_disabled_is_noop():
    fl = FlightRecorder(enabled=False)
    fl.record("ns/a", "informer")
    assert fl.timeline("ns/a") == [] and fl.stats()["pods"] == 0


@pytest.mark.parametrize("evicted", [False, True])
def test_flight_recorder_bind_observes_e2e_histograms(evicted):
    from kubernetes_tpu.metrics.registry import E2E_DURATION, E2E_SCHEDULING
    fl = FlightRecorder(max_pods=1, enabled=True)
    fl.record("ns/p", "informer")
    fl.record("ns/p", "queue_add")
    fl.record("ns/p", "dispatch")
    if evicted:
        for i in range(fl.OPEN_FACTOR):  # pushes ns/p out, still open
            fl.record(f"ns/other{i}", "informer")
        assert fl.timeline("ns/p") == []
    base_e2e = E2E_SCHEDULING.count()
    base_sli = E2E_DURATION.count()
    drops = fl.stats()["droppedPods"]
    fl.record("ns/p", "bind", node="n0")
    if evicted:
        # the first stamp is gone: the histograms stay silent, never ~0 s
        assert E2E_SCHEDULING.count() == base_e2e
        assert E2E_DURATION.count() == base_sli
        assert fl.stats()["droppedPods"] == drops + 1
        assert fl.timeline("ns/p") == []
    else:
        assert E2E_SCHEDULING.count() == base_e2e + 1
        assert E2E_DURATION.count() == base_sli + 1


def test_flight_recorder_new_incarnation_resets_closed_timeline():
    """A recreated pod under the same ns/name must not stitch onto the
    old incarnation's bound timeline (the gap between them would poison
    the derived e2e histogram)."""
    from kubernetes_tpu.metrics.registry import E2E_SCHEDULING
    fl = FlightRecorder(enabled=True)
    fl.record("ns/p", "informer")
    fl.record("ns/p", "bind", node="n0")
    t_gap = time.time()
    fl.record("ns/p", "informer")  # second incarnation
    tl = fl.timeline("ns/p")
    assert [e["stage"] for e in tl] == ["informer"]
    assert tl[0]["ts"] >= t_gap
    base = E2E_SCHEDULING.count()
    fl.record("ns/p", "bind", node="n1")
    assert E2E_SCHEDULING.count() == base + 1
    # the new observation spans only the second incarnation, and a
    # requeue mid-flight does NOT reset (same incarnation)
    fl.record("ns/p", "informer")
    fl.record("ns/p", "requeue")
    fl.record("ns/p", "dispatch")
    assert [e["stage"] for e in fl.timeline("ns/p")] == [
        "informer", "requeue", "dispatch"]


def test_flight_recorder_timeline_attrs_and_span_link():
    tr = Tracer()
    fl = FlightRecorder(enabled=True)
    with tr.span("batch") as sp:
        fl.record("ns/p", "dispatch", span=sp, depth=2)
    (ev,) = fl.timeline("ns/p")
    assert ev["span_id"] == sp.span_id
    assert ev["attrs"] == {"depth": 2}
