"""scheduler_perf harness: config loading, op materialization, thresholds,
and the tracing aux subsystem."""

import time

from benchmarks.scheduler_perf import load_config, materialize, run_workload
from kubernetes_tpu.utils.tracing import Tracer


def test_config_covers_baseline_cases():
    cases = {c["name"] for c in load_config()}
    assert {"SchedulingBasic", "NodeResourcesFit", "SchedulingPodAntiAffinity",
            "PreferredTopologySpreading", "MixedHeterogeneous"} <= cases


def test_materialize_ops():
    cases = {c["name"]: c for c in load_config()}
    nodes, measured, warm = materialize(
        cases["SchedulingPodAntiAffinity"],
        {"initNodes": 8, "measurePods": 4})
    assert len(nodes) == 8 and len(measured) == 4 and warm == []
    # label strategy cycles zones
    zones = {n.metadata.labels["topology.kubernetes.io/zone"] for n in nodes}
    assert len(zones) == 4
    # template parsed into real API objects with anti-affinity
    assert measured[0].spec.affinity.pod_anti_affinity.required


def test_slo_gates_fail_on_missing_or_worse_numbers():
    """The hard SLO gate contract (BENCH_r05 lesson): a value that is
    missing, None, or unparsed fails exactly like a regressed one — it can
    never read as a pass. Unknown gate keys refuse to skip silently too."""
    from benchmarks.connected import check_slo_gates
    gates = {"SchedulingThroughput": 30, "p99AttemptLatencySeconds": 30}
    ok = {"SchedulingThroughput": 75.0, "p99_attempt_latency_s": 12.0}
    assert check_slo_gates(ok, gates) == []
    slow = {"SchedulingThroughput": 10.0, "p99_attempt_latency_s": 45.0}
    assert len(check_slo_gates(slow, gates)) == 2
    missing = {"SchedulingThroughput": None}  # p99 absent entirely
    fails = check_slo_gates(missing, gates)
    assert len(fails) == 2 and all("missing" in f for f in fails)
    assert check_slo_gates(ok, {"bogusGate": 1})  # unknown key = failure
    assert check_slo_gates(ok, None) == [] == check_slo_gates(ok, {})
    # the churn case config actually carries the gates the bench enforces
    cases = {c["name"]: c for c in load_config()}
    wl = cases["SchedulingChurn"]["workloads"][0]
    assert wl["sloGates"]["p99AttemptLatencySeconds"] > 0
    assert wl["sloGates"]["SchedulingThroughput"] > 0


def test_run_workload_small_passes_threshold():
    cases = {c["name"]: c for c in load_config()}
    res = run_workload(cases["SchedulingBasic"],
                       cases["SchedulingBasic"]["workloads"][0], scale=0.2)
    assert res["scheduled"] == res["pods"]
    assert res["passed"], res
    assert res["SchedulingThroughput"] > 0


def test_tracer_spans_nest_and_sample():
    tr = Tracer()
    with tr.span("outer", a=1):
        time.sleep(0.01)
        with tr.span("inner"):
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]
    outer = tr.spans("outer")[0]
    assert outer.parent_id == 0 and outer.duration_ms >= 10
    assert tr.spans("inner")[0].parent_id == outer.span_id
    assert outer.attributes == {"a": 1}


def test_scheduler_emits_spans():
    from kubernetes_tpu.client.clientset import DirectClient
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.store import ObjectStore
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    from kubernetes_tpu.utils.tracing import TRACER

    TRACER.reset()
    client = DirectClient(ObjectStore())
    client.nodes().create(make_node("t1").allocatable(
        {"cpu": "4", "pods": "10"}).obj().to_dict())
    runner = SchedulerRunner(client).start()
    try:
        client.pods().create(make_pod("traced").req({"cpu": "100m"}).obj().to_dict())
        deadline = time.time() + 15
        while time.time() < deadline:
            if client.pods().get("traced")["spec"].get("nodeName"):
                break
            time.sleep(0.05)
        assert client.pods().get("traced")["spec"].get("nodeName")
        names = {s.name for s in TRACER.spans()}
        assert "scheduler/gang_schedule" in names
        assert "scheduler/snapshot" in names
    finally:
        runner.stop()
