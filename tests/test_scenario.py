"""Cluster time machine: trace format, generators, recorders, driver.

Format tests pin the canonical-bytes contract (save -> load -> save is
bit-equal, generators are pure in (params, seed), the committed golden
fixture never drifts); recorder tests turn a real WAL and a synthetic
audit bundle into traces; the e2e tests replay small traces through a
REAL in-process apiserver + connected scheduler and hold the replay's
gates (all resident pods bound, per-phase p99 present, dispatch order ==
plan, status ConfigMap published).
"""

import io
import json
import os
import time

import pytest

from kubernetes_tpu.scenario import (Trace, TraceEvent, TraceFormatError,
                                     TraceManifest, builtin_trace,
                                     trace_from_bundle, trace_from_wal)
from kubernetes_tpu.scenario.driver import (SCENARIO_CONFIGMAP,
                                            ScenarioDriver)
from kubernetes_tpu.scenario.generate import (BUILTINS, diurnal_burst,
                                              job_waves, rolling_update,
                                              smoke, tenant_onboarding)
from kubernetes_tpu.scenario.trace import TENANT_LABEL

pytestmark = pytest.mark.scenario

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "config",
    "scenario-smoke.trace.jsonl")


# ---- format ---------------------------------------------------------------

def test_round_trip_is_bit_equal(tmp_path):
    t = smoke(seed=7)
    p1 = str(tmp_path / "a.trace.jsonl")
    p2 = str(tmp_path / "b.trace.jsonl")
    t.save(p1)
    loaded = Trace.load(p1)
    loaded.save(p2)
    assert open(p1).read() == open(p2).read()
    assert loaded == t


def test_unknown_version_refused():
    t = smoke(seed=0)
    lines = t.to_lines()
    head = json.loads(lines[0])
    head["version"] = 99
    with pytest.raises(TraceFormatError, match="unknown trace version"):
        Trace.loads("\n".join([json.dumps(head)] + lines[1:]))


def test_wrong_kind_refused():
    with pytest.raises(TraceFormatError, match="not a ktpu-trace"):
        Trace.loads(json.dumps({"kind": "ConfigMap", "version": 1}))


def test_bad_verb_refused():
    t = smoke(seed=0)
    lines = t.to_lines()
    ev = json.loads(lines[1])
    ev["verb"] = "explode"
    with pytest.raises(TraceFormatError, match="unknown event verb"):
        Trace.loads("\n".join([lines[0], json.dumps(ev)]))


def test_malformed_event_line_names_the_line():
    t = smoke(seed=0)
    with pytest.raises(TraceFormatError, match="line 2"):
        Trace.loads("\n".join([t.to_lines()[0], "{not json"]))


def test_manifest_slo_gates_and_chaos_round_trip():
    t = diurnal_burst({"pods": 6, "nodes": 2, "p99_slo_s": 2.5}, seed=1)
    t.manifest.chaos = {"seed": 42, "profile": "churn"}
    rt = Trace.loads("\n".join(t.to_lines()))
    assert rt.manifest.slo_gates == {"p99AttemptLatencySeconds": 2.5}
    assert rt.manifest.chaos == {"seed": 42, "profile": "churn"}
    assert rt.manifest.seed == 1
    assert rt.describe()["sloGates"] == {"p99AttemptLatencySeconds": 2.5}


@pytest.mark.parametrize("name", sorted(BUILTINS))
def test_generator_determinism_across_seeds(name):
    for seed in (0, 1, 2):
        a = builtin_trace(name, seed=seed).to_lines()
        b = builtin_trace(name, seed=seed).to_lines()
        assert a == b, f"{name} seed={seed} is not pure"
    assert (builtin_trace(name, seed=0).to_lines()
            != builtin_trace(name, seed=1).to_lines()), \
        f"{name} ignores its seed"


def test_golden_fixture_pinned():
    # the committed fixture IS smoke(seed=0): tests and
    # `ktpu scenario replay builtin:smoke` replay the same bytes, and
    # toolchain drift in the generators gets caught here
    assert open(FIXTURE).read().splitlines() == smoke(seed=0).to_lines()


def test_unknown_builtin_lists_catalog():
    with pytest.raises(KeyError, match="diurnal-burst"):
        builtin_trace("nope")


def test_materialize_stamps_identity_and_tenant():
    t = tenant_onboarding({"tenants": 1, "pods_per_tenant": 2,
                           "background_pods": 0, "nodes": 2}, seed=0)
    ev = next(e for e in t.events if e.tenant)
    obj = t.materialize(ev)
    assert obj["metadata"]["name"] == ev.name
    assert obj["metadata"]["namespace"] == ev.ns
    assert obj["metadata"]["labels"][TENANT_LABEL] == ev.tenant
    node = t.fleet_nodes()[0]
    assert node["metadata"]["labels"]["kubernetes.io/hostname"] == \
        node["metadata"]["name"]


def test_unknown_template_ref_refused():
    t = Trace(TraceManifest(name="x"),
              [TraceEvent(at_s=0.0, verb="create", kind="Pod",
                          ns="default", name="p0", template="ghost")])
    with pytest.raises(TraceFormatError, match="unknown template"):
        t.materialize(t.events[0])


def test_resident_pods_tracks_deletes():
    t = rolling_update({"replicas": 6, "nodes": 3}, seed=0)
    resident = t.resident_pods()
    # every old-generation pod is deleted by the rollout; the new
    # generation stays
    assert len(resident) == 6
    assert all(name.startswith("new-") for _, name in resident)
    jw = job_waves({"waves": 2, "jobs_per_wave": 3}, seed=0)
    assert len(jw.resident_pods()) == 3  # only the final wave survives


# ---- recorders ------------------------------------------------------------

def test_trace_from_wal(tmp_path):
    from kubernetes_tpu.store.store import ObjectStore
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    store = ObjectStore(data_dir=str(tmp_path))
    for i in range(2):
        store.create("Node", make_node(f"wn{i}").capacity(
            {"cpu": "4", "pods": "10"}).obj().to_dict())
    for i in range(3):
        store.create("Pod", make_pod(f"wp{i}").req(
            {"cpu": "100m"}).obj().to_dict())
    store.delete("Pod", "default", "wp2")
    store.close()

    t = trace_from_wal(str(tmp_path / "wal.jsonl"), chaos_seed=99)
    # nodes journaled before the first pod op became the manifest fleet
    assert len(t.manifest.fleet) == 2
    assert {e.verb for e in t.events} == {"create", "delete"}
    assert len(t.resident_pods()) == 2
    assert t.manifest.chaos == {"seed": 99, "profile": "churn"}
    # recorded events carry inline objects stripped of server-minted
    # metadata, and replay in rv order
    ev = t.events[0]
    assert ev.obj is not None
    assert "resourceVersion" not in ev.obj["metadata"]
    assert [e.at_s for e in t.events] == sorted(e.at_s for e in t.events)
    # and the capture round-trips through the canonical format
    assert Trace.loads("\n".join(t.to_lines())) == t


def test_trace_from_wal_refuses_empty(tmp_path):
    p = tmp_path / "wal.jsonl"
    p.write_text(json.dumps({"op": "set", "kind": "ConfigMap",
                             "ns": "default", "name": "c", "rv": "1",
                             "obj": {}}) + "\n")
    with pytest.raises(TraceFormatError, match="no replayable"):
        trace_from_wal(str(p))


def test_trace_from_bundle():
    bundle = {"invariant": "phantom_binding", "chaosSeed": 1234,
              "resourceVersion": "567",
              "podBatch": [f"default/ip{i}" for i in range(4)]}
    t = trace_from_bundle(bundle, nodes=3)
    assert t.manifest.name == "bundle-phantom_binding"
    assert t.manifest.chaos == {"seed": 1234, "profile": "churn"}
    assert len(t.events) == 4
    assert all(e.template == "incident-pod" for e in t.events)
    assert len(t.fleet_nodes()) == 3
    with pytest.raises(TraceFormatError, match="no podBatch"):
        trace_from_bundle({"podBatch": []})


# ---- driver e2e -----------------------------------------------------------

def _wait_for(pred, timeout=20.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _replay_against_live_stack(trace, speed=0.0):
    """Seed the trace's fleet into a real in-process apiserver with a
    connected scheduler, replay, and return (result, server url)."""
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.apiserver import APIServer
    server = APIServer().start()
    client = HTTPClient(server.url)
    runner = SchedulerRunner(client, SchedulerConfiguration(
        backoff_initial_s=0.05, backoff_max_s=0.2))
    runner.start()
    try:
        for n in trace.fleet_nodes():
            client.nodes().create(n)
        driver = ScenarioDriver(HTTPClient(server.url), trace,
                                speed=speed, bind_timeout_s=30.0)
        result = driver.run()
        assert result["dispatch_order"] == driver.plan()
        try:  # snapshot the published status CM before teardown
            cm = client.resource("configmaps", "default").get(
                SCENARIO_CONFIGMAP)
            status_cm = json.loads(cm["data"]["scenario"])
        except Exception:
            status_cm = None
        return result, status_cm
    finally:
        runner.stop()
        server.stop()


def test_driver_replay_e2e_diurnal():
    from kubernetes_tpu.metrics.registry import SCENARIO_ATTEMPT
    trace = diurnal_burst({"pods": 8, "nodes": 4, "cycles": 1,
                           "period_s": 0.5, "bursts": 1,
                           "burst_pods": 4}, seed=0)
    result, status_cm = _replay_against_live_stack(trace)
    assert result["completed"], result
    assert result["bound"] == result["resident"] == 12
    assert result["error_count"] == 0
    # per-phase p99 attempt latency present for every phase with pods —
    # the bench gate treats a missing number as failure
    assert result["phases"]
    for ph, st in result["phases"].items():
        assert st["bound"] == st["pods"], (ph, st)
        assert isinstance(st["p99_attempt_latency_s"], (int, float)), ph
        assert SCENARIO_ATTEMPT.count({"phase": ph}) == st["pods"]
    # the driver published its status ConfigMap (KTL006 upsert path)
    assert status_cm is not None
    assert status_cm["state"] == "done"
    assert status_cm["podsBound"] == 12
    assert status_cm["trace"] == "diurnal-burst"


def test_driver_status_line_renders():
    """ktpu status renders the Scenario: line from the published CM."""
    from kubernetes_tpu.cli.ktpu import main as ktpu_main
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.store.apiserver import APIServer
    from kubernetes_tpu.utils.configmap import upsert_configmap
    server = APIServer().start()
    try:
        upsert_configmap(
            HTTPClient(server.url), "default", SCENARIO_CONFIGMAP,
            {"scenario": json.dumps(
                {"trace": "smoke", "state": "dispatching",
                 "phase": "wave-1", "eventsDispatched": 20,
                 "eventsTotal": 32, "skewMaxMs": 3.1, "podsBound": 9,
                 "podsResident": 32, "speed": 4.0})},
            site="test_scenario")
        out = io.StringIO()
        assert ktpu_main(["-s", server.url, "status"], out=out) == 0
        text = out.getvalue()
        assert "Scenario:" in text
        assert "smoke dispatching (phase wave-1)" in text
        assert "20/32 events" in text
    finally:
        server.stop()


def test_bundle_to_trace_to_replay_e2e(tmp_path):
    """The acceptance e2e: an audit repro bundle becomes a trace file
    that replays through the driver against the live stack."""
    from kubernetes_tpu.audit.auditor import write_bundle
    bundle_path = write_bundle(
        str(tmp_path), "incident",
        {"invariant": "test_incident", "resourceVersion": "42",
         "podBatch": [f"default/bp{i}" for i in range(6)]})
    trace = trace_from_bundle(bundle_path, nodes=4)
    path = str(tmp_path / "incident.trace.jsonl")
    trace.save(path)
    replayed = Trace.load(path)
    assert replayed == trace
    result, _ = _replay_against_live_stack(replayed)
    assert result["completed"], result
    assert result["bound"] == result["resident"] == 6
    assert result["phases"]["incident"]["bound"] == 6


class _NullRes:
    def create(self, obj):
        pass


class _NullClient:
    def pods(self, ns):
        return _NullRes()

    def nodes(self):
        return _NullRes()


def _warp_trace():
    return Trace(
        TraceManifest(name="warp", templates={"pod": {
            "kind": "Pod", "metadata": {}, "spec": {}}}),
        [TraceEvent(at_s=i * 0.2, verb="create", kind="Pod",
                    ns="default", name=f"w{i}", template="pod")
         for i in range(3)])


def test_driver_time_warp_paces_dispatch():
    """speed warps dispatch pacing: the 0.4s trace dispatches in >= 0.2s
    at speed 2, and near-instantly at speed 0 (as fast as possible)."""
    fast = ScenarioDriver(_NullClient(), _warp_trace(), speed=0.0,
                          publish=False, bind_timeout_s=0.0).run()
    assert fast["dispatched"] == 3
    assert fast["dispatch_s"] < 0.2  # no pacing at speed 0
    paced = ScenarioDriver(_NullClient(), _warp_trace(), speed=2.0,
                           publish=False, bind_timeout_s=0.0).run()
    assert paced["dispatched"] == 3
    assert paced["dispatch_s"] >= 0.2  # 0.4s of trace time at 2x
    assert paced["resident"] == 3  # nothing binds (null client)
    assert paced["bound"] == 0 and not paced["completed"]


def test_driver_counts_dispatch_errors():
    """API errors during dispatch are counted and listed, never raised —
    a replayed incident is expected to hit conflicts."""

    class _Boom:
        def create(self, obj):
            raise RuntimeError("conflict")

    class _BoomClient:
        def pods(self, ns):
            return _Boom()

        def nodes(self):
            return _Boom()

    res = ScenarioDriver(_BoomClient(), _warp_trace(), speed=0.0,
                         publish=False, bind_timeout_s=0.0).run()
    assert res["dispatched"] == 3
    assert res["error_count"] == 3
    assert "RuntimeError" in res["errors"][0]


# ---- workloads seed audit -------------------------------------------------

def _seeded_content(objs):
    """Object dicts minus the wrapper's bookkeeping fields (uid counter,
    wall-clock creationTimestamp) — the seed governs everything else."""
    out = []
    for o in objs:
        d = o.to_dict()
        for k in ("uid", "creationTimestamp", "resourceVersion"):
            d.get("metadata", {}).pop(k, None)
        out.append(d)
    return out


def test_workloads_same_seed_twice_identical():
    """The seed-threading contract the scenario generators rely on:
    mixed_heterogeneous/huge_cluster derive ALL randomness from the
    passed seed — same seed, same objects; different seed, different."""
    from benchmarks.workloads import huge_cluster, mixed_heterogeneous
    for fn, kw in ((mixed_heterogeneous, {"pods": 40, "nodes": 20}),
                   (huge_cluster, {"pods": 12, "nodes": 16})):
        n1, p1 = fn(seed=3, **kw)
        n2, p2 = fn(seed=3, **kw)
        assert _seeded_content(n1) == _seeded_content(n2), fn.__name__
        assert _seeded_content(p1) == _seeded_content(p2), fn.__name__
        _, p3 = fn(seed=4, **kw)
        assert _seeded_content(p1) != _seeded_content(p3), fn.__name__
