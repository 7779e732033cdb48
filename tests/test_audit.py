"""Continuous invariant auditor + device-parity sentinel.

Contracts pinned here:

1. DETECTION — forced state corruption (overcommit, double-bind, partial
   gangs, stale nominations, cache/ctx divergence) is caught by the named
   invariant, counted, and repro-bundled; a healthy cluster confirms
   NOTHING (anti-flap).
2. PARITY — a device program that silently returns wrong winners (the
   GSPMD-miscompile class) is refuted by the oracle cross-check and trips
   the circuit breaker with reason "parity"; a device program that RAISES
   trips as "device". After a parity trip the scheduler converges via the
   oracle fallback without losing pods.
3. HYGIENE — stale nominations are garbage-collected by the runner sweep
   and non-daemon thread leaks are detectable (a result without its
   invariant_violations field is refused in tests/test_chip_smoke.py).
"""

import io
import json
import os
import threading
import time
from types import SimpleNamespace

import pytest

from kubernetes_tpu.audit.auditor import (
    InvariantAuditor,
    InvariantViolationError,
)
from kubernetes_tpu.audit.invariants import (
    AuditSnapshot,
    check_ctx_parity,
    run_invariants,
)
from kubernetes_tpu.audit.sentinel import (
    verify_drain_winners,
    verify_wave_results,
)
from kubernetes_tpu.client.clientset import DirectClient
from kubernetes_tpu.config.types import SchedulerConfiguration, validate
from kubernetes_tpu.sched.cache import SchedulerCache
from kubernetes_tpu.sched.queue import SchedulingQueue
from kubernetes_tpu.sched.runner import SchedulerRunner
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.store.store import ObjectStore
from kubernetes_tpu.testing.wrappers import make_node, make_pod

pytestmark = pytest.mark.audit


def wait_for(pred, timeout=15.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _nodes(n, cpu="4", pods="16"):
    return [make_node(f"n{i}")
            .capacity({"cpu": cpu, "memory": "8Gi", "pods": pods})
            .label("kubernetes.io/hostname", f"n{i}")
            .obj() for i in range(n)]


def _auditor(store, cache=None, scheduler=None, tmp_path=None, **kw):
    return InvariantAuditor(client=DirectClient(store), cache=cache,
                            scheduler=scheduler,
                            audit_dir=str(tmp_path) if tmp_path else None,
                            **kw)


# ---- 1. invariant detection ----------------------------------------------

def test_overcommit_detected_and_bundled(tmp_path):
    store = ObjectStore()
    client = DirectClient(store)
    client.nodes().create(make_node("n0").capacity(
        {"cpu": "2", "memory": "4Gi", "pods": "10"}).obj().to_dict())
    for i in range(2):
        client.pods().create(make_pod(f"p{i}").req({"cpu": "1500m"})
                             .node("n0").obj().to_dict())
    auditor = _auditor(store, tmp_path=tmp_path)
    fresh = auditor.run_once()
    assert [v.invariant for v in fresh] == ["node_overcommit"]
    assert "cpu" in fresh[0].detail
    # repro bundle on disk, replayable fields present
    bundles = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert len(bundles) == 1
    payload = json.loads((tmp_path / bundles[0]).read_text())
    assert payload["invariant"] == "node_overcommit"
    assert "chaosSeed" in payload and "podBatch" in payload
    assert payload["objects"][0]["node"] == "n0"
    # same corruption is not re-counted every sweep
    assert auditor.run_once() == []
    assert auditor.total_violations == 1
    assert auditor.status()["byInvariant"] == {"node_overcommit": 1}


def test_overcommit_counts_assumed_pods(tmp_path):
    """A wrong ASSUME overbooks a node before any binding exists in the
    API — the auditor must see scheduler-side optimism too."""
    store = ObjectStore()
    client = DirectClient(store)
    client.nodes().create(make_node("n0").capacity(
        {"cpu": "2", "memory": "4Gi", "pods": "10"}).obj().to_dict())
    cache = SchedulerCache()
    cache.add_node(make_node("n0").capacity(
        {"cpu": "2", "memory": "4Gi", "pods": "10"}).obj())
    for i in range(2):
        pod = make_pod(f"a{i}").req({"cpu": "1500m"}).obj()
        client.pods().create(pod.to_dict())  # pending in the API
        cache.assume(pod, "n0")              # but double-booked by assume
    auditor = _auditor(store, cache=cache, tmp_path=tmp_path)
    fresh = auditor.run_once()
    assert [v.invariant for v in fresh] == ["node_overcommit"]


def test_double_bind_confirms_across_sweeps(tmp_path):
    store = ObjectStore()
    client = DirectClient(store)
    client.nodes().create(_nodes(2)[0].to_dict())
    pod = make_pod("p0").req({"cpu": "100m"}).obj()
    client.pods().create(make_pod("p0").req({"cpu": "100m"})
                         .node("n1").obj().to_dict())
    cache = SchedulerCache()
    cache.assume(pod, "n0")  # scheduler thinks n0; apiserver says n1
    auditor = _auditor(store, cache=cache, tmp_path=tmp_path)
    assert auditor.run_once() == []  # first sighting: could be a race
    fresh = auditor.run_once()       # persisted: corruption
    assert [v.invariant for v in fresh] == ["double_bind"]
    assert "n0" in fresh[0].detail and "n1" in fresh[0].detail


def test_gang_atomicity_partial_flagged_full_clean(tmp_path):
    store = ObjectStore()
    client = DirectClient(store)
    for n in _nodes(2):
        client.nodes().create(n.to_dict())
    # gang g1: half bound (older than one sweep -> violation)
    client.pods().create(make_pod("g1a").label(
        "kubernetes-tpu.io/gang", "g1").node("n0").obj().to_dict())
    client.pods().create(make_pod("g1b").label(
        "kubernetes-tpu.io/gang", "g1").obj().to_dict())
    # gang g2: fully bound (clean)
    for m in ("a", "b"):
        client.pods().create(make_pod(f"g2{m}").label(
            "kubernetes-tpu.io/gang", "g2").node("n1").obj().to_dict())
    auditor = _auditor(store, tmp_path=tmp_path)
    assert auditor.run_once() == []
    fresh = auditor.run_once()
    assert [(v.invariant, v.fingerprint[1]) for v in fresh] \
        == [("gang_atomicity", "g1")]


def test_cache_parity_phantom_pod(tmp_path):
    store = ObjectStore()
    client = DirectClient(store)
    client.nodes().create(_nodes(1)[0].to_dict())
    cache = SchedulerCache()
    cache.add_pod(make_pod("ghost").node("n0").obj())  # not in the API
    auditor = _auditor(store, cache=cache, tmp_path=tmp_path)
    assert auditor.run_once() == []
    assert auditor.run_once() == []
    fresh = auditor.run_once()  # confirm=3 for phantom pods
    assert [v.invariant for v in fresh] == ["cache_parity"]
    assert "ghost" in fresh[0].detail


def test_ctx_parity_unit():
    base = dict(ts=0.0, rv=None, api_pods=[], api_nodes=[],
                cache={"bound": {"default/p1": "n0"}, "assumed": {},
                       "nodes": {"n0"}, "generation": 1})
    ctx = {"profile": "default-scheduler", "tainted": False, "seq": 0,
           "fill_bound": 1, "fill_host": 1, "top": 8,
           "folded": {"default/p1": "n0", "default/p2": "n1"},
           "mesh_epoch": 0, "pending": 0}
    # p2 folded but unknown to the cache and no pending delta explains it
    snap = AuditSnapshot(**base, ctx=ctx, ctx_pending_keys=set())
    out = check_ctx_parity(snap)
    assert [v.fingerprint[1] for v in out] == ["default/p2"]
    # a pending delta for p2 exempts it (the ctx just hasn't consumed it)
    snap = AuditSnapshot(**base, ctx=ctx, ctx_pending_keys={"default/p2"})
    assert check_ctx_parity(snap) == []
    # tainted ctx is declared unaccountable: no judgment
    snap = AuditSnapshot(**base, ctx=dict(ctx, tainted=True),
                         ctx_pending_keys=set())
    assert check_ctx_parity(snap) == []
    # fold accounting gone negative (top is a downward cursor and NOT
    # comparable to the watermark — only negativity is judgeable)
    snap = AuditSnapshot(**base, ctx=dict(ctx, fill_bound=-1,
                                          folded={"default/p1": "n0"}),
                         ctx_pending_keys=set())
    assert any(v.fingerprint[1] == "fill" for v in check_ctx_parity(snap))


def test_fail_fast_raises(tmp_path):
    store = ObjectStore()
    client = DirectClient(store)
    client.nodes().create(make_node("n0").capacity(
        {"cpu": "1", "pods": "10"}).obj().to_dict())
    client.pods().create(make_pod("p0").req({"cpu": "2"})
                         .node("n0").obj().to_dict())
    auditor = _auditor(store, tmp_path=tmp_path, fail_fast=True)
    with pytest.raises(InvariantViolationError) as ei:
        auditor.run_once()
    assert ei.value.violations[0].invariant == "node_overcommit"
    assert auditor.failed


def test_clean_connected_runner_confirms_nothing(tmp_path):
    """Anti-flap acceptance: a healthy live runner — binds in flight,
    assumed pods, resident drain ctx — must audit clean sweep after
    sweep."""
    store = ObjectStore()
    truth = DirectClient(store)
    for n in _nodes(4, cpu="8", pods="32"):
        truth.nodes().create(n.to_dict())
    runner = SchedulerRunner(DirectClient(store), SchedulerConfiguration(
        batch_size=8, backoff_initial_s=0.02, backoff_max_s=0.1))
    runner.auditor.audit_dir = str(tmp_path)
    try:
        runner.start()
        for i in range(24):
            truth.pods().create(make_pod(f"cp{i}")
                                .req({"cpu": "200m"}).obj().to_dict())
        assert wait_for(lambda: sum(
            1 for p in truth.pods().list()
            if p["spec"].get("nodeName")) == 24)
        for _ in range(4):
            assert runner.auditor.run_once() == []
        assert runner.auditor.total_violations == 0
    finally:
        runner.stop()


# ---- 2. stale-nomination GC ----------------------------------------------

def test_stale_nomination_gc_clears_bound_and_terminal_only():
    store = ObjectStore()
    client = DirectClient(store)
    client.nodes().create(_nodes(1)[0].to_dict())
    # bound pod with a leftover nomination (preemption churn shape)
    bound = client.pods().create(make_pod("b0").node("n0").obj().to_dict())
    bound.setdefault("status", {})["nominatedNodeName"] = "n0"
    client.pods().update_status(bound)
    # terminal pod with a leftover nomination
    term = client.pods().create(make_pod("t0").obj().to_dict())
    term.setdefault("status", {}).update(
        {"phase": "Succeeded", "nominatedNodeName": "n0"})
    client.pods().update_status(term)
    # PENDING nominee: its reservation is live and must survive the sweep
    pend = client.pods().create(make_pod("p0").obj().to_dict())
    pend.setdefault("status", {})["nominatedNodeName"] = "n0"
    client.pods().update_status(pend)

    runner = SchedulerRunner(DirectClient(store))
    try:
        assert runner.sweep_stale_nominations() == 2
        pods = {p["metadata"]["name"]: p for p in client.pods().list()}
        assert "nominatedNodeName" not in pods["b0"]["status"]
        assert "nominatedNodeName" not in pods["t0"]["status"]
        assert pods["p0"]["status"]["nominatedNodeName"] == "n0"
        assert runner.sweep_stale_nominations() == 0  # idempotent
    finally:
        runner.scheduler.close()


def test_nomination_invariant_flags_what_gc_missed(tmp_path):
    """The auditor's nomination_consistency invariant is the check that
    the GC converged; with the GC as pre-sweep hook, the sweep judges the
    post-GC state and stays clean."""
    store = ObjectStore()
    client = DirectClient(store)
    client.nodes().create(_nodes(1)[0].to_dict())
    bound = client.pods().create(make_pod("b0").node("n0").obj().to_dict())
    bound.setdefault("status", {})["nominatedNodeName"] = "n0"
    client.pods().update_status(bound)
    # without the GC hook: flagged once confirmed
    auditor = _auditor(store, tmp_path=tmp_path)
    assert auditor.run_once() == []
    fresh = auditor.run_once()
    assert [v.invariant for v in fresh] == ["nomination_consistency"]
    # with the GC riding as pre-sweep (the runner wiring): never flagged
    bound2 = client.pods().get("b0")
    bound2.setdefault("status", {})["nominatedNodeName"] = "n0"
    client.pods().update_status(bound2)
    runner = SchedulerRunner(DirectClient(store))
    runner.auditor.audit_dir = str(tmp_path)
    try:
        for _ in range(3):
            assert runner.auditor.run_once() == []
    finally:
        runner.scheduler.close()


# ---- 3. parity sentinel ---------------------------------------------------

def _drain_sched(nodes, batch_size=4, **cfg_kw):
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    queue = SchedulingQueue(backoff_initial=0.01, backoff_max=0.05)
    cfg = SchedulerConfiguration(batch_size=batch_size,
                                 max_drain_batches=2,
                                 backoff_initial_s=0.01,
                                 backoff_max_s=0.05, **cfg_kw)
    log = []
    sched = Scheduler(cfg, cache, queue,
                      lambda pod, node: log.append(
                          (pod.metadata.name, node)) or True)
    return sched, cache, queue, log


def test_parity_sentinel_clean_drain_no_divergence(tmp_path, monkeypatch):
    monkeypatch.setenv("KTPU_AUDIT_DIR", str(tmp_path))
    sched, cache, queue, log = _drain_sched(_nodes(8),
                                            parity_sample_every=1)
    assert sched.sentinel is not None
    warm = [make_pod(f"__warm{i}").req({"cpu": "100m"}).obj()
            for i in range(4)]
    assert sched.warm_drain(warm, slot_headroom=64)
    try:
        for i in range(16):
            queue.add(make_pod(f"d{i}").req({"cpu": "100m"}).obj())
        bound = 0
        for _ in range(20):
            bound += sched.run_once(wait=0.01)
            if bound >= 16:
                break
        bound += sched._resolve_pending()
        assert bound == 16
        sched.sentinel.drain()
        assert sched.sentinel.samples["drain"] >= 1
        assert sched.sentinel.divergences == 0
        assert sched.breaker.mode == "single"
        assert not os.listdir(tmp_path)  # no bundles from a clean run
    finally:
        sched.close()


def test_wrong_winners_trip_parity_and_converge_via_oracle(tmp_path,
                                                           monkeypatch):
    """The acceptance gate: a miscompile simulation (drain returns every
    winner on node 0 — overcommitted, no exception raised) must be
    refuted by the sentinel, trip the breaker with reason 'parity', write
    a repro bundle, and the scheduler must keep binding pods through the
    oracle fallback."""
    from kubernetes_tpu.metrics.registry import PARITY_DIVERGENCES
    import kubernetes_tpu.models.gang as gang_mod
    monkeypatch.setenv("KTPU_AUDIT_DIR", str(tmp_path))
    sched, cache, queue, log = _drain_sched(_nodes(4),
                                            parity_sample_every=1)
    # ground-truth store mirroring the workload: the auditor judges the
    # corrupted assumes against it
    store = ObjectStore()
    truth = DirectClient(store)
    for n in _nodes(4):
        truth.nodes().create(n.to_dict())
    warm = [make_pod(f"__warm{i}").req({"cpu": "1"}).obj()
            for i in range(4)]
    assert sched.warm_drain(warm, slot_headroom=64)
    before = PARITY_DIVERGENCES.get({"site": "drain"})
    orig = gang_mod.drain_step

    def wrong_winners(ct, pb, fill, patch=None, **kw):
        import jax.numpy as jnp
        a, rounds, ct2, fill2 = orig(ct, pb, fill, patch, **kw)
        return jnp.where(a >= 0, 0, a), rounds, ct2, fill2
    monkeypatch.setattr(gang_mod, "drain_step", wrong_winners)
    try:
        # 8 x 1cpu onto 4cpu nodes: all-on-n0 is a 2x overcommit
        for i in range(8):
            pod = make_pod(f"w{i}").req({"cpu": "1"}).obj()
            truth.pods().create(pod.to_dict())
            queue.add(pod)
        bound = 0
        for _ in range(10):
            bound += sched.run_once(wait=0.01)
            bound += sched._resolve_pending()
            if bound >= 8:
                break
        sched.sentinel.drain()
        assert wait_for(lambda: sched.breaker.mode == "oracle", timeout=5)
        assert sched.breaker.last_trip_reason == "parity"
        assert sched.breaker.trip_reasons.get("parity", 0) >= 1
        assert PARITY_DIVERGENCES.get({"site": "drain"}) > before
        last = sched.sentinel.last_divergence
        assert last is not None and last["site"] == "drain"
        bundles = [f for f in os.listdir(tmp_path) if "parity" in f]
        assert bundles, "divergence must write a repro bundle"
        payload = json.loads((tmp_path / bundles[0]).read_text())
        assert payload["problems"]
        # the AUDITOR catches the same corruption by name: the wrong
        # assumes overbook n0 against the apiserver's view
        auditor = _auditor(store, cache=cache, tmp_path=tmp_path)
        caught = auditor.run_once()
        assert "node_overcommit" in [v.invariant for v in caught]
        assert any("node_overcommit" in f for f in os.listdir(tmp_path))
        # convergence: with the device still lying, the oracle floor keeps
        # binding — a fresh batch schedules to 100%
        for i in range(8):
            queue.add(make_pod(f"o{i}").req({"cpu": "1"}).obj())
        bound2 = 0
        for _ in range(30):
            bound2 += sched.run_once(wait=0.01)
            if bound2 >= 8:
                break
        assert bound2 == 8
        sched.wait_for_bindings()
        assert len(log) >= 16
    finally:
        sched.close()


def test_device_fault_trips_as_device_not_parity():
    """Attribution: a drain_step that RAISES (chaos device fault) must
    trip via the consecutive-failure path with reason 'device' — never
    'parity' (no answer was produced to refute)."""
    from kubernetes_tpu.chaos import DeviceChaos, Fault, FaultSchedule
    sched, cache, queue, log = _drain_sched(_nodes(4),
                                            parity_sample_every=1,
                                            breaker_threshold=1)
    warm = [make_pod(f"__warm{i}").req({"cpu": "100m"}).obj()
            for i in range(4)]
    assert sched.warm_drain(warm, slot_headroom=64)
    schedule = FaultSchedule([Fault("device.drain", "runtime", 0, 1)])
    chaos = DeviceChaos(schedule).install()
    try:
        for i in range(8):
            queue.add(make_pod(f"f{i}").req({"cpu": "100m"}).obj())
        bound = 0
        for _ in range(20):
            bound += sched.run_once(wait=0.01)
            if bound >= 8:
                break
        bound += sched._resolve_pending()
        assert bound == 8
        assert sched.breaker.trips >= 1
        assert sched.breaker.last_trip_reason == "device"
        assert "parity" not in sched.breaker.trip_reasons
    finally:
        chaos.uninstall()
        sched.close()


def test_trip_now_stale_level_ignored():
    """A parity verdict attributed to a level that is no longer active
    (the breaker restored past it while the verdict was in flight) must
    not degrade the level nobody refuted."""
    from kubernetes_tpu.sched.resilience import DeviceCircuitBreaker
    from kubernetes_tpu.utils.clock import FakeClock
    clock = FakeClock(0.0)
    br = DeviceCircuitBreaker(levels=("mesh", "single", "oracle"),
                              threshold=1, cooldown_s=10.0, clock=clock)
    br.fail("mesh")
    assert br.mode == "single"
    clock.advance(11.0)
    assert br.attempt_level() == "mesh"  # half-open probe
    br.succeed("mesh")
    assert br.mode == "mesh"
    # stale verdict for the since-restored-past level: ignored
    assert br.trip_now("single", "parity") == "mesh"
    assert "parity" not in br.trip_reasons
    # active-level verdict: immediate one-step degrade, reason recorded
    assert br.trip_now("mesh", "parity") == "single"
    assert br.last_trip_reason == "parity"
    assert br.trip_reasons == {"device": 1, "parity": 1}


def test_auditor_post_sweep_hook_fires(tmp_path):
    """Every background sweep runs the post-sweep hook (the runner hooks
    publish_status here so `ktpu audit status` reads LIVE state, not the
    start-time snapshot)."""
    store = ObjectStore()
    DirectClient(store).nodes().create(_nodes(1)[0].to_dict())
    published = []
    auditor = InvariantAuditor(client=DirectClient(store),
                               audit_dir=str(tmp_path), interval_s=0.05,
                               post_sweep=lambda: published.append(1))
    auditor.start()
    try:
        assert wait_for(lambda: auditor.sweeps >= 2 and len(published) >= 2,
                        timeout=10)
    finally:
        auditor.stop()


def test_verify_drain_winners_unit():
    nodes = _nodes(2, cpu="2")
    p0 = make_pod("p0").req({"cpu": "1500m"}).obj()
    p1 = make_pod("p1").req({"cpu": "1500m"}).obj()
    # sound: one per node
    assert verify_drain_winners(nodes, [], [(p0, "n0"), (p1, "n1")],
                                []) == []
    # overcommit: both on n0
    problems = verify_drain_winners(nodes, [], [(p0, "n0"), (p1, "n0")],
                                    [])
    assert any("overcommitted" in s for s in problems)
    # bound state counts; an EXEMPT bound pod does not (the device
    # provably had not seen it)
    b = make_pod("b0").req({"cpu": "1500m"}).node("n0").obj()
    assert verify_drain_winners(nodes, [b], [(p0, "n0")], [])
    assert verify_drain_winners(nodes, [b], [(p0, "n0")], [],
                                exempt=frozenset({b.key})) == []
    # prior in-flight drains' winners count like bound state
    assert verify_drain_winners(nodes, [], [(p0, "n0")], [(p1, "n0")])


def test_verify_wave_results_unit():
    from kubernetes_tpu.sched.preemption import PreemptionResult
    nodes = _nodes(1, cpu="2")
    victim = make_pod("v0").req({"cpu": "1500m"}).priority(1) \
        .node("n0").obj()
    pre = make_pod("hi").req({"cpu": "1500m"}).priority(100).obj()
    sound = PreemptionResult(node_name="n0", victims=[victim])
    assert verify_wave_results(nodes, [victim], [pre], [sound]) == []
    # equal-priority victim is never evictable
    peer = make_pod("peer").req({"cpu": "1500m"}).priority(100) \
        .node("n0").obj()
    bad = PreemptionResult(node_name="n0", victims=[peer])
    assert any("equal/higher-priority" in s for s in
               verify_wave_results(nodes, [peer], [pre], [bad]))
    # victim not on the named node
    stray = make_pod("stray").req({"cpu": "1"}).priority(1).node("nX").obj()
    ghost = PreemptionResult(node_name="n0", victims=[stray])
    assert any("not a bound pod" in s for s in
               verify_wave_results(nodes, [victim, stray], [pre], [ghost]))
    # evictions that still leave the preemptor infeasible
    small = make_pod("small").req({"cpu": "100m"}).priority(1) \
        .node("n0").obj()
    weak = PreemptionResult(node_name="n0", victims=[small])
    assert any("still infeasible" in s for s in verify_wave_results(
        nodes, [victim, small], [pre], [weak]))


# ---- 4. surfaces: CLI, config, thread-leak detector ----------------------

def test_ktpu_audit_status():
    from kubernetes_tpu.cli.ktpu import cmd_audit
    store = ObjectStore()
    runner = SchedulerRunner(DirectClient(store))
    try:
        runner.publish_status()
        out = io.StringIO()
        rc = cmd_audit(runner.client,
                       SimpleNamespace(namespace="default", output="json"),
                       out)
        assert rc == 0
        audit = json.loads(out.getvalue())
        assert audit["violations"] == 0 and "parity" in audit
        assert audit["parity"]["every"] == runner.cfg.parity_sample_every
        out = io.StringIO()
        rc = cmd_audit(runner.client,
                       SimpleNamespace(namespace="default", output=None),
                       out)
        assert rc == 0
        text = out.getvalue()
        assert "Violations:    0" in text and "Parity:" in text
    finally:
        runner.scheduler.close()


def test_audit_config_knobs():
    cfg = SchedulerConfiguration.from_dict({
        "auditIntervalSeconds": 5, "auditFailFast": True,
        "paritySampleEvery": 3})
    assert cfg.audit_interval_s == 5.0
    assert cfg.audit_fail_fast is True
    assert cfg.parity_sample_every == 3
    validate(cfg)
    from kubernetes_tpu.config.types import ValidationError
    import dataclasses
    with pytest.raises(ValidationError):
        validate(dataclasses.replace(cfg, audit_interval_s=0))
    with pytest.raises(ValidationError):
        validate(dataclasses.replace(cfg, parity_sample_every=-1))
    # paritySampleEvery: 0 disables the sentinel
    sched, *_ = _drain_sched(_nodes(1), parity_sample_every=0)
    try:
        assert sched.sentinel is None
    finally:
        sched.close()


def test_thread_leak_detector_helper():
    import conftest
    baseline = {t.ident for t in threading.enumerate() if not t.daemon}
    ev = threading.Event()
    t = threading.Thread(target=ev.wait, name="leaky", daemon=False)
    t.start()
    try:
        leaked = conftest._leaked_nondaemon(baseline, grace_s=0.1)
        assert any(x.name == "leaky" for x in leaked)
    finally:
        ev.set()
        t.join()
    assert conftest._leaked_nondaemon(baseline, grace_s=0.5) == []
