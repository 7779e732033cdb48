"""One resident scheduling program: fused churn folds + resident preemption.

PR tentpole coverage: (a) churn deltas ride the drain dispatch as
``drain_step``'s third donated input (models/gang.py) instead of a separate
blocking ``apply_ctx_patch`` dispatch, and fold-SAFE churn no longer drains
the multi-deep dispatch pipeline first (encode/patch.py entries_fold_safe);
(b) the preemption wave shares the device-resident cluster image — static
masks on the resident encoding in place, per-node totals read back from it,
victim request vectors from its fold ledger — instead of re-encoding
``nodes``/``bound_pods`` per wave.

The parity tests are the contract: a context that folds churn must place
exactly as one rebuilt from the host snapshot before every drain on the
same delta log, and the resident wave must return exactly
what the snapshot-path wave returns (PDB budgets, victim sets, dedup
included) — the fusion is an optimization, never a semantics fork.
"""

import threading

import numpy as np
import pytest

from kubernetes_tpu.config.types import SchedulerConfiguration, validate
from kubernetes_tpu.sched.cache import SchedulerCache
from kubernetes_tpu.sched.queue import SchedulingQueue
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _nodes(n, cpu="4", prefix="n"):
    return [make_node(f"{prefix}{i}")
            .capacity({"cpu": cpu, "memory": "8Gi", "pods": "32"})
            .obj() for i in range(n)]


def _sched(nodes, batch_size=4, drain_batches=2,
           pipeline_depth=2, parity_every=0, cfg=None):
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    queue = SchedulingQueue(backoff_initial=0.05)
    log = []
    cfg = cfg or SchedulerConfiguration(batch_size=batch_size,
                                        max_drain_batches=drain_batches,
                                        pipeline_depth=pipeline_depth,
                                        parity_sample_every=parity_every)
    sched = Scheduler(cfg, cache, queue,
                      lambda pod, node: log.append(
                          (pod.metadata.name, node)) or True)
    return sched, cache, queue, log


def _arm(sched, slot_headroom=128):
    warm = [make_pod(f"__warm{i}").req({"cpu": "100m"}).obj()
            for i in range(sched.cfg.batch_size)]
    assert sched.warm_drain(warm, slot_headroom=slot_headroom)
    return sched._drain_ctx


def _drain(sched, queue, pods, rounds=8):
    for p in pods:
        queue.add(p)
    bound = 0
    for _ in range(rounds):
        bound += sched.run_once(wait=0.01)
        if not sched._pending and not queue.stats()["active"]:
            break
    bound += sched._resolve_pending()
    sched.wait_for_bindings()
    return bound


# ---- tentpole (a): fused fold vs rebuild-from-snapshot parity -------------

def _churn_script(rng, cache, i):
    """One randomized churn op against the cache (the delta-log feed)."""
    op = rng.integers(0, 4)
    if op == 0:  # foreign bound pod lands
        cache.add_pod(make_pod(f"foreign{i}").req({"cpu": "300m"})
                      .node(f"n{int(rng.integers(0, 3))}").obj())
    elif op == 1:  # foreign pod leaves
        cache.remove_pod(f"default/foreign{max(0, i - 2)}")
    elif op == 2:  # node add
        cache.add_node(make_node(f"late{i}")
                       .capacity({"cpu": "2", "memory": "4Gi", "pods": "8"})
                       .obj())
    else:  # node relabel (upsert of an existing node)
        cache.add_node(make_node("n0")
                       .capacity({"cpu": "4", "memory": "8Gi", "pods": "32"})
                       .label("churn", f"v{i}").obj())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fused_fold_matches_apply_then_dispatch(seed):
    """Randomized parity: the SAME delta log driven through a scheduler
    that folds churn into its resident context and through one whose
    context is discarded before every drain (so each drain rebuilds from
    the host snapshot: the independent answer a fold must equal) must bind
    the same pods to the same nodes."""
    rng = np.random.default_rng(seed)
    script = []  # (kind, payload) replayed identically against both
    for i in range(6):
        script.append(("churn", int(rng.integers(0, 4)), i))
        script.append(("drain", i))

    def run(resident):
        sched, cache, queue, log = _sched(_nodes(3))
        _arm(sched)
        for step in script:
            if step[0] == "churn":
                _churn_script(np.random.default_rng(step[1] * 7 + step[2]),
                              cache, step[2])
            else:
                i = step[1]
                if not resident:
                    # _drain resolved everything: nothing in flight reads it
                    sched._drain_ctx = None
                # more than one batch: a pop this deep takes the drain path
                # with or without a context to ride
                got = _drain(sched, queue,
                             [make_pod(f"m{i}-{j}").req({"cpu": "200m"}).obj()
                              for j in range(6)])
                assert got == 6, f"step {i} lost pods (resident={resident})"
        stats = dict(sched.ctx_stats)
        sched.close()
        return sorted(log), stats  # bind workers race log order, not content

    log_fused, stats_fused = run(True)
    log_rebuilt, stats_rebuilt = run(False)
    assert log_fused == log_rebuilt, (log_fused, log_rebuilt)
    # each run took its own way through the churn at least once
    assert stats_fused["patches"] == 0
    assert stats_fused["folds"] >= 1
    assert stats_rebuilt["folds"] == 0
    assert stats_rebuilt["rebuilds"] >= 1


def test_removed_switch_keys_load_as_unknown_keys_and_the_drain_folds(
        tmp_path):
    """``fusedFold`` and ``stagingArena`` were fields once. A file that
    still carries them loads and validates like one with any unknown key,
    and the scheduler built from it folds churn into the dispatch."""
    path = tmp_path / "scheduler.yaml"
    path.write_text("batchSize: 4\nmaxDrainBatches: 2\n"
                    "fusedFold: false\nstagingArena: false\n")
    cfg = SchedulerConfiguration.from_yaml(str(path))
    validate(cfg)
    assert cfg.batch_size == 4 and cfg.max_drain_batches == 2
    assert vars(cfg) == vars(SchedulerConfiguration.from_dict(
        {"batchSize": 4, "maxDrainBatches": 2}))
    sched, cache, queue, log = _sched(_nodes(3), cfg=cfg)
    ctx = _arm(sched)
    cache.add_pod(make_pod("foreign").req({"cpu": "300m"}).node("n1").obj())
    assert _drain(sched, queue,
                  [make_pod(f"m{j}").req({"cpu": "200m"}).obj()
                   for j in range(4)]) == 4
    assert sched._drain_ctx is ctx
    assert sched.ctx_stats["folds"] >= 1 and sched.ctx_stats["patches"] == 0
    # every key stays for the readers that print each of them
    assert set(sched.ctx_stats) == {"patches", "folds", "rebuilds", "unfit",
                                    "reasons"}
    assert cache.staging_stats()["enabled"] is False  # no mesh, no arena
    sched.close()


def test_fold_safe_churn_does_not_drain_the_pipeline():
    """The serialize-on-churn fix: with a drain IN FLIGHT, fold-safe foreign
    churn (a bound pod landing) must compile into the next dispatch without
    resolving the pipeline; fold-UNSAFE churn (a node delete, whose retire
    accounting cannot see in-flight folds) must still resolve first."""
    sched, cache, queue, log = _sched(_nodes(4), pipeline_depth=2)
    _arm(sched)
    # park resolution: dispatched drains stay in flight until we say so
    parked = []

    def park(pend):
        pend["done"] = threading.Event()
        parked.append(pend)
    sched._submit_resolve = park
    resolves = {"n": 0}
    orig_rp = sched._resolve_pending

    def counting_rp():
        resolves["n"] += 1
        return orig_rp()
    sched._resolve_pending = counting_rp

    def release_and_resolve():
        for pend in parked:
            pend["done"].set()  # inline fetch takes over immediately
        n = orig_rp()
        parked.clear()
        return n

    # cycle 1: drain dispatches, stays in flight
    for p in [make_pod(f"a{j}").req({"cpu": "200m"}).obj() for j in range(4)]:
        queue.add(p)
    sched.run_once(wait=0.01)
    assert len(sched._pending) == 1

    # fold-safe foreign churn + cycle 2: NO pipeline drain, one fused fold
    cache.add_pod(make_pod("foreign").req({"cpu": "300m"}).node("n1").obj())
    for p in [make_pod(f"b{j}").req({"cpu": "200m"}).obj() for j in range(4)]:
        queue.add(p)
    sched.run_once(wait=0.01)
    assert resolves["n"] == 0, "fold-safe churn drained the pipeline"
    assert len(sched._pending) == 2
    assert sched.ctx_stats["folds"] == 1
    assert sched.ctx_stats["patches"] == 0

    # fold-UNSAFE churn (node delete) with drains in flight: resolve first
    # (the forced resolve's bounded wait degrades to an inline fetch — cut
    # the wait short so the test doesn't idle 30s against a parked Event)
    import kubernetes_tpu.sched.scheduler as sched_mod
    saved_wait = sched_mod.RESOLVE_WAIT_S
    sched_mod.RESOLVE_WAIT_S = 0.3
    try:
        cache.remove_node("n3")
        for p in [make_pod(f"c{j}").req({"cpu": "200m"}).obj()
                  for j in range(4)]:
            queue.add(p)
        sched.run_once(wait=0.01)
        assert resolves["n"] >= 1, "node delete must settle in-flight folds"
    finally:
        sched_mod.RESOLVE_WAIT_S = saved_wait

    release_and_resolve()
    sched.wait_for_bindings()
    assert len(log) == 12, (len(log), log)
    assert not any(node == "n3" for name, node in log
                   if name.startswith("c")), log
    sched.close()


def test_steady_state_churn_zero_separate_patch_dispatches():
    """Acceptance: a fused-mode churn storm (the scheduler_perf recreate
    shape) keeps ctx_stats['patches'] at 0 — every delta folds on-device
    inside a dispatch — and the context never rebuilds."""
    sched, cache, queue, log = _sched(_nodes(4))
    ctx = _arm(sched)
    for i in range(6):
        cache.add_node(make_node(f"churn-n{i}")
                       .capacity({"cpu": "2", "memory": "4Gi", "pods": "8"})
                       .obj())
        if i >= 2:
            cache.remove_node(f"churn-n{i-2}")
            cache.remove_pod(f"default/m{i-2}")
        assert _drain(sched, queue,
                      [make_pod(f"m{i}").req({"cpu": "100m"}).obj()]) == 1
        assert sched._drain_ctx is ctx, f"context rebuilt at cycle {i}"
    assert sched.ctx_stats["patches"] == 0, sched.ctx_stats
    assert sched.ctx_stats["folds"] >= 6, sched.ctx_stats
    assert sched.ctx_stats["rebuilds"] == 0, sched.ctx_stats
    sched.close()


def test_sentinel_judges_deltas_folded_inside_a_dispatch():
    """Parity sentinel vs the fused fold: deltas folded INSIDE a sampled
    dispatch are part of what the device saw (the scatter applies in front
    of the scan), and the capture's log cursor is taken after the advance —
    so a correct fused program must produce zero divergences even when the
    sampled dispatch itself carried churn."""
    sched, cache, queue, log = _sched(_nodes(3), parity_every=1)
    _arm(sched)
    assert sched.sentinel is not None
    for i in range(3):
        cache.add_pod(make_pod(f"f{i}").req({"cpu": "500m"})
                      .node(f"n{i}").obj())  # fold-safe churn, every cycle
        assert _drain(sched, queue,
                      [make_pod(f"m{i}-{j}").req({"cpu": "200m"}).obj()
                       for j in range(4)]) == 4
    sched.sentinel.drain()
    assert sched.ctx_stats["folds"] >= 1
    assert sched.sentinel.samples["drain"] >= 1
    assert sched.sentinel.divergences == 0, sched.sentinel.last_divergence
    sched.close()


# ---- tentpole (b): resident preemption wave -------------------------------

def _preempt_fixture(pdb=False):
    """A saturated little cluster scheduled THROUGH the drain (so the
    resident context's fold ledger owns the placements), plus high-priority
    preemptors."""
    sched, cache, queue, log = _sched(_nodes(6, cpu="2"))
    _arm(sched)
    low = [make_pod(f"low{i}").req({"cpu": "1500m"}).priority(1)
           .label("app", "victim").obj() for i in range(6)]
    assert _drain(sched, queue, low) == 6
    if pdb:
        sched.pdb_lister = lambda: [{
            "metadata": {"name": "pdb", "namespace": "default"},
            "spec": {"selector": {"matchLabels": {"app": "victim"}},
                     "minAvailable": 4},
            "status": {}}]
    views = [make_pod(f"hi{i}").req({"cpu": "1800m"}).priority(100).obj()
             for i in range(3)]
    return sched, cache, views


def _norm(results):
    return [(r.node_name, sorted(v.key for v in r.victims),
             r.num_pdb_violations) if r else None for r in results]


@pytest.mark.parametrize("pdb", [False, True])
def test_resident_wave_parity_with_snapshot_wave(pdb):
    """The wave riding the resident context must return EXACTLY what the
    snapshot-path wave returns — same winners, same victim sets (deduped
    across picks by the shared sequential commit), same PDB-violation
    counts charged against the same budgets."""
    import kubernetes_tpu.sched.preemption as pmod
    sched, cache, views_src = _preempt_fixture(pdb=pdb)
    view = sched._resident_wave_view()
    assert view is not None, "fixture should leave a current resident ctx"
    bound = cache.bound_pods(include_assumed=True)
    pdbs = sched.pdb_lister()
    masks = pmod.tensor_static_masks(
        view["nodes"], views_src, ct=view["ct"], meta=view["meta"],
        encode_pods=cache.encode_pods, min_p=4, pre_staged=True,
        node_rows=view["rows"])
    resident = pmod.preempt_wave(
        view["nodes"], bound, views_src, pdbs=pdbs, static_masks=masks,
        min_q=4, resident_arrays=sched._resident_cluster_arrays(view),
        req_lookup=sched._resident_req_lookup(view))
    plain = pmod.preempt_wave(view["nodes"], bound, views_src, pdbs=pdbs,
                              min_q=4)
    assert _norm(resident) == _norm(plain)
    # victim dedup holds across the wave's sequential commits
    evicted = [v.key for r in resident if r for v in r.victims]
    assert len(evicted) == len(set(evicted))
    sched.close()


def test_resident_cluster_arrays_match_host_encode():
    """The arrays the resident path feeds dry_run_wave — totals read back
    from the device-resident encoding, victim vectors from the fold
    ledger — must equal the host encode bit for bit (same scaled-integer
    arithmetic, same implicit 'pods' slot, same UNLIMITED caps)."""
    from kubernetes_tpu.ops.preemption import _encode_cluster_arrays
    sched, cache, views = _preempt_fixture()
    # foreign churn so the ledger holds PATCHED vectors too, not just folds
    cache.add_pod(make_pod("patched").req({"cpu": "250m"}).priority(1)
                  .node("n0").obj())
    # a probe drain consumes the delta (fused fold) so the ctx is current
    assert _drain(sched, sched.queue,
                  [make_pod("probe").req({"cpu": "100m"}).obj()]) == 1
    view = sched._resident_wave_view()
    assert view is not None
    bound = cache.bound_pods(include_assumed=True)
    resources = sorted({**dict(views[0].resource_requests())})
    host = _encode_cluster_arrays(view["nodes"], bound, resources, 100, [])
    res = _encode_cluster_arrays(
        view["nodes"], bound, resources, 100, [],
        resident_arrays=sched._resident_cluster_arrays(view),
        req_lookup=sched._resident_req_lookup(view))
    for a, b, name in zip(host, res, ("allocatable", "requested", "vic_req",
                                      "vic_valid", "vic_violating",
                                      "vic_prio", "vic_ref")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    sched.close()


def test_resident_wave_declines_when_stale_or_inflight():
    """Discipline: the resident view must refuse to stand in for a snapshot
    when the context is tainted, lags the delta log, was staged under an
    old mesh epoch, or when drains are still in flight (their folds are in
    the resident totals but not in the cache's bound view)."""
    sched, cache, _ = _preempt_fixture()
    assert sched._resident_wave_view() is not None
    # unconsumed foreign delta -> stale
    cache.add_pod(make_pod("fresh").req({"cpu": "100m"}).node("n0").obj())
    assert sched._resident_wave_view() is None
    # a probe drain folds the delta in: current again
    assert _drain(sched, sched.queue,
                  [make_pod("probe").req({"cpu": "100m"}).obj()]) == 1
    assert sched._resident_wave_view() is not None
    # in-flight drain -> decline
    sched._pending.append({"chunks": []})
    assert sched._resident_wave_view() is None
    sched._pending.clear()
    # mesh epoch moved -> decline (reshape semantics)
    sched._mesh_epoch += 1
    assert sched._resident_wave_view() is None
    sched._mesh_epoch -= 1
    # tainted -> decline
    sched._drain_ctx["cs"].tainted = True
    assert sched._resident_wave_view() is None
    sched.close()


def test_connected_failure_path_uses_resident_wave():
    """End to end through _handle_failures: a wave of preemptors failing at
    a drain resolve must ride the resident context (no snapshot span), and
    the nominations + evictions must match what the standalone wave
    computes on the same state."""
    import kubernetes_tpu.sched.preemption as pmod
    sched, cache, views = _preempt_fixture()
    bound_before = cache.bound_pods(include_assumed=True)
    expect = _norm(pmod.preempt_wave(
        sched._resident_wave_view()["nodes"], bound_before, views,
        min_q=pmod.WAVE_BUCKET))
    evicted = []
    sched._evict = lambda v: evicted.append(v.key) or \
        cache.remove_pod(v.key)
    noms = sched._default_preempt_wave(views)
    assert noms == [e[0] if e else None for e in expect]
    assert sorted(evicted) == sorted(
        v for e in expect if e for v in e[1])
    sched.close()
