"""Pre-sharded double-buffered batch staging (sched/staging.py) + the
resident-totals host shadow.

The arena's contract: a redeemed swap is bit- and sharding-identical to the
inline ``device_put``, and every invalidation path (mesh reshape,
upload failure, dead stager thread, buffer-full submit) DECLINES into the
inline fallback — placements never depend on which path staged the batch.
The invalidation matrix runs the live scheduler through mesh reshape,
catalog-epoch bumps, sticky row-width growth, ctx taint, and mid-stream
churn, once redeeming tickets and once with every ticket declined, and
diffs placements bit-for-bit.

Mesh-executing tests carry the ``multichip`` marker and gate on the
test_mesh GSPMD canary, like test_mesh_live.
"""

import sys
import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.config.types import SchedulerConfiguration, validate
from kubernetes_tpu.sched.cache import SchedulerCache
from kubernetes_tpu.sched.queue import SchedulingQueue
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.sched.staging import ResidentShadow, StagingArena
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _nodes(n=32):
    return [make_node(f"n{i:03d}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
            .label("kubernetes.io/hostname", f"n{i:03d}")
            .obj() for i in range(n)]


def _pods(n=24, prefix="p", cpu="500m"):
    return [make_pod(f"{prefix}{i:03d}")
            .req({"cpu": cpu, "memory": "256Mi"})
            .label("app", f"g{i % 3}").obj() for i in range(n)]


def _scheduler(mesh_shape=None, nodes=None, batch_size=16, warm=True):
    cfg = SchedulerConfiguration(batch_size=batch_size, max_drain_batches=2,
                                 mesh_shape=mesh_shape)
    validate(cfg)
    cache = SchedulerCache()
    for n in (nodes or _nodes()):
        cache.add_node(n)
    queue = SchedulingQueue(backoff_initial=0.05)
    log = []
    sched = Scheduler(cfg, cache, queue,
                      lambda pod, node: log.append(
                          (pod.metadata.name, node)) or True)
    if warm:
        _warm(sched)
    return sched, cache, queue, log


def _warm(sched):
    warm_pods = [make_pod(f"__warm{i}").req({"cpu": "100m"}).obj()
                 for i in range(sched.cfg.batch_size)]
    assert sched.warm_drain(warm_pods, slot_headroom=256)


def _run_to_empty(sched, queue, pods, rounds=30):
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


def _mesh_or_skip():
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    import test_mesh
    usable, why = test_mesh._sharded_backend_verdict((1, 2))
    if not usable:
        pytest.skip(why)
    from kubernetes_tpu.parallel.mesh import mesh_from_shape
    return mesh_from_shape((1, 2))


def _stack(P=8, R=3):
    """A tiny stacked-batch-shaped pytree (plain dict works for the arena —
    it stages any pytree of numpy leaves)."""
    rng = np.random.default_rng(7)
    return {"requests": rng.integers(0, 100, (2, P, R)).astype(np.int32),
            "pod_valid": np.ones((2, P), bool),
            "labels": rng.integers(-1, 9, (2, P, 4)).astype(np.int32)}


# ---- arena unit contract -------------------------------------------------

@pytest.mark.multichip
def test_arena_submit_redeem_swap():
    mesh = _mesh_or_skip()
    arena = StagingArena()
    stack = _stack()
    t = arena.submit(stack, mesh)
    assert t is not None
    staged = arena.redeem(t, mesh)
    assert staged is not None
    import jax
    for x, y in zip(jax.tree_util.tree_leaves(staged),
                    jax.tree_util.tree_leaves(stack)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    st = arena.stats()
    assert st["swaps"] == 1 and st["fallbacks"] == 0
    assert st["bytesStaged"] > 0 and st["inflight"] == 0
    arena.close()


@pytest.mark.multichip
def test_arena_invalidate_declines_redeem():
    """A mesh install/reshape between submit and redeem must decline the
    swap — the staged buffers carry the OLD layout."""
    mesh = _mesh_or_skip()
    arena = StagingArena()
    t = arena.submit(_stack(), mesh)
    arena.invalidate()
    assert arena.redeem(t, mesh) is None
    assert arena.stats()["fallbacks"] == 1
    # a redeem against a DIFFERENT active mesh declines too
    t2 = arena.submit(_stack(), mesh)
    assert arena.redeem(t2, None) is None
    arena.close()


@pytest.mark.multichip
def test_arena_double_buffer_bound_and_failure():
    mesh = _mesh_or_skip()
    arena = StagingArena(depth=2)
    t1 = arena.submit(_stack(), mesh)
    t2 = arena.submit(_stack(), mesh)
    assert t1 is not None and t2 is not None
    # full-buffer decline, made deterministic: let both uploads land
    # (queue quiet, no concurrent decrements), then pin the in-flight
    # counter at depth — a live three-submit assertion would race the
    # stager's slot release
    assert t1.done.wait(10.0) and t2.done.wait(10.0)
    with arena._lock:
        arena._inflight = arena.depth
    assert arena.submit(_stack(), mesh) is None
    with arena._lock:
        arena._inflight = 0
    assert arena.redeem(t1, mesh) is not None
    assert arena.redeem(t2, mesh) is not None
    # an upload that raises surfaces as a declined redeem, not a crash
    t3 = arena.submit({"bad": object()}, mesh)  # not an array: upload fails
    assert arena.redeem(t3, mesh) is None
    # and the stager thread survives to serve the next submit
    t4 = arena.submit(_stack(), mesh)
    assert arena.redeem(t4, mesh) is not None
    arena.close()


@pytest.mark.multichip
def test_arena_unredeemed_tickets_do_not_leak_slots():
    """A cycle that dies between submit and redeem must not pin a depth
    slot: the slot frees when the UPLOAD finishes, so abandoned tickets
    can never disable the arena for the process lifetime."""
    mesh = _mesh_or_skip()
    arena = StagingArena(depth=2)
    for _ in range(4):  # > 2x depth abandoned tickets
        t = arena.submit(_stack(), mesh)
        assert t is not None
        assert t.done.wait(10.0)
        # never redeemed — the exception-unwound-cycle case
    assert arena.stats()["inflight"] == 0
    t = arena.submit(_stack(), mesh)
    assert arena.redeem(t, mesh) is not None
    arena.close()


def test_single_device_submit_is_none_and_inline_counts_bytes():
    """Single-device: no arena tickets; stage_drain_batch is one EXPLICIT
    device_put whose bytes land on the inline counter."""
    from kubernetes_tpu.metrics.registry import STAGE_BYTES
    cache = SchedulerCache()
    assert cache.stage_submit(_stack()) is None
    before = STAGE_BYTES.get({"path": "inline"})
    staged = cache.stage_drain_batch(_stack())
    import jax
    assert all(hasattr(l, "sharding")
               for l in jax.tree_util.tree_leaves(staged))
    assert STAGE_BYTES.get({"path": "inline"}) > before


# ---- the switches that went: their variables change nothing -------------

@pytest.mark.parametrize("var,value,mesh_shape", [
    ("KTPU_FUSED_FOLD", "0", None),
    ("KTPU_RESOLVER_THREAD", "0", None),
    ("KTPU_SWITCH_INTERVAL", "0.0005", None),
    pytest.param("KTPU_STAGE_ARENA", "0", (1, 2),
                 marks=pytest.mark.multichip),
    pytest.param("KTPU_PRESPLIT", "1", (1, 2),
                 marks=pytest.mark.multichip),
])
def test_a_removed_switch_variable_changes_nothing(monkeypatch, var, value,
                                                   mesh_shape):
    """Each variable once selected another way through the drain path (or,
    for the switch interval, retuned the interpreter). Set to the value
    that did, one churned drain still folds its deltas into the dispatch,
    fetches on the resolver thread and leaves the interpreter alone; under
    a mesh it also stages through the arena, with one sharded put of the
    whole stack on the stager thread."""
    import jax
    from kubernetes_tpu.client.clientset import DirectClient
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.store import ObjectStore
    if mesh_shape is not None:
        _mesh_or_skip()
    monkeypatch.setenv(var, value)
    interval = sys.getswitchinterval()
    # a runner's construction was where the interpreter was retuned
    SchedulerRunner(DirectClient(ObjectStore())).scheduler.close()
    stager_puts = []
    real_put = jax.device_put

    def spy_put(x, *a, **kw):
        if threading.current_thread().name == "batch-stager":
            stager_puts.append(type(x).__name__)
        return real_put(x, *a, **kw)
    monkeypatch.setattr(jax, "device_put", spy_put)
    sched, cache, queue, log = _scheduler(mesh_shape=mesh_shape)
    if mesh_shape is not None and sched._mesh is None:
        pytest.skip("mesh unavailable on this backend")
    try:
        cache.add_pod(make_pod("foreign").req({"cpu": "300m"})
                      .node("n001").obj())
        assert _run_to_empty(sched, queue, _pods(8)) == 8
        assert sched.ctx_stats["folds"] >= 1, sched.ctx_stats
        assert sched.ctx_stats["patches"] == 0
        resolver = sched._resolver_thread
        assert resolver is not None and resolver.is_alive()
        assert resolver.name == "drain-resolver"
        assert sys.getswitchinterval() == interval
        st = cache.staging_stats()
        assert st["enabled"] is (mesh_shape is not None)
        if mesh_shape is not None:
            assert st["swaps"] >= 1 and st["fallbacks"] == 0, st
            assert stager_puts and set(stager_puts) == {"PodBatch"}
    finally:
        sched.close()
        sys.setswitchinterval(interval)


# ---- live invalidation matrix: redeemed == declined, bit-identical --------

def _matrix_scenario(sched, cache, queue, scenario):
    """One churny workload with a mid-run invalidation event; returns the
    placement map."""
    bound = _run_to_empty(sched, queue, _pods(24))
    if scenario == "mesh_reshape":
        sched.set_mesh(None)
    elif scenario == "catalog_epoch":
        # namespace-label churn bumps the encoder's pod epoch: cached row
        # packs invalidate, the staged copy of ALREADY-encoded stacks is
        # unaffected (it was cut after encode) — placements must not move
        cache.update_namespace({"metadata": {"name": "default",
                                             "labels": {"team": "a"}}})
    elif scenario == "row_width_growth":
        # wider pods promote the sticky bucket widths -> the next stack's
        # shapes exceed the ctx's compiled shapes -> rebuild + restage
        wide = [make_pod(f"w{i}").req({"cpu": "100m"})
                .toleration("k1", "v1").toleration("k2", "v2")
                .toleration("k3", "v3").obj() for i in range(4)]
        bound += _run_to_empty(sched, queue, wide)
    elif scenario == "ctx_taint":
        sched.taint_ctx()
    elif scenario == "churn_mid_stage":
        cache.add_node(
            make_node("late-node")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
            .label("kubernetes.io/hostname", "late-node").obj())
    bound += _run_to_empty(sched, queue, _pods(24, prefix="q"))
    return bound


@pytest.mark.multichip
@pytest.mark.parametrize("scenario", ["mesh_reshape", "catalog_epoch",
                                      "row_width_growth", "ctx_taint",
                                      "churn_mid_stage"])
def test_invalidation_matrix_parity_vs_legacy_staging(scenario):
    """Every invalidation event must fall back to the inline device_put
    path with bit-identical placements: the reference side keeps the mesh
    and has every ticket declined at submit (what a full buffer does), so
    each of its batches stages inline."""
    _mesh_or_skip()
    placements = {}
    for staging in (True, False):
        sched, cache, queue, log = _scheduler(mesh_shape=(1, 2), warm=False)
        if sched._mesh is None:
            pytest.skip("mesh unavailable on this backend")
        if not staging:
            cache.stage_submit = lambda pb_stack: None
        _warm(sched)
        bound = _matrix_scenario(sched, cache, queue, scenario)
        expected = 48 + (4 if scenario == "row_width_growth" else 0)
        assert bound == expected, f"{scenario} staging={staging}: {bound}"
        placements[staging] = dict(log)
        st = cache.staging_stats()
        # "enabled" says whether a mesh is installed NOW
        assert st["enabled"] is (scenario != "mesh_reshape")
        assert (st["submits"] >= 1) is staging, st
        sched.close()
    assert placements[True] == placements[False], scenario


@pytest.mark.multichip
def test_steady_state_swaps_track_dispatches():
    """A churn-free steady state serves (nearly) every dispatch from a
    buffer swap: fallbacks stay at zero once the context is warm."""
    _mesh_or_skip()
    sched, cache, queue, log = _scheduler(mesh_shape=(1, 2))
    if sched._mesh is None:
        pytest.skip("mesh unavailable on this backend")
    bound = _run_to_empty(sched, queue, _pods(32))
    bound += _run_to_empty(sched, queue, _pods(32, prefix="q"))
    assert bound == 64
    st = cache.staging_stats()
    assert st["swaps"] >= 2, st
    assert st["fallbacks"] == 0, st
    from kubernetes_tpu.metrics.registry import (STAGE_BUFFER_REUSE,
                                                 STAGE_BYTES)
    assert STAGE_BYTES.get({"path": "arena"}) > 0
    assert STAGE_BUFFER_REUSE.get() >= st["swaps"]
    sched.close()


# ---- resident-totals host shadow -----------------------------------------

def test_resident_shadow_unit():
    sh = ResidentShadow(np.full((4, 2), 100, np.int32),
                        np.zeros((4, 2), np.int32))
    pod = make_pod("x").req({"cpu": "1"}).obj()
    sh.fold_winners([(pod, 1), (pod, 1)])
    assert sh.arrays() is None  # pending winners: behind until catch_up
    sh.catch_up(lambda p: np.array([3, 1], np.int32))
    alloc, req = sh.arrays()
    assert req[1].tolist() == [6, 2]
    # patch mirror: reset row 2, rewrite row 0's allocatable, add a delta
    patch = {"node_row": np.array([0, 2, -1], np.int32),
             "n_alloc": np.array([[7, 7], [0, 0], [0, 0]], np.int32),
             "n_reset": np.array([False, True, False]),
             "req_delta": np.full((4, 2), 1, np.int32)}
    sh.req[2] = 50
    sh.apply_patch(patch)
    alloc, req = sh.arrays()
    assert alloc[0].tolist() == [7, 7]
    assert req[2].tolist() == [1, 1]      # reset then delta
    assert req[1].tolist() == [7, 3]
    # a failing catch_up poisons the shadow instead of lying
    sh.fold_winners([(pod, 0)])
    sh.catch_up(lambda p: (_ for _ in ()).throw(RuntimeError("boom")))
    assert sh.ok is False and sh.arrays() is None
    # order contract: a patch applied with winner folds still pending
    # poisons rather than mis-mirroring (on device the folds happened
    # BEFORE the patch — a reset row must zero them too)
    sh2 = ResidentShadow(np.full((4, 2), 100, np.int32),
                         np.zeros((4, 2), np.int32))
    sh2.fold_winners([(pod, 1)])
    sh2.apply_patch(patch)
    assert sh2.ok is False and sh2.arrays() is None


def test_shadow_matches_device_totals_through_churn():
    """After drains + churn patches + winner folds, the host shadow equals
    a device readback of the resident totals bit-for-bit (the wave's
    zero-round-trip source is exact, not approximate)."""
    import jax
    sched, cache, queue, log = _scheduler()
    bound = _run_to_empty(sched, queue, _pods(24))
    cache.add_node(
        make_node("late-node")
        .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
        .label("kubernetes.io/hostname", "late-node").obj())
    bound += _run_to_empty(sched, queue, _pods(16, prefix="late"))
    assert bound == 40
    assert not sched._pending
    ctx = sched._drain_ctx
    assert ctx is not None and not ctx["cs"].tainted
    shadow = ctx["shadow"]
    shadow.catch_up(
        lambda p: cache.request_vector(p, ctx["cs"].resources))
    got = shadow.arrays()
    assert got is not None and shadow.ok
    alloc_s, req_s = got
    alloc_d, req_d = jax.device_get((ctx["ct"].allocatable,
                                     ctx["ct"].requested))
    np.testing.assert_array_equal(alloc_s, np.asarray(alloc_d, np.int64))
    np.testing.assert_array_equal(req_s, np.asarray(req_d, np.int64))
    sched.close()


def test_wave_reads_shadow_not_device(monkeypatch):
    """A preemption wave riding the resident context serves cluster totals
    from the host shadow — and nominates identically to the snapshot
    path."""
    nodes = _nodes(4)
    outcomes = {}
    for use_shadow in (True, False):
        sched, cache, queue, log = _scheduler(nodes=nodes, batch_size=8)
        # saturate: 4 nodes x 8 cpu, low-prio pods eat all of it
        low = [make_pod(f"low{i}").req({"cpu": "4"}).priority(1).obj()
               for i in range(8)]
        assert _run_to_empty(sched, queue, low) == 8
        served = []
        if use_shadow:
            orig = ResidentShadow.arrays

            def spy(self):
                got = orig(self)
                if got is not None:
                    served.append(1)
                return got
            monkeypatch.setattr(ResidentShadow, "arrays", spy)
        else:
            sched._drain_ctx["shadow"] = None
        high = [make_pod(f"hi{i}").req({"cpu": "4"}).priority(100).obj()
                for i in range(2)]
        for p in high:
            queue.add(p)
        deadline = time.time() + 30
        while time.time() < deadline:
            sched.run_once(wait=0.01)
            sched._resolve_pending()
            if all(sched._nominated.get(p.key) or cache.is_bound(p.key)
                   for p in high):
                break
        noms = {p.metadata.name:
                (sched._nominated.get(p.key) or (None,))[0]
                for p in high}
        if use_shadow:
            assert served, "wave never read the shadow totals"
            monkeypatch.setattr(ResidentShadow, "arrays", orig)
        outcomes[use_shadow] = noms
        sched.close()
    assert outcomes[True] == outcomes[False]
