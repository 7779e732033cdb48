"""Live multi-chip scheduling: the CONNECTED drain/dispatch/preemption path
under a device mesh (sched/scheduler.py + parallel/mesh.py).

test_mesh.py proves the device programs are sharding-parity-safe in
isolation; this file proves the LIVE scheduler — cache, queue, resident
drain context, churn patches, resolve — produces identical placements with
``meshShape`` on, and that the mesh plumbing (epoch-checked rebuilds,
donation, row-pack encode) holds on any backend.

Mesh-executing tests carry the ``multichip`` marker and gate on the same
GSPMD canary as test_mesh.py: a jaxlib that miscompiles sharded programs on
the virtual-CPU platform skips them deterministically instead of failing
tier-1. Everything else here runs single-device and stays in tier-1.
"""

import io
import warnings

import numpy as np
import pytest

from kubernetes_tpu.config.types import (SchedulerConfiguration,
                                         ValidationError, validate)
from kubernetes_tpu.parallel.mesh import parse_mesh_shape
from kubernetes_tpu.sched.cache import SchedulerCache
from kubernetes_tpu.sched.queue import SchedulingQueue
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _nodes(n=64):
    return [make_node(f"n{i:03d}")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
            .label("topology.kubernetes.io/zone", f"z{i // 3}")
            .label("kubernetes.io/hostname", f"n{i:03d}")
            .obj() for i in range(n)]


def _pods(n=48, prefix="p"):
    out = []
    for i in range(n):
        b = (make_pod(f"{prefix}{i:03d}")
             .req({"cpu": "500m", "memory": "256Mi"})
             .label("app", f"g{i % 3}"))
        if i % 5 == 0:
            b = b.pod_anti_affinity("kubernetes.io/hostname", {"app": "g0"})
        out.append(b.obj())
    return out


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
        warm_pods = [make_pod(f"__warm{i}").req({"cpu": "100m"}).obj()
                     for i in range(batch_size)]
        assert sched.warm_drain(warm_pods, slot_headroom=256)
    return sched, cache, queue, log


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


# ---- config surface ------------------------------------------------------

def test_parse_mesh_shape_forms():
    assert parse_mesh_shape(None) is None
    assert parse_mesh_shape("") is None
    assert parse_mesh_shape("off") is None
    assert parse_mesh_shape("1x2") == (1, 2)
    assert parse_mesh_shape("2,4") == (2, 4)
    assert parse_mesh_shape("4") == (1, 4)
    assert parse_mesh_shape([2, 2]) == (2, 2)
    assert parse_mesh_shape(1) is None


def test_mesh_shape_yaml_and_validation():
    cfg = SchedulerConfiguration.from_dict({"meshShape": [1, 2]})
    assert cfg.mesh_shape == (1, 2)
    validate(cfg)
    cfg = SchedulerConfiguration.from_dict({"meshShape": "2x4"})
    assert cfg.mesh_shape == (2, 4)
    with pytest.raises(ValidationError):
        validate(SchedulerConfiguration(mesh_shape=(3, 2)))  # not a pow2
    with pytest.raises(ValidationError):
        # pods axis must divide the batch bucket
        validate(SchedulerConfiguration(batch_size=6, mesh_shape=(4, 1)))


def test_unavailable_mesh_degrades_to_single_device():
    """A meshShape wider than the backend must degrade to single-device
    scheduling (the mesh is a throughput knob), not refuse to construct."""
    sched, _cache, queue, log = _scheduler(mesh_shape=(1, 1024), warm=False)
    assert sched._mesh is None
    bound = _run_to_empty(sched, queue, _pods(8))
    assert bound == 8
    sched.close()


# ---- mesh-epoch discipline (single-device: epoch logic only) -------------

def test_mesh_reshape_forces_ctx_rebuild():
    """A mesh reshape between drains must rebuild the resident context —
    patching arrays staged under the old layout would be silently wrong.
    set_mesh(None) still bumps the epoch, so this runs on any backend."""
    sched, cache, queue, log = _scheduler()
    bound = _run_to_empty(sched, queue, _pods(24))
    assert bound == 24
    assert sched._drain_ctx is not None
    rebuilds0 = sched.ctx_stats["rebuilds"]
    sched.set_mesh(None)  # reshape: epoch moves, layout semantics change
    bound += _run_to_empty(sched, queue, _pods(24, prefix="q"))
    assert bound == 48
    assert sched.ctx_stats["reasons"].get("mesh_reshape", 0) >= 1
    assert sched.ctx_stats["rebuilds"] > rebuilds0
    assert sched._drain_ctx is not None
    assert sched._drain_ctx["mesh_epoch"] == sched._mesh_epoch
    sched.close()


# ---- donation audit (satellite): steady-state drain/patch aliasing -------

def test_drain_patch_steady_state_no_copy_on_donate_warnings():
    """The resident ctx is donated through drain_step, in both its plain
    and fused-fold variants; steady-state cycles must alias buffers in
    place. A 'donated buffers were not usable' warning means a layout
    mismatch re-copies the multi-MB encoding every drain — the exact
    regression the warmup double-execute exists to prevent. Runs plain
    drains and the THREE-input drain (churn patch fused into the
    dispatch) back to back, twice."""
    sched, cache, queue, log = _scheduler()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        bound = _run_to_empty(sched, queue, _pods(24))
        # churn -> fold-into-dispatch -> drain again (three-input drain)
        cache.add_node(
            make_node("late-node")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
            .label("kubernetes.io/hostname", "late-node").obj())
        bound += _run_to_empty(sched, queue, _pods(16, prefix="late"))
        # and again: a fold over the layouts a fold left behind
        cache.add_node(
            make_node("late-node-2")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
            .label("kubernetes.io/hostname", "late-node-2").obj())
        bound += _run_to_empty(sched, queue, _pods(16, prefix="later"))
    assert bound == 56
    assert sched.ctx_stats["folds"] >= 2, \
        "churn did not take the fused-fold path"
    assert sched.ctx_stats["patches"] == 0
    donate_warnings = [str(w.message) for w in caught
                       if "donated" in str(w.message).lower()]
    assert not donate_warnings, donate_warnings
    sched.close()


# ---- EK width regression (found arming the mesh path) --------------------

def test_ctx_patch_after_batch_widened_label_bucket():
    """Regression: a context armed before any labeled pod was seen (K=4
    bucket), then batches whose label keys crossed the bucket (extend_cluster
    widens the RESIDENT epod arrays to K=8), then a node-add churn patch.
    The patch must compile at the resident widths (CtxPatchState.EK) — it
    used to compile at the encoder's K and fail to broadcast at apply."""
    sched, cache, queue, log = _scheduler()  # warm pods carry no labels
    bound = _run_to_empty(sched, queue, _pods(24))  # labels cross the bucket
    assert bound == 24
    cache.add_node(
        make_node("late-node")
        .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
        .label("kubernetes.io/hostname", "late-node").obj())
    bound += _run_to_empty(sched, queue, _pods(16, prefix="late"))
    assert bound == 40
    assert sched.ctx_stats["patches"] + sched.ctx_stats["folds"] >= 1
    sched.close()


# ---- encode row packs (satellite): fill-only cycles ----------------------

def test_fill_only_cycles_do_no_per_pod_fill_work():
    """Once a pod's row pack exists (informer-time precompile or a prior
    encode), encode_pods must assemble it with bulk stacks only — the
    pod_rows_filled counter is the proof the bench reports."""
    from kubernetes_tpu.encode.snapshot import SnapshotEncoder
    import jax
    nodes, pods = _nodes(16), _pods(12)
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, [], pending_pods=pods)
    pb1 = enc.encode_pods(pods, meta)
    assert enc.pod_rows_filled == 12 and enc.pod_rows_stacked == 0
    pb2 = enc.encode_pods(pods, meta)  # fill-only cycle: pure stack
    assert enc.pod_rows_filled == 12 and enc.pod_rows_stacked == 12
    for a, b in zip(jax.tree_util.tree_leaves(pb1),
                    jax.tree_util.tree_leaves(pb2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # informer-time precompile: fresh pods arrive with rows prebuilt
    fresh = _pods(8, prefix="q")
    for p in fresh:
        enc.precompile_pod(p)
    filled0 = enc.pod_rows_filled
    enc.encode_pods(fresh, meta)
    assert enc.pod_rows_filled == filled0, \
        "precompiled pods paid per-pod fill work on the hot path"


def test_row_pack_invalidation_on_epoch_and_identity():
    """A catalog change (epoch bump) or a new watch object must invalidate
    the cached rows — stale packs would encode dead state."""
    from kubernetes_tpu.encode.snapshot import SnapshotEncoder
    nodes, pods = _nodes(8), _pods(4)
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, [], pending_pods=pods)
    enc.encode_pods(pods, meta)
    filled0 = enc.pod_rows_filled
    enc._pod_epoch += 1  # what set_volumes/set_namespaces/set_dra do
    enc.encode_pods(pods, meta)
    assert enc.pod_rows_filled == filled0 + len(pods)
    # fresh objects with the same keys (a new watch event) re-fill too
    pods2 = _pods(4)
    filled1 = enc.pod_rows_filled
    enc.encode_pods(pods2, meta)
    assert enc.pod_rows_filled == filled1 + len(pods2)


def test_sticky_widths_promote_monotonically():
    """A wide pod promotes the batch buckets; later narrow batches keep the
    promoted widths so their packs stay valid (stable compiled shapes)."""
    from kubernetes_tpu.encode.snapshot import SnapshotEncoder
    nodes = _nodes(8)
    plain = [make_pod(f"a{i}").req({"cpu": "100m"}).obj() for i in range(4)]
    wide = [make_pod(f"w{i}").req({"cpu": "100m"})
            .toleration("k1", "v1").toleration("k2", "v2")
            .toleration("k3", "v3").obj() for i in range(2)]
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, [], pending_pods=plain + wide)
    pb_plain = enc.encode_pods(plain, meta)
    assert pb_plain.tol_valid.shape[1] == 0
    pb_wide = enc.encode_pods(wide, meta)
    TOL = pb_wide.tol_valid.shape[1]
    assert TOL >= 3
    pb_plain2 = enc.encode_pods(plain, meta)
    assert pb_plain2.tol_valid.shape[1] == TOL  # sticky: no shrink


# ---- status surface ------------------------------------------------------

def test_publish_status_and_ktpu_status():
    from kubernetes_tpu.cli.ktpu import main as ktpu_main
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.apiserver import APIServer
    server = APIServer().start()
    try:
        out = io.StringIO()
        rc = ktpu_main(["--server", server.url, "status"], out=out)
        assert rc == 1 and "no scheduler status" in out.getvalue()
        runner = SchedulerRunner(HTTPClient(server.url))
        runner.publish_status()
        out = io.StringIO()
        rc = ktpu_main(["--server", server.url, "status"], out=out)
        assert rc == 0
        text = out.getvalue()
        assert "Mesh:" in text and "single-device" in text
        assert "default-scheduler" in text
        # resident-ctx fusion health is part of the status surface
        assert "Resident ctx:" in text and "folds 0, patches 0" in text
        assert "in flight" in text
        # zero-copy staging health (sched/staging.py arena): the mesh
        # chooses it, and this runner has none
        assert "Staging:" in text and "arena off" in text
        # no aotCacheDir configured -> the cache reports itself off
        assert "Compile cache: off" in text
        out = io.StringIO()
        rc = ktpu_main(["--server", server.url, "status", "-o", "json"],
                       out=out)
        assert rc == 0
        import json
        st = json.loads(out.getvalue())
        assert st["mesh"] is None and st["batchSize"] == 256
        assert st["ctx"]["patches"] == 0 and st["ctx"]["folds"] == 0
        assert st["pipelineInflight"] == 0
        assert st["staging"]["enabled"] is False
        assert st["staging"]["fallbacks"] == 0
        assert st["aotCache"] == {"enabled": False}
        runner.scheduler.close()
    finally:
        server.stop()


def test_ktpu_status_compile_cache_line(tmp_path):
    """With an aotCacheDir configured the status surface reports the
    durable compile cache: entry/byte counts and this boot's load, in
    both the text line and the -o json block."""
    from kubernetes_tpu.cli.ktpu import main as ktpu_main
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.sched.aotcache import AotExecutableCache
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.apiserver import APIServer
    server = APIServer().start()
    try:
        cfg = SchedulerConfiguration.from_dict(
            {"aotCacheDir": str(tmp_path / "aot")})
        runner = SchedulerRunner(HTTPClient(server.url), cfg)
        assert runner.aot_cache is not None
        runner.publish_status()
        out = io.StringIO()
        assert ktpu_main(["--server", server.url, "status"], out=out) == 0
        text = out.getvalue()
        assert "Compile cache: 0 entries" in text
        assert "boot loaded 0" in text
        out = io.StringIO()
        assert ktpu_main(["--server", server.url, "status", "-o", "json"],
                         out=out) == 0
        import json
        ac = json.loads(out.getvalue())["aotCache"]
        assert ac["enabled"] is True
        assert ac["entries"] == 0 and ac["bootEntries"] == 0
        assert ac["bootLoadMs"] is not None
        assert ac["errors"] == 0 and ac["invalidations"] == 0
        runner.scheduler.close()
    finally:
        AotExecutableCache.disarm()
        server.stop()


# ---- live parity under a real mesh (canary-gated, multichip tier) --------

def _mesh_backend_or_skip():
    # canary at THIS suite's mesh shape: GSPMD miscompiles are
    # shape-specific, so the 2x4 verdict must not over-skip the 1x2 path
    import test_mesh
    usable, why = test_mesh._sharded_backend_verdict((1, 2))
    if not usable:
        pytest.skip(why)


@pytest.mark.multichip
def test_live_path_parity_mesh_vs_single_device():
    """The SAME workload through the live scheduler with meshShape=(1,2)
    and single-device must bind every pod to identical nodes — the whole
    connected path (resident ctx staging, sharded dispatch, churn patch,
    replicated winners resolve), not just the isolated device programs."""
    _mesh_backend_or_skip()
    placements = {}
    for shape in (None, (1, 2)):
        sched, cache, queue, log = _scheduler(mesh_shape=shape)
        if shape is not None and sched._mesh is None:
            pytest.skip("mesh unavailable on this backend")
        bound = _run_to_empty(sched, queue, _pods(48))
        # churn against the resident (sharded) context mid-run
        cache.add_node(
            make_node("late-node")
            .capacity({"cpu": "8", "memory": "16Gi", "pods": "20"})
            .label("kubernetes.io/hostname", "late-node").obj())
        bound += _run_to_empty(sched, queue, _pods(16, prefix="late"))
        assert bound == 64, f"shape {shape} lost pods: {bound}"
        placements[shape] = dict(log)
        sched.close()
    assert placements[None] == placements[(1, 2)]


@pytest.mark.multichip
def test_preempt_masks_sharded_parity():
    """tensor_static_masks under the mesh == unsharded (the preempt/wave
    setup program the live failure path runs)."""
    _mesh_backend_or_skip()
    import jax
    from kubernetes_tpu.parallel.mesh import make_mesh
    from kubernetes_tpu.sched.preemption import tensor_static_masks
    nodes = _nodes(32)
    preemptors = [make_pod(f"hi{i}").req({"cpu": "6"}).priority(100).obj()
                  for i in range(8)]
    base = tensor_static_masks(nodes, preemptors, bound_pods=[])
    mesh = make_mesh(jax.devices()[:2], pods_axis=1)
    sharded = tensor_static_masks(nodes, preemptors, bound_pods=[],
                                  mesh=mesh)
    np.testing.assert_array_equal(base, sharded)
