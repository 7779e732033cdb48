"""Device mesh + sharding specs for the scheduling tensors.

The reference scales the filter/score loop with 16 goroutines chunked over
nodes (framework/parallelize/parallelism.go). The TPU analog is a 2-D
``Mesh("pods", "nodes")``:

  node-major cluster tensors  -> sharded over the "nodes" axis (TP-like)
  pod-major batch tensors     -> sharded over the "pods" axis (DP-like)
  [P,N] intermediates         -> sharded over both

All cross-node reductions (NormalizeScore max, selectHost argmin, spread
domain min) lower to XLA collectives over ICI (psum/pmax style) via GSPMD —
no hand-written comms. Existing-pods tensors and intern side-tables are
replicated: they are contracted against the node axis inside the one-hot
matmuls, and GSPMD partitions those contractions.

Multi-host: the same Mesh spans hosts (jax.distributed.initialize); the
"nodes" axis should map to the ICI-dominant mesh dimension so domain matmuls
avoid DCN.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubernetes_tpu.encode.snapshot import ClusterTensors, PodBatch


def make_mesh(devices=None, pods_axis: int = 1) -> Mesh:
    """Build a ("pods", "nodes") mesh. With k devices, pods_axis x (k/pods_axis)."""
    devices = devices if devices is not None else jax.devices()
    k = len(devices)
    while k % pods_axis:
        pods_axis -= 1
    arr = np.asarray(devices).reshape(pods_axis, k // pods_axis)
    return Mesh(arr, ("pods", "nodes"))


def parse_mesh_shape(value) -> "tuple[int, int] | None":
    """Mesh-shape wire forms -> (pods_axis, nodes_axis) | None.

    Accepted: None/""/"off" (disabled), "PxN" / "P,N" strings (KTPU_MESH
    env), a bare int/"N" (1 x N: node-axis only, the common single-host
    case), or a 2-sequence (YAML ``meshShape: [1, 2]``)."""
    if value is None:
        return None
    if isinstance(value, str):
        s = value.strip().lower()
        if s in ("", "0", "off", "none"):
            return None
        for sep in ("x", ","):
            if sep in s:
                p, n = s.split(sep, 1)
                return (int(p), int(n))
        return (1, int(s))
    if isinstance(value, int):
        return None if value <= 1 else (1, value)
    if len(value) != 2:
        raise ValueError(f"mesh shape must be (pods, nodes), got {value!r}")
    p, n = value
    return (int(p), int(n))


def mesh_from_shape(shape: tuple[int, int], devices=None) -> Mesh:
    """An EXACT (pods, nodes) mesh from the first pods*nodes devices —
    the live scheduler's configured shape, unlike make_mesh's best-fit.
    Raises ValueError when the backend has too few devices (callers decide
    whether that degrades to single-device or aborts)."""
    pods_axis, nodes_axis = int(shape[0]), int(shape[1])
    want = pods_axis * nodes_axis
    devices = devices if devices is not None else jax.devices()
    if len(devices) < want:
        raise ValueError(
            f"mesh shape {pods_axis}x{nodes_axis} needs {want} devices, "
            f"backend has {len(devices)}")
    arr = np.asarray(devices[:want]).reshape(pods_axis, nodes_axis)
    return Mesh(arr, ("pods", "nodes"))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding on the mesh — the drain's compact winners
    view (assignment rows + fill scalar) is constrained to this so the
    resolver thread's device_get pulls O(P) bytes from one shard instead of
    gathering whole sharded intermediates."""
    return NamedSharding(mesh, P())


def _split_or_replicate(mesh: Mesh, leaf, axis_index: int,
                        axis_name: str) -> NamedSharding:
    """Split ``leaf`` on ``axis_name`` at ``axis_index`` — or REPLICATE when
    the dim isn't divisible by the mesh axis. Live encodes bucket to powers
    of two, but a bucket can shrink below the axis size (a scaled-down
    cluster's N=4 under a 1x8 mesh): device_put with a non-divisible split
    raises, and an uncaught raise here kills the scheduling loop thread.
    Replication is always semantics-preserving — the mesh stays a
    throughput knob, never a crash."""
    size = mesh.shape[axis_name]
    if axis_index < leaf.ndim and leaf.shape[axis_index] % size == 0:
        spec = [None] * leaf.ndim
        spec[axis_index] = axis_name
        return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def cluster_shardings(mesh: Mesh, ct: ClusterTensors) -> ClusterTensors:
    """Sharding pytree for ClusterTensors: node-leading arrays split on "nodes"."""
    node_dim = {"allocatable", "requested", "node_valid", "unschedulable",
                "node_labels", "taint_key", "taint_val", "taint_effect",
                "taint_valid", "port_proto", "port_port", "port_ip",
                "port_valid", "node_images"}

    def spec(path, leaf):
        name = path[-1].name if hasattr(path[-1], "name") else str(path[-1])
        if name in node_dim:
            return _split_or_replicate(mesh, leaf, 0, "nodes")
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec, ct)


def batch_shardings(mesh: Mesh, pb: PodBatch) -> PodBatch:
    """Sharding pytree for PodBatch: every pod-leading array splits on "pods"."""
    def spec(leaf):
        return _split_or_replicate(mesh, leaf, 0, "pods")
    return jax.tree_util.tree_map(spec, pb)


def constrain_cluster(mesh: Mesh, ct: ClusterTensors) -> ClusterTensors:
    """``with_sharding_constraint`` pinning a (traced) ClusterTensors to the
    canonical cluster shardings — used INSIDE jitted programs (drain_step,
    apply_ctx_patch) so their OUTPUT shardings are exactly the next
    dispatch's input shardings: donation then aliases every buffer in
    place, and a layout drift can never silently re-copy the multi-MB
    resident encoding between steady-state drains (SNIPPETS [1]/[3]: one
    dispatch's out_axis_resources must match the next's
    in_axis_resources)."""
    return jax.lax.with_sharding_constraint(ct, cluster_shardings(mesh, ct))


def shard_cluster(mesh: Mesh, ct: ClusterTensors) -> ClusterTensors:
    return jax.device_put(ct, cluster_shardings(mesh, ct))


def shard_batch(mesh: Mesh, pb: PodBatch) -> PodBatch:
    return jax.device_put(pb, batch_shardings(mesh, pb))


def stack_shardings(mesh: Mesh, pb_stack: PodBatch) -> PodBatch:
    """Sharding pytree for a STACKED drain batch [B,P,...]: the pod axis
    (axis 1) splits over "pods"; the scan axis B stays replicated (the
    drain scans batches sequentially — capacity carries batch to batch)."""
    def spec(leaf):
        return _split_or_replicate(mesh, leaf, 1, "pods")
    return jax.tree_util.tree_map(spec, pb_stack)


def shard_drain(mesh: Mesh, ct_all: ClusterTensors, pb_stack: PodBatch):
    """Stage a fused-drain problem onto the mesh: cluster tensors split on
    "nodes" (the SURVEY §2.6 core replacement for parallelize.Until's
    node-axis goroutine fan-out), stacked batches split on "pods",
    epod/relational side-tables replicated — drain_step then runs with
    GSPMD collectives over ICI for every cross-node reduction
    (normalize max, selectHost argmax, domain-count matmuls, fold
    scatters)."""
    ct_s = jax.device_put(ct_all, cluster_shardings(mesh, ct_all))
    pb_s = jax.device_put(pb_stack, stack_shardings(mesh, pb_stack))
    return ct_s, pb_s
