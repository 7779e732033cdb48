"""The slice carver — contiguous ICI sub-slice placement as ONE batched
contraction over the resident encoding.

The feasibility grid is DERIVED, not stored: node coordinates ride the
pre-interned ``kubernetes-tpu.io/topology-{x,y,z}`` label columns of
``ClusterTensors`` (encode/snapshot.py), so the scatter into the dense
[X,Y,Z] occupancy grid happens INSIDE the jitted program and node churn
keeps it current through the existing fused-fold patch path — no new
tensor field, no new dispatch on the churn side.

One ``carve_step`` dispatch evaluates, for a requested shape, EVERY
wrap-around torus origin x EVERY axis-order rotation at once:

  - per-node ``free`` (valid, on-grid, schedulable, tenant-visible,
    capacity fits one member, not claimed by an earlier gang this cycle)
    scatters to the free grid;
  - a separable box-sum (``sum_i roll(g, -i, axis)`` per axis — wrap-around
    is free on a torus) turns the grid into per-origin slice-fit counts;
    ``count == a*b*c`` IS the slice-fit score plane;
  - the SAME box-sum over the bound-occupancy grid (existing-pod counts,
    infinity where a cell can never host) is the
    "fewest-evictions-to-free-a-slice" plane — defrag-toward-contiguity
    and slice preemption read it without a second program.

Expressed as large XLA contractions on purpose: a hand Pallas kernel
measured 120x slower on the v5e than the fused XLA form of exactly this
kind of pass (ops/topology._domain_counts carries the figures), so there
is no Pallas here.

Host-side selection is deliberately tiny (argmax/argmin over the readback
grids) and shared, ORDER AND ALL, with the numpy twin ``numpy_grids`` —
the bit-parity contract the oracle carver (sched/oracle.py) and the
ParitySentinel carve site build on.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.encode.snapshot import (
    TENANT_KEY_ID,
    TOPO_X_KEY_ID,
    TOPO_Y_KEY_ID,
    TOPO_Z_KEY_ID,
    ClusterTensors,
)
from kubernetes_tpu.topology.slicing import box_cells, rotations


@dataclass
class CarveResult:
    """Readback of one carve dispatch (device or numpy twin — identical
    layout, identical selection semantics)."""

    fits: np.ndarray       # [R?,X,Y,Z] bool: origin hosts the whole slice
    cost: np.ndarray       # [R?,X,Y,Z] float32: evictions to free it (inf = never)
    node_grid: np.ndarray  # [X,Y,Z] int32 node index, -1 = no node at cell
    free_grid: np.ndarray  # [X,Y,Z] bool
    rots: tuple            # rotation r -> (a, b, c) extents
    dims: tuple            # grid extents (X, Y, Z)
    shape: tuple           # requested shape as labelled


def _box_sum(g, rot):
    """Separable wrap-around box sum: S[o] = sum over the rot-shaped box
    anchored at o. One roll per unit of extent; wrap-around is what
    ``jnp.roll``/``np.roll`` do natively, so the torus costs nothing."""
    roll = jnp.roll if isinstance(g, jax.Array) else np.roll
    for ax, d in enumerate(rot):
        acc = g
        for i in range(1, d):
            acc = acc + roll(g, -i, axis=ax)
        g = acc
    return g


@partial(jax.jit, static_argnames=("dims", "rots"))
def carve_step(ct: ClusterTensors, member_req, pod_tenant, claimed,
               dims: tuple, rots: tuple):
    """-> (fits [R,X,Y,Z] bool, cost [R,X,Y,Z] f32, node_grid [X,Y,Z] i32,
    free_grid [X,Y,Z] bool). Static args: grid extents + the (already
    dims-filtered) rotation tuple — both fixed per installed topology, so
    steady-state carves ride one warm program."""
    X, Y, Z = dims
    N = ct.node_valid.shape[0]
    K = ct.node_labels.shape[1]
    V = ct.label_value_num.shape[0]

    def coord(kid):
        # label-column coordinate: value-id -> numeric parse via the
        # existing label_value_num plane (churn patches already ship it)
        vid = ct.node_labels[:, kid]
        val = ct.label_value_num[jnp.clip(vid, 0, V - 1)]
        ok = (vid >= 0) & ~jnp.isnan(val) & (val >= 0)
        return jnp.where(ok, val, -1.0).astype(jnp.int32), ok

    if K > TOPO_Z_KEY_ID:
        x, okx = coord(TOPO_X_KEY_ID)
        y, oky = coord(TOPO_Y_KEY_ID)
        z, okz = coord(TOPO_Z_KEY_ID)
        on_grid = (okx & oky & okz & (x < X) & (y < Y) & (z < Z)
                   & ct.node_valid)
    else:
        # hand-built tensors predating the topology columns: no grid
        x = y = z = jnp.zeros(N, jnp.int32)
        on_grid = jnp.zeros(N, bool)
    if K > TENANT_KEY_ID:
        visible = ct.node_labels[:, TENANT_KEY_ID] == pod_tenant
    else:
        visible = jnp.ones(N, bool)

    free_cap = jnp.all(member_req[None, :] <= ct.allocatable - ct.requested,
                       axis=-1)
    alone_cap = jnp.all(member_req[None, :] <= ct.allocatable, axis=-1)
    usable = on_grid & visible & ~ct.unschedulable & ~claimed
    free = usable & free_cap
    evictable = usable & alone_cap

    # cell -> node: flat scatter, HIGHEST node index wins a duplicated
    # coordinate (deterministic; the numpy twin iterates ascending so its
    # last write is the same winner). Off-grid rows scatter out of range
    # and drop.
    flat = jnp.where(on_grid, (x * Y + y) * Z + z, X * Y * Z)
    idx = jnp.arange(N, dtype=jnp.int32)
    node_grid = (jnp.full((X * Y * Z,), -1, jnp.int32)
                 .at[flat].max(jnp.where(on_grid, idx, -1), mode="drop")
                 .reshape(X, Y, Z))
    in_t = node_grid >= 0
    gi = jnp.clip(node_grid, 0)
    free_grid = jnp.where(in_t, free[gi], False)

    # bound-occupancy plane: existing pods per node (epod slots are the
    # encoder's bound set; pending/pad slots are invalid and weigh 0)
    pods_on = jnp.zeros(N, jnp.float32).at[
        jnp.clip(ct.epod_node, 0, N - 1)].add(
        jnp.where(ct.epod_valid, 1.0, 0.0))
    cell_cost = jnp.where(
        jnp.where(in_t, evictable[gi], False),
        jnp.where(free_grid, 0.0, pods_on[gi]),
        jnp.inf)

    fits, costs = [], []
    for rot in rots:
        want = rot[0] * rot[1] * rot[2]
        fits.append(_box_sum(free_grid.astype(jnp.int32), rot) == want)
        costs.append(_box_sum(cell_cost, rot))
    return (jnp.stack(fits), jnp.stack(costs), node_grid,
            free_grid)


def carve_device(ct: ClusterTensors, member_req, pod_tenant: int, claimed,
                 dims: tuple, shape: tuple) -> Optional[CarveResult]:
    """Run one carve dispatch and read the score planes back. None when no
    rotation of ``shape`` fits ``dims`` at all (the shape can NEVER be
    carved on this torus — a static verdict, no device needed)."""
    rots = rotations(shape, dims)
    if not rots:
        return None
    # ktpu-lint: disable=KTL005 -- group-path carve: one batched readback of the tiny score planes per gang, same contract as gang_schedule's readback
    fits, cost, node_grid, free_grid = jax.device_get(carve_step(
        ct, jnp.asarray(member_req), jnp.int32(pod_tenant),
        jnp.asarray(claimed), dims=dims, rots=rots))
    return CarveResult(fits=np.asarray(fits), cost=np.asarray(cost),
                       node_grid=np.asarray(node_grid),
                       free_grid=np.asarray(free_grid),
                       rots=rots, dims=dims, shape=shape)


def numpy_grids(coords: list, free: list, evictable: list, n_pods: list,
                dims: tuple, shape: tuple) -> Optional[CarveResult]:
    """The carver's numpy twin over per-node host verdicts: ``coords[i]``
    is node i's (x, y, z) or None, ``free``/``evictable``/``n_pods`` its
    host-judged cell state. Same max-wins scatter, same roll-based box
    sums, same rotation order — bit-equal planes to ``carve_step`` by
    construction, asserted by the parity tests and the sentinel."""
    rots = rotations(shape, dims)
    if not rots:
        return None
    X, Y, Z = dims
    node_grid = np.full(dims, -1, np.int32)
    for i, c in enumerate(coords):
        if c is None or not all(0 <= v < d for v, d in zip(c, dims)):
            continue
        node_grid[c] = i  # ascending i: last write == max-wins
    in_t = node_grid >= 0
    gi = np.clip(node_grid, 0, None)
    free_grid = np.where(in_t, np.asarray(free, bool)[gi], False)
    evict_grid = np.where(in_t, np.asarray(evictable, bool)[gi], False)
    cell_cost = np.where(
        evict_grid,
        np.where(free_grid, 0.0, np.asarray(n_pods, np.float32)[gi]),
        np.inf).astype(np.float32)
    fits = np.stack([
        _box_sum(free_grid.astype(np.int32), rot) == rot[0] * rot[1] * rot[2]
        for rot in rots])
    cost = np.stack([_box_sum(cell_cost, rot) for rot in rots])
    return CarveResult(fits=fits, cost=cost, node_grid=node_grid,
                       free_grid=free_grid, rots=rots, dims=dims,
                       shape=shape)


# ---- host-side selection (shared by device and twin paths) ----------------

def select_assignment(res: Optional[CarveResult]
                      ) -> Optional[list[int]]:
    """First-fit origin in flat (rotation, x, y, z) order -> the member ->
    node-index assignment (C-order box cells, slicing.box_cells). None
    when no origin hosts the slice."""
    if res is None or res.fits.size == 0:
        return None
    flat = res.fits.reshape(-1)
    i = int(np.argmax(flat))  # argmax over bool = FIRST True
    if not flat[i]:
        return None
    r, ox, oy, oz = np.unravel_index(i, res.fits.shape)
    return [int(res.node_grid[c])
            for c in box_cells((int(ox), int(oy), int(oz)),
                               res.rots[r], res.dims)]


def select_eviction(res: Optional[CarveResult]
                    ) -> Optional[tuple[list[int], list[tuple], float]]:
    """Cheapest contiguous victim set: the finite-minimum origin of the
    eviction plane (first minimum in flat order) -> (node indices of the
    slice's cells, the cells themselves, total eviction cost). None when
    no origin can EVER host the slice (an unusable cell in every box)."""
    if res is None or res.cost.size == 0:
        return None
    flat = res.cost.reshape(-1)
    i = int(np.argmin(flat))  # first minimum in flat order
    if not np.isfinite(flat[i]):
        return None
    r, ox, oy, oz = np.unravel_index(i, res.cost.shape)
    cells = box_cells((int(ox), int(oy), int(oz)), res.rots[r], res.dims)
    nodes = [int(res.node_grid[c]) for c in cells]
    return nodes, cells, float(flat[i])


def _covered_grid(res: CarveResult) -> np.ndarray:
    """[X,Y,Z] bool: cell belongs to SOME carveable placement of the shape
    (any rotation, any fitting origin)."""
    covered = np.zeros(res.dims, bool)
    for r, rot in enumerate(res.rots):
        f = res.fits[r]
        for cell in box_cells((0, 0, 0), rot, res.dims):
            covered |= np.roll(f, cell, axis=(0, 1, 2))
    return covered


def covered_nodes(res: Optional[CarveResult], n_nodes: int) -> list[bool]:
    """Per-node verdict "this node sits inside some carveable placement" —
    the oracle explainer's SliceCarve filter plane (a node outside every
    placement can never host a member of the requested slice as things
    stand)."""
    out = [False] * n_nodes
    if res is None:
        return out
    covered = _covered_grid(res)
    for cell in np.argwhere(covered):
        ni = int(res.node_grid[tuple(cell)])
        if 0 <= ni < n_nodes:
            out[ni] = True
    return out


def coverage_stats(res: Optional[CarveResult]) -> dict:
    """Status-surface numbers for one shape: carveable origin count and
    fragmentation % — the share of free cells that sit in NO carveable
    placement of the shape (100% = plenty of free nodes, none of them
    composable into a slice; 0% = every free cell is part of some fit)."""
    if res is None:
        return {"origins": 0, "fragmentationPct": None}
    covered = _covered_grid(res)
    n_free = int(res.free_grid.sum())
    frag = (100.0 * (1.0 - int((covered & res.free_grid).sum()) / n_free)
            if n_free else 0.0)
    return {"origins": int(res.fits.sum()),
            "fragmentationPct": round(float(frag), 1)}
