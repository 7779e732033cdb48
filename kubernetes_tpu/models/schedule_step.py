"""The flagship jitted program: one scheduling step for a batch of P pods.

This is the inversion of the reference's hot path (SURVEY §3.1): where
``schedule_one.go`` runs pop -> PreFilter -> 16-goroutine Filter loop ->
Score loop -> NormalizeScore -> selectHost *per pod*, here the whole
Filter/Score/Normalize/Select pipeline is a single XLA program over the
[P, N] batch:

    feasible[P,N] = AND of plugin masks        (ops/filters.py, ops/topology.py)
    scores[P,N]   = sum_w w * normalize(raw)   (ops/scores.py)
    choice[P]     = argmax + seeded tie-break

Gang conflict resolution (capacity, anti-affinity among batch members) lives
in models/gang.py and calls back into this step between rounds.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from flax import struct

from kubernetes_tpu.encode.snapshot import ClusterTensors, PodBatch
from kubernetes_tpu.ops import topology
from kubernetes_tpu.ops.filters import run_filters
from kubernetes_tpu.ops.scores import combined_score, select_host


class StepResult(struct.PyTreeNode):
    choice: jnp.ndarray     # [P] int32 node index (valid only where assigned)
    assigned: jnp.ndarray   # [P] bool
    feasible: jnp.ndarray   # [P,N] bool
    scores: jnp.ndarray     # [P,N] float32 (-inf infeasible)
    # [P,S] float32: at the chosen node, how many more matching pods each
    # spread constraint's domain takes before maxSkew refuses this pod
    # (topology.spread_mask_and_room); None when the batch has no spread
    # constraint or the profile runs without the PodTopologySpread filter
    spread_room: jnp.ndarray | None = None


def evaluate(ct: ClusterTensors, pb: PodBatch, seed: int = 0,
             weights=None, fit_strategy: str = "LeastAllocated",
             topo_keys: tuple[int, ...] = (),
             enabled_filters=None, ext_mask=None,
             ext_scores=None, plugins: tuple = ()) -> StepResult:
    """Filter + score + select for the whole batch, assuming an EMPTY batch
    context (no intra-batch interactions — gang.py supplies those).

    ``topo_keys``: static tuple of distinct topology key-ids in play
    (meta.topo_keys) — unrolls into a handful of per-key domain aggregations.
    ``weights`` / ``enabled_filters``: the active profile's plugin config
    (None = reference defaults / all filters). ``ext_mask``/``ext_scores``
    [P,N]: host-computed scheduler-extender feasibility veto and weighted
    score overlay (sched/extender.py) — the findNodesThatPassExtenders
    position in the cycle. ``plugins``: static tuple of out-of-tree
    TensorPlugins (sched/framework.py) traced INTO this program — their
    filters AND into feasibility, their scores merge through the shared
    normalize pipeline."""
    def _on(name):
        return enabled_filters is None or name in enabled_filters

    feasible = run_filters(ct, pb, enabled=enabled_filters)
    room = None
    if _on("PodTopologySpread"):
        mask, room = topology.spread_mask_and_room(ct, pb, topo_keys)
        feasible &= mask
    if _on("InterPodAffinity"):
        feasible &= topology.interpod_required_mask(ct, pb, topo_keys)
        feasible &= topology.interpod_symmetry_mask(ct, pb, topo_keys)
    if ext_mask is not None:
        feasible &= ext_mask
    for plugin in plugins:
        if plugin.filter_fn is not None:
            feasible &= plugin.filter_fn(ct, pb, topo_keys)
    extra = {}
    score_plugins = [p for p in plugins if p.score_fn is not None]
    if score_plugins:
        # weight applies AFTER normalization, exactly like in-tree plugins
        # (normalize rescales raw magnitudes away). Plugin defaults sit
        # UNDER the profile map so a profile's scoreWeights override —
        # including disable(0) — wins over the plugin's own weight.
        weights = {**{p.name: p.weight for p in score_plugins},
                   **(weights or {})}
    for plugin in score_plugins:
        extra[plugin.name] = (plugin.score_fn(ct, pb, topo_keys),
                              plugin.normalize, None)
    if pb.sc_valid.shape[1] > 0:
        extra["PodTopologySpread"] = (
            topology.spread_score_raw(ct, pb, topo_keys), "default_reverse",
            jnp.any(pb.sc_valid & ~pb.sc_hard, axis=1))
    if pb.paff_valid.shape[1] > 0:
        extra["InterPodAffinity"] = (
            topology.interpod_score_raw(ct, pb, topo_keys), "minmax",
            jnp.any(pb.paff_valid, axis=1))
    scores = combined_score(ct, pb, feasible, weights=weights, extra_raw=extra,
                            fit_strategy=fit_strategy)
    if ext_scores is not None:
        scores = jnp.where(feasible, scores + ext_scores, scores)
    # tenant-local tie-break identity: arange(N) for single-tenant
    # clusters (bit-identical to the historical index tie-break), the
    # per-tenant rank under a fleet (ops/filters.tenant_local_rank)
    from kubernetes_tpu.ops.filters import tenant_local_rank
    choice, has = select_host(scores, seed=seed,
                              node_rank=tenant_local_rank(ct))
    choice = choice.astype(jnp.int32)
    if room is not None:
        room = jnp.take_along_axis(room, choice[:, None, None], axis=2)[..., 0]
    return StepResult(choice=choice,
                      assigned=has & jnp.any(feasible, axis=-1),
                      feasible=feasible, scores=scores, spread_room=room)


@partial(jax.jit, static_argnames=("seed", "fit_strategy", "topo_keys"))
def schedule_step(ct: ClusterTensors, pb: PodBatch, seed: int = 0,
                  fit_strategy: str = "LeastAllocated",
                  topo_keys: tuple[int, ...] = ()) -> StepResult:
    """Jitted single-shot evaluate (default weights)."""
    return evaluate(ct, pb, seed=seed, fit_strategy=fit_strategy,
                    topo_keys=topo_keys)
