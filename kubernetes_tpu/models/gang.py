"""Gang batcher — schedule P pods per device step with conflict resolution.

The reference schedules one pod at a time (``schedule_one.go`` ScheduleOne);
batching P pods against one snapshot introduces intra-batch conflicts the
serial loop never sees:

  capacity     two batch members both fit node n, but not together
  relational   anti-affinity/spread/affinity between batch members

Design: iterative propose/commit rounds, all tensor-side:

  1. evaluate() all uncommitted pods against cluster state + already-committed
     batch members (committed members occupy pre-padded "extension" slots of
     the existing-pods tensors).
  2. every pod proposes its argmax node.
  3. capacity acceptance per node: proposals sorted by (node, rank) with
     rank = (-priority, batch index); segmented exclusive prefix-sums of
     requests accept the prefix that fits (sort + cumsum, no scatter loops).
  4. relational veto: an accepted pod is rejected if a higher-rank pod
     accepted THIS round conflicts (anti-affinity either direction, or
     required-affinity forcing co-location), or if the higher-rank pods of
     this round leave its hard-spread constraint no room. The veto is
     conservative — rejected pods simply re-propose next round against the
     updated state, so committed state is always sequentially valid. The
     hard-spread arm is a quota, not a pairwise conflict: of the pods a
     DoNotSchedule selector matches, a round commits into a domain as many
     as ``maxSkew`` leaves room for against the minimum at the round's
     opening (maxSkew 5 over balanced domains: five a domain a round;
     maxSkew 1: one). Commits only raise counts, so that minimum is never
     above the true one when a commit is replayed in rank order.
  5. fold acceptances into requested[N,R] + extension slots; repeat.

``serial=True`` caps acceptance at one pod per round (highest rank), which
reproduces the reference's serial semantics exactly — the parity tests diff it
against the oracle's ScheduleOne loop bit-for-bit.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from kubernetes_tpu.encode.snapshot import ClusterTensors, PodBatch, SelectorSet
from kubernetes_tpu.models.schedule_step import evaluate


class GangState(struct.PyTreeNode):
    requested: jnp.ndarray    # [N,R] current (base + committed batch members)
    committed: jnp.ndarray    # [P] bool
    assignment: jnp.ndarray   # [P] int32, -1 unassigned
    tried: jnp.ndarray        # [P] bool (serial mode: attempted exactly once)
    rounds: jnp.ndarray       # scalar int32


def _pad_axis(a: np.ndarray, axis: int, size: int, fill):
    if a.shape[axis] == size:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, size - a.shape[axis])
    return np.pad(a, pads, constant_values=fill)


def extend_cluster(ct: ClusterTensors, pb: PodBatch) -> ClusterTensors:
    """Host-side: widen the existing-pods tensors with P extension slots for
    batch members (invalid until committed) so relational plugins see committed
    members. Anti-affinity term buckets are unified by padding."""
    E = int(ct.epod_valid.shape[0])
    P = int(pb.pod_valid.shape[0])
    K = max(int(ct.epod_labels.shape[1]), int(pb.pod_labels.shape[1]))

    epod_labels = np.concatenate([
        _pad_axis(np.asarray(ct.epod_labels), 1, K, -1),
        _pad_axis(np.asarray(pb.pod_labels), 1, K, -1)], axis=0)
    # unify anti-affinity term buckets: [E,ET,...] with [P,BT,...]
    ET = max(int(ct.ea_valid.shape[1]), int(pb.anti_valid.shape[1]))
    AX = max(int(ct.ea_sel.key.shape[2]) if ct.ea_sel.key.ndim == 3 else 0,
             int(pb.anti_sel.key.shape[2]) if pb.anti_sel.key.ndim == 3 else 0)
    AV = max(int(ct.ea_sel.vals.shape[3]) if ct.ea_sel.vals.ndim == 4 else 0,
             int(pb.anti_sel.vals.shape[3]) if pb.anti_sel.vals.ndim == 4 else 0)

    def pad_sel(sel: SelectorSet, T):
        key = _pad_axis(_pad_axis(np.asarray(sel.key), 1, T, -1), 2, AX, -1)
        op = _pad_axis(_pad_axis(np.asarray(sel.op), 1, T, 0), 2, AX, 0)
        vals = _pad_axis(_pad_axis(_pad_axis(np.asarray(sel.vals), 1, T, -1), 2, AX, -1),
                         3, AV, -1)
        ev = _pad_axis(_pad_axis(np.asarray(sel.expr_valid), 1, T, False), 2, AX, False)
        valid = _pad_axis(np.asarray(sel.valid), 1, T, False)
        return key, op, vals, ev, valid

    ek, eo, ev_, ee, eval_ = pad_sel(ct.ea_sel, ET)
    pk, po, pv, pe, pval = pad_sel(pb.anti_sel, ET)
    ea_sel = SelectorSet(
        key=np.concatenate([ek, pk]), op=np.concatenate([eo, po]),
        vals=np.concatenate([ev_, pv]), expr_valid=np.concatenate([ee, pe]),
        valid=np.concatenate([eval_, pval]))
    ea_topo = np.concatenate([_pad_axis(np.asarray(ct.ea_topo), 1, ET, -1),
                              _pad_axis(np.asarray(pb.anti_topo), 1, ET, -1)])
    ea_valid = np.concatenate([_pad_axis(np.asarray(ct.ea_valid), 1, ET, False),
                               _pad_axis(np.asarray(pb.anti_valid), 1, ET, False)])
    # unify the namespace-mask width (the tables only grow, so the larger
    # bucket covers every id the smaller one can hold)
    NSB = max(int(ct.ea_ns_mask.shape[2]), int(pb.anti_ns_mask.shape[2]))
    ea_ns_explicit = np.concatenate([
        _pad_axis(np.asarray(ct.ea_ns_explicit), 1, ET, False),
        _pad_axis(np.asarray(pb.anti_ns_explicit), 1, ET, False)])
    ea_ns_mask = np.concatenate([
        _pad_axis(_pad_axis(np.asarray(ct.ea_ns_mask), 1, ET, False), 2, NSB, False),
        _pad_axis(_pad_axis(np.asarray(pb.anti_ns_mask), 1, ET, False), 2, NSB, False)])
    return ct.replace(
        epod_node=np.concatenate([np.asarray(ct.epod_node), np.full(P, -1, np.int32)]),
        epod_ns=np.concatenate([np.asarray(ct.epod_ns), np.asarray(pb.pod_ns)]),
        epod_labels=epod_labels,
        epod_valid=np.concatenate([np.asarray(ct.epod_valid), np.zeros(P, bool)]),
        ea_sel=ea_sel, ea_topo=ea_topo, ea_valid=ea_valid,
        ea_ns_explicit=ea_ns_explicit, ea_ns_mask=ea_ns_mask,
    )


def _segmented_capacity_accept(choice, want, rank, requests, free_at_choice,
                               per_node_cap=None):
    """Per-node priority-ordered capacity acceptance.

    choice [P] proposed node; want [P] proposal live; rank [P] lower = first;
    requests [P,R]; free_at_choice [P,R] free capacity on the proposed node;
    per_node_cap: scalar max acceptances per node this round (balance guard —
    batch members share one snapshot, so without a cap equal-score pods pile
    onto tie-break winners instead of spreading like the serial loop).
    Returns accept [P] bool. Uses sort + segmented exclusive cumsum.
    """
    P = choice.shape[0]
    node_key = jnp.where(want, choice, jnp.int32(0x3FFFFFFF))
    order = jnp.lexsort((rank, node_key))          # group by node, rank within
    sn = node_key[order]
    seg_start = jnp.concatenate([jnp.ones(1, bool), sn[1:] != sn[:-1]])

    def seg_excl(values):
        """Segmented exclusive prefix sums along axis 0 (values >= 0)."""
        cs = jnp.cumsum(values, axis=0)
        base = jnp.where(seg_start[:, None], cs - values, jnp.iinfo(jnp.int32).min)
        base = jax.lax.associative_scan(jnp.maximum, base, axis=0)
        return cs - values - base

    req_s = jnp.where(want[order, None], requests[order], 0)
    fits = jnp.all(seg_excl(req_s) + req_s <= free_at_choice[order], axis=-1)
    fits &= want[order]
    if per_node_cap is not None:
        # cap counts capacity-FITTING entries only (rejected ones don't burn
        # slots); a second scan over the fits indicator gives that count.
        ones = fits[:, None].astype(jnp.int32)
        fits &= seg_excl(ones)[:, 0] < per_node_cap
    accept = jnp.zeros(P, bool).at[order].set(fits)
    return accept


def _relational_veto(ct: ClusterTensors, pb: PodBatch, choice, accept, rank,
                     topo_keys: tuple[int, ...], spread_room=None):
    """Reject accepted pods conflicting with a higher-rank pod accepted this
    round (anti-affinity both directions, required affinity forcing
    co-location) or whose hard-spread constraint has no room left for them.
    Conservative; rejects re-propose next round.

    The hard-spread arm counts against ``spread_room`` [P,S] — ``maxSkew``
    less the skew the filter computed at the pod's chosen node
    (``StepResult.spread_room``; the minimum is the one at the round's
    opening, before any of this round's commits). Pod q is rejected when
    more higher-rank accepted pods than that, matched by q's selector in q's
    namespace, chose q's domain. Replayed in rank order every commit then
    passes upstream's filter: commits only raise counts, so the true minimum
    is never below the one used, and a counted pod that another arm rejects
    only over-counts. ``spread_room`` None (no constraint in the batch, or a
    profile without the PodTopologySpread filter: nothing to keep valid)
    skips the arm."""
    from kubernetes_tpu.ops.exprs import eval_selector_set
    from kubernetes_tpu.ops.topology import _gather_ns
    P = pb.pod_valid.shape[0]
    K = ct.node_labels.shape[1]
    higher = (rank[None, :] < rank[:, None]) & accept[None, :] & accept[:, None]  # [q,p]
    conflict = jnp.zeros((P, P), bool)
    no_room = jnp.zeros(P, bool)
    ns_eq = pb.pod_ns[:, None] == pb.pod_ns[None, :]                # [q,p]

    def _term_ns_ok(explicit, mask):
        """[q,T,p]: does q's term t apply to p's namespace?"""
        exp = _gather_ns(mask, pb.pod_ns)                           # [q,T,p]
        return jnp.where(explicit[..., None], exp, ns_eq[:, None, :])

    for k in topo_keys:
        if k < 0 or k >= K:
            continue
        dv = ct.node_labels[:, k]                                   # [N]
        dvc = dv[jnp.clip(choice, 0, dv.shape[0] - 1)]              # [P] chosen domain
        same = (dvc[:, None] == dvc[None, :]) & (dvc[:, None] >= 0)  # [q,p]
        if pb.anti_valid.shape[1] > 0:
            m = eval_selector_set(pb.anti_sel, pb.pod_labels)       # [p_t, q, BT]
            qt = (pb.anti_topo == k) & pb.anti_valid                # [q,BT]
            ns_ok = _term_ns_ok(pb.anti_ns_explicit, pb.anti_ns_mask)  # [q,BT,p]
            # q's term matches p (selector + per-term namespaces): m[p, q, t]
            q_hits_p = jnp.any(jnp.moveaxis(m, 0, 2) & qt[..., None]
                               & ns_ok, axis=1)                     # [q,p]
            conflict |= q_hits_p & same
            # symmetry: p's anti term matches q -> q (lower rank) rejected
            conflict |= q_hits_p.T & same
        if spread_room is not None:
            m = eval_selector_set(pb.sc_sel, pb.pod_labels)         # [p_t, q, SC]
            qt = (pb.sc_topo == k) & pb.sc_valid & pb.sc_hard       # [q,SC]
            ahead = same & ns_eq & higher   # [q,p]; spread: own namespace only
            before = jnp.sum(m & ahead.T[..., None], axis=0,
                             dtype=jnp.int32)                       # [q,SC]
            no_room |= jnp.any(qt & (before.astype(jnp.float32) > spread_room),
                               axis=-1)
        if pb.aff_valid.shape[1] > 0:
            m = eval_selector_set(pb.aff_sel, pb.pod_labels)        # [p_t, q, AT]
            qt = (pb.aff_topo == k) & pb.aff_valid
            ns_ok = _term_ns_ok(pb.aff_ns_explicit, pb.aff_ns_mask)  # [q,AT,p]
            q_hits_p = jnp.any(jnp.moveaxis(m, 0, 2) & qt[..., None]
                               & ns_ok, axis=1)                     # [q,p]
            # required affinity: must be in SAME domain as matching member
            conflict |= q_hits_p & ~same
    veto = jnp.any(conflict & higher, axis=1) | no_room
    return accept & ~veto


def _gang_round_impl(ct_ext: ClusterTensors, pb: PodBatch, state: GangState,
                     seed: int = 0, fit_strategy: str = "LeastAllocated",
                     topo_keys: tuple[int, ...] = (), serial: bool = False,
                     weights: tuple = (), enabled_filters: tuple = (),
                     cap_scale=1, slot_start=None, ext_mask=None,
                     ext_scores=None, plugins: tuple = ()):
    """Traceable body of one propose/accept/fold round. Returns
    (new_state, progress) where progress counts acceptances (plus serial-mode
    attempts). ``slot_start``: index (may be traced) of this batch's extension
    slots in the epod tensors; defaults to the trailing P slots."""
    P = state.committed.shape[0]
    N = ct_ext.node_valid.shape[0]
    if slot_start is None:
        slot_start = ct_ext.epod_valid.shape[0] - P
    # wire committed members into this batch's extension slots
    ct_round = ct_ext.replace(
        requested=state.requested,
        epod_node=jax.lax.dynamic_update_slice(
            ct_ext.epod_node, state.assignment, (slot_start,)),
        epod_valid=jax.lax.dynamic_update_slice(
            ct_ext.epod_valid, state.committed, (slot_start,)),
    )
    pb_round = pb.replace(pod_valid=pb.pod_valid & ~state.committed)
    with jax.named_scope("gang/evaluate"):
        res = evaluate(ct_round, pb_round, seed=seed,
                       fit_strategy=fit_strategy, topo_keys=topo_keys,
                       weights=dict(weights) if weights else None,
                       enabled_filters=frozenset(enabled_filters) if enabled_filters else None,
                       ext_mask=ext_mask, ext_scores=ext_scores, plugins=plugins)
    want = res.assigned & ~state.committed & pb.pod_valid
    tried = state.tried
    n_attempted = jnp.int32(0)
    if serial:
        # Exact ScheduleOne semantics: attempt pods once each, in a-priori
        # (priority desc, index asc) order — a pod that fails is NOT retried
        # even if later commits would make it feasible.
        untried = ~state.committed & ~tried & pb.pod_valid
        tprio = jnp.where(untried, -pb.priority, jnp.iinfo(jnp.int32).max)
        torder = jnp.lexsort((jnp.arange(P), tprio))
        target = torder[0]
        is_target = (jnp.arange(P) == target) & untried[target]
        want = want & is_target
        tried = tried | is_target
        n_attempted = jnp.sum(is_target).astype(jnp.int32)
    with jax.named_scope("gang/accept"):
        # rank: priority desc, batch index asc; non-proposing pods rank last
        prio_key = jnp.where(want, -pb.priority, jnp.iinfo(jnp.int32).max)
        order0 = jnp.lexsort((jnp.arange(P), prio_key))
        rank = jnp.zeros(P, jnp.int32).at[order0].set(jnp.arange(P, dtype=jnp.int32))
        free = ct_round.allocatable - state.requested                   # [N,R]
        free_at_choice = free[jnp.clip(res.choice, 0, N - 1)]
        # Balance guard: spread this round's acceptances across the nodes feasible
        # for someone, approximating the serial loop's load feedback. cap_scale
        # doubles every round (driver), so strict-preference workloads where the
        # cap would serialize still converge in O(log P) rounds — early rounds do
        # the balancing, late rounds drain.
        distinct = jnp.sum(jnp.any(res.feasible & want[:, None], axis=0))
        cap = jnp.maximum(1, -(-jnp.sum(want) // jnp.maximum(distinct, 1))) * cap_scale
        accept = _segmented_capacity_accept(res.choice, want, rank, pb.requests,
                                            free_at_choice, per_node_cap=cap)
    with jax.named_scope("gang/veto"):
        accept = _relational_veto(ct_round, pb, res.choice, accept, rank,
                                  topo_keys, res.spread_room)
    with jax.named_scope("gang/commit"):
        onehot = ((res.choice[:, None] == jnp.arange(N)[None, :])
                  & accept[:, None])
        add = jnp.einsum("pn,pr->nr", onehot.astype(jnp.int32), pb.requests)
        new_state = GangState(
            requested=state.requested + add,
            committed=state.committed | accept,
            assignment=jnp.where(accept, res.choice, state.assignment),
            tried=tried,
            rounds=state.rounds + 1,
        )
        progress = jnp.sum(accept) + n_attempted
    return new_state, progress


gang_round = partial(jax.jit, static_argnames=(
    "seed", "fit_strategy", "topo_keys", "serial", "weights",
    "enabled_filters", "plugins"))(_gang_round_impl)


@partial(jax.jit, static_argnames=("seed", "fit_strategy", "topo_keys",
                                   "serial", "weights", "enabled_filters",
                                   "max_rounds", "plugins"))
def gang_converge(ct_ext: ClusterTensors, pb: PodBatch, state: GangState,
                  seed: int = 0, fit_strategy: str = "LeastAllocated",
                  topo_keys: tuple[int, ...] = (), serial: bool = False,
                  weights: tuple = (), enabled_filters: tuple = (),
                  max_rounds: int = 64, ext_mask=None,
                  ext_scores=None, plugins: tuple = ()) -> GangState:
    """On-device convergence: the whole propose/accept/fold round sequence is
    one XLA program — no device→host sync per round (the reference's per-pod
    loop is host-side; our analog keeps the batch's entire conflict resolution
    on device and transfers once per batch).

    Shape: a STATIC-trip ``fori_loop`` whose body is a ``lax.cond`` that
    becomes a no-op once a round makes no progress. A data-dependent
    ``while_loop`` would be semantically cleaner, but on remote-attached TPU
    runtimes each dynamic condition evaluation stalls the dispatch pipeline
    for a host round-trip (~100ms/iteration measured); a constant-trip loop
    with a conditional body runs entirely ahead of the host, and the dead
    branch costs nothing after convergence."""
    return _converge(ct_ext, pb, state, seed=seed, fit_strategy=fit_strategy,
                     topo_keys=topo_keys, serial=serial, weights=weights,
                     enabled_filters=enabled_filters, max_rounds=max_rounds,
                     ext_mask=ext_mask, ext_scores=ext_scores, plugins=plugins)


def _converge(ct_ext, pb, state, *, seed, fit_strategy, topo_keys,
              weights, enabled_filters, max_rounds, serial=False,
              slot_start=None, ext_mask=None, ext_scores=None,
              plugins: tuple = ()) -> GangState:
    """Shared traceable convergence loop (gang_converge + the drain's
    per-batch step): fori(max_rounds) of cond-guarded rounds."""
    def body(i, carry):
        def live(c):
            st, _ = c
            # cap_scale doubles every live round (see _gang_round_impl);
            # no progress => cond is dead forever, so i counts live rounds.
            cap = jnp.left_shift(jnp.int32(1), jnp.minimum(i, 20))
            return _gang_round_impl(ct_ext, pb, st, seed=seed,
                                    fit_strategy=fit_strategy,
                                    topo_keys=topo_keys, serial=serial,
                                    weights=weights,
                                    enabled_filters=enabled_filters,
                                    cap_scale=cap, slot_start=slot_start,
                                    ext_mask=ext_mask, ext_scores=ext_scores,
                                    plugins=plugins)
        _, n = carry
        return jax.lax.cond(n > 0, live, lambda c: c, carry)

    state, _ = jax.lax.fori_loop(0, max(int(max_rounds), 1), body,
                                 (state, jnp.int32(1)))
    return state


def gang_schedule(ct: ClusterTensors, pb: PodBatch, seed: int = 0,
                  fit_strategy: str = "LeastAllocated",
                  topo_keys: tuple[int, ...] = (), serial: bool = False,
                  max_rounds: int = 64, weights=None, enabled_filters=None,
                  mesh=None, ext_mask=None, ext_scores=None,
                  plugins: tuple = ()):
    """Drive rounds until convergence. Returns (assignment [P] np.int32 with -1
    for unschedulable, rounds_used). ``weights`` (plugin->weight) and
    ``enabled_filters`` (set of filter names) carry the active profile's
    plugin configuration; they are static for jit purposes. ``mesh``: optional
    ("pods","nodes") Mesh — tensors are sharded over it and the converge
    program runs with GSPMD collectives over the node/pod axes."""
    P = int(pb.pod_valid.shape[0])
    ct_ext = extend_cluster(ct, pb)
    if mesh is not None:
        from kubernetes_tpu.parallel.mesh import shard_batch, shard_cluster
        ct_ext = shard_cluster(mesh, ct_ext)
        pb = shard_batch(mesh, pb)
    state = GangState(
        requested=jnp.asarray(ct.requested),
        committed=jnp.zeros(P, bool),
        assignment=jnp.full(P, -1, jnp.int32),
        tried=jnp.zeros(P, bool),
        rounds=jnp.zeros((), jnp.int32),
    )
    weights_t = tuple(sorted(weights.items())) if weights else ()
    filters_t = tuple(sorted(enabled_filters)) if enabled_filters else ()
    limit = max(P if serial else max_rounds, 1)
    if ext_mask is not None:
        ext_mask = jnp.asarray(ext_mask)
    if ext_scores is not None:
        ext_scores = jnp.asarray(ext_scores)
    state = gang_converge(ct_ext, pb, state, seed=seed,
                          fit_strategy=fit_strategy, topo_keys=topo_keys,
                          serial=serial, weights=weights_t,
                          enabled_filters=filters_t, max_rounds=limit,
                          ext_mask=ext_mask, ext_scores=ext_scores,
                          plugins=plugins)
    # one batched readback: sequential per-array fetches each pay a full
    # host<->device round trip (~100ms on remote-attached TPUs)
    # ktpu-lint: disable=KTL005 -- legacy non-resident gang path: its contract IS one batched readback per convergence
    assignment, rounds = jax.device_get((state.assignment, state.rounds))
    return assignment, int(rounds)


# -- multi-batch drain: the whole queue as ONE device program ----------------

def _pad_to(a: np.ndarray, shape: tuple[int, ...], fill):
    pads = [(0, t - s) for s, t in zip(a.shape, shape)]
    if not any(hi for _, hi in pads):
        return a
    return np.pad(a, pads, constant_values=fill)


def unify_batches(pbs: list[PodBatch]) -> list[PodBatch]:
    """Host-side: pad every leaf of each PodBatch to the max shape across
    batches. Bucket dims (selector terms, toleration slots, ...) can differ
    batch to batch; every padded region is guarded by its validity flag, so
    dtype-driven fills (-1 ids / False / 0.0) are semantically inert."""
    leaves = [jax.tree_util.tree_leaves(pb) for pb in pbs]
    treedef = jax.tree_util.tree_structure(pbs[0])
    unified: list[list[np.ndarray]] = []
    for i in range(len(leaves[0])):
        arrs = [np.asarray(ls[i]) for ls in leaves]
        shape = tuple(max(a.shape[d] for a in arrs)
                      for d in range(arrs[0].ndim))
        if arrs[0].dtype == bool:
            fill = False
        elif np.issubdtype(arrs[0].dtype, np.floating):
            fill = 0.0
        else:
            fill = -1
        unified.append([_pad_to(a, shape, fill) for a in arrs])
    return [jax.tree_util.tree_unflatten(
                treedef, [unified[i][b] for i in range(len(unified))])
            for b in range(len(pbs))]


def extend_cluster_drain(ct: ClusterTensors, pbs: list[PodBatch]
                         ) -> tuple[ClusterTensors, int]:
    """Chain P extension slots for EVERY batch onto the cluster: batch b's
    pods live at epod slots [e0 + b*P, e0 + (b+1)*P). Committed members of
    earlier batches therefore stay relationally visible (spread counts,
    affinity, anti-affinity symmetry) to later batches — the sequential
    semantics the reference's one-pod-at-a-time loop gets for free."""
    e0 = int(ct.epod_valid.shape[0])
    for pb in pbs:
        ct = extend_cluster(ct, pb)
    return ct, e0


@partial(jax.jit, static_argnames=("e0", "seed", "fit_strategy", "topo_keys",
                                   "weights", "enabled_filters", "max_rounds",
                                   "plugins"))
def _gang_drain_compiled(ct_all: ClusterTensors, pb_stack: PodBatch, e0: int,
                         seed: int, fit_strategy: str,
                         topo_keys: tuple[int, ...], weights: tuple,
                         enabled_filters: tuple, max_rounds: int,
                         plugins: tuple = ()):
    B, P = pb_stack.pod_valid.shape

    def batch_body(carry, xs):
        requested, epod_node, epod_valid = carry
        pb, b = xs
        start = e0 + b * P
        ct_b = ct_all.replace(epod_node=epod_node, epod_valid=epod_valid)
        st0 = GangState(requested=requested,
                        committed=jnp.zeros(P, bool),
                        assignment=jnp.full(P, -1, jnp.int32),
                        tried=jnp.zeros(P, bool),
                        rounds=jnp.zeros((), jnp.int32))
        st = _converge(ct_b, pb, st0, seed=seed, fit_strategy=fit_strategy,
                       topo_keys=topo_keys, weights=weights,
                       enabled_filters=enabled_filters,
                       max_rounds=max_rounds, slot_start=start,
                       plugins=plugins)
        epod_node = jax.lax.dynamic_update_slice(
            epod_node, st.assignment, (start,))
        epod_valid = jax.lax.dynamic_update_slice(
            epod_valid, st.committed, (start,))
        return ((st.requested, epod_node, epod_valid),
                (st.assignment, st.rounds))

    carry0 = (jnp.asarray(ct_all.requested),
              jnp.asarray(ct_all.epod_node),
              jnp.asarray(ct_all.epod_valid))
    (requested, _, _), (assignments, rounds) = jax.lax.scan(
        batch_body, carry0, (pb_stack, jnp.arange(B)))
    return assignments, rounds, requested


_stage = jax.jit(lambda tree: tree)


# -- device-resident drain: cluster tensors stay in HBM across drains --------
#
# The connected scheduler's steady state is a loop of drains over an almost-
# unchanged cluster. Re-uploading the full encoding every drain (tens of MB
# over a remote-attached TPU link) dominated the connected path's wall time;
# this keeps ``ct_all`` device-resident and per drain ships only the new pod
# batches (~1MB): refill the extension rows from the batch, run the scan,
# then FOLD committed pods into free base existing-pod slots on device — the
# donate-buffers snapshot update of SURVEY §7 phase 8.

def _flat(x):
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def _jpad(a, axis: int, size: int, fill):
    if a.shape[axis] == size:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, pads, constant_values=fill)


def drain_widths_fit(ct_all: ClusterTensors, pb_stack: PodBatch) -> bool:
    """Host-side guard: the batch's bucket widths must fit the resident
    extension slots (they only grow when pods carry new label keys / wider
    anti-affinity terms — fall back to a host re-encode when they do)."""
    return (pb_stack.pod_labels.shape[2] <= ct_all.epod_labels.shape[1]
            and pb_stack.anti_valid.shape[2] <= ct_all.ea_valid.shape[1]
            and pb_stack.anti_sel.key.shape[3] <= ct_all.ea_sel.key.shape[2]
            and pb_stack.anti_sel.vals.shape[4] <= ct_all.ea_sel.vals.shape[3]
            and pb_stack.anti_ns_mask.shape[3] <= ct_all.ea_ns_mask.shape[2]
            and pb_stack.requests.shape[2] == ct_all.requested.shape[1])


@partial(jax.jit, donate_argnums=(0, 2),
         static_argnames=("e0", "seed", "fit_strategy", "topo_keys",
                          "weights", "enabled_filters", "max_rounds",
                          "plugins", "winners_sharding", "mesh"))
def drain_step(ct_all: ClusterTensors, pb_stack: PodBatch, fill,
               patch=None, *,
               e0: int, seed: int, fit_strategy: str,
               topo_keys: tuple[int, ...], weights: tuple,
               enabled_filters: tuple, max_rounds: int,
               plugins: tuple = (), winners_sharding=None, mesh=None):
    """One fused drain over a DEVICE-RESIDENT cluster encoding.

    ``ct_all``: donated; rows [0,e0) are base existing-pod slots (``fill`` of
    them occupied, packed), rows [e0,e0+B*P) are extension slots whose content
    this call overwrites from ``pb_stack``. ``fill`` is donated too — in
    steady state it is the previous call's device-resident ``new_fill`` and
    the scalar aliases in place instead of allocating per drain. Returns
    ``(assignments [B,P], rounds [B], new_ct_all, new_fill)`` where
    ``new_ct_all`` has every committed pod folded into base slots
    [fill, fill+n) and the extension region invalidated — ready to be the
    next call's ``ct_all`` with zero host↔device traffic.

    ``patch``: optional compiled churn patch (encode/patch.py) — the THIRD
    input of the resident program. When present, the scatter that used to
    be a separate blocking ``apply_ctx_patch`` dispatch is FUSED in front
    of the scan: foreign churn folds into the same device program that
    schedules over it, so a churn cycle costs zero extra dispatches and
    (when the deltas are fold-safe) no pipeline drain. The patch arrays
    are ~KB and compile at fixed bucket widths, so the fused variant is
    one extra XLA program, compiled once at warmup.

    ``winners_sharding``: optional (hashable) NamedSharding the compact
    winners view (assignments + rounds + new_fill) is constrained to. Under
    a device mesh the cluster encoding stays sharded in HBM, and pinning
    the winners replicated means the resolver's device_get moves O(B*P)
    int32s — never a gathered sharded intermediate.

    ``mesh``: optional (hashable) Mesh — the folded ``new_ct_all`` is
    constrained to the canonical cluster shardings, making the OUTPUT
    shardings exactly the next dispatch's INPUT shardings: donation then
    aliases the whole resident encoding in place across steady-state
    drains (zero copy-on-donate, zero resharding between cycles).
    """
    # the scopes below are metadata for the profiler's trace: the four
    # stages of the resident program, findable by name after a refactor
    if patch is not None:
        with jax.named_scope("drain/patch"):
            ct_all = _apply_patch(ct_all, patch)
    B, P = pb_stack.pod_valid.shape
    K = ct_all.epod_labels.shape[1]
    ET = ct_all.ea_valid.shape[1]
    AX = ct_all.ea_sel.key.shape[2]
    AV = ct_all.ea_sel.vals.shape[3]
    NSB = ct_all.ea_ns_mask.shape[2]
    BP = B * P

    def ext(base, new):
        return jnp.concatenate([base[:e0], new], axis=0)

    with jax.named_scope("drain/extend"):
        ct_r = ct_all.replace(
            epod_node=ext(ct_all.epod_node, jnp.full(BP, -1, jnp.int32)),
            epod_ns=ext(ct_all.epod_ns, _flat(pb_stack.pod_ns)),
            epod_labels=ext(ct_all.epod_labels,
                            _jpad(_flat(pb_stack.pod_labels), 1, K, -1)),
            epod_valid=ext(ct_all.epod_valid, jnp.zeros(BP, bool)),
            ea_sel=SelectorSet(
                key=ext(ct_all.ea_sel.key,
                        _jpad(_jpad(_flat(pb_stack.anti_sel.key), 1, ET, -1),
                              2, AX, -1)),
                op=ext(ct_all.ea_sel.op,
                       _jpad(_jpad(_flat(pb_stack.anti_sel.op), 1, ET, 0),
                             2, AX, 0)),
                vals=ext(ct_all.ea_sel.vals,
                         _jpad(_jpad(_jpad(_flat(pb_stack.anti_sel.vals),
                                           1, ET, -1), 2, AX, -1), 3, AV, -1)),
                expr_valid=ext(ct_all.ea_sel.expr_valid,
                               _jpad(_jpad(_flat(pb_stack.anti_sel.expr_valid),
                                           1, ET, False), 2, AX, False)),
                valid=ext(ct_all.ea_sel.valid,
                          _jpad(_flat(pb_stack.anti_sel.valid), 1, ET, False))),
            ea_topo=ext(ct_all.ea_topo, _jpad(_flat(pb_stack.anti_topo), 1, ET, -1)),
            ea_valid=ext(ct_all.ea_valid,
                         _jpad(_flat(pb_stack.anti_valid), 1, ET, False)),
            ea_ns_explicit=ext(ct_all.ea_ns_explicit,
                               _jpad(_flat(pb_stack.anti_ns_explicit), 1, ET, False)),
            ea_ns_mask=ext(ct_all.ea_ns_mask,
                           _jpad(_jpad(_flat(pb_stack.anti_ns_mask), 1, ET, False),
                                 2, NSB, False)),
        )

    def batch_body(carry, xs):
        requested, epod_node, epod_valid = carry
        pb, b = xs
        start = e0 + b * P
        ct_b = ct_r.replace(epod_node=epod_node, epod_valid=epod_valid)
        st0 = GangState(requested=requested,
                        committed=jnp.zeros(P, bool),
                        assignment=jnp.full(P, -1, jnp.int32),
                        tried=jnp.zeros(P, bool),
                        rounds=jnp.zeros((), jnp.int32))
        st = _converge(ct_b, pb, st0, seed=seed, fit_strategy=fit_strategy,
                       topo_keys=topo_keys, weights=weights,
                       enabled_filters=enabled_filters,
                       max_rounds=max_rounds, slot_start=start,
                       plugins=plugins)
        epod_node = jax.lax.dynamic_update_slice(
            epod_node, st.assignment, (start,))
        epod_valid = jax.lax.dynamic_update_slice(
            epod_valid, st.committed, (start,))
        return ((st.requested, epod_node, epod_valid),
                (st.assignment, st.rounds))

    carry0 = (ct_r.requested, ct_r.epod_node, ct_r.epod_valid)
    with jax.named_scope("drain/converge"):
        (requested, epod_node, epod_valid), (assignments, rounds) = (
            jax.lax.scan(batch_body, carry0, (pb_stack, jnp.arange(B))))

    # ---- fold committed pods into base slots [fill, fill+n) --------------
    with jax.named_scope("drain/fold"):
        flags = _flat(assignments >= 0)
        # exclusive prefix count -> packed destinations; uncommitted rows get an
        # out-of-bounds index and are dropped by the scatter
        dest = jnp.where(flags, fill + jnp.cumsum(flags) - flags, e0 + BP)

        def fold(arr):
            return arr.at[dest].set(arr[e0:], mode="drop")

        ct_out = ct_r.replace(
            requested=requested,
            epod_node=epod_node.at[dest].set(_flat(assignments), mode="drop"),
            epod_ns=fold(ct_r.epod_ns),
            epod_labels=fold(ct_r.epod_labels),
            # fold then invalidate the extension region (labels/terms of dead
            # rows are inert once the valid flags drop)
            epod_valid=epod_valid.at[dest].set(flags, mode="drop")
                                 .at[e0:].set(False),
            ea_sel=SelectorSet(key=fold(ct_r.ea_sel.key), op=fold(ct_r.ea_sel.op),
                               vals=fold(ct_r.ea_sel.vals),
                               expr_valid=fold(ct_r.ea_sel.expr_valid),
                               valid=fold(ct_r.ea_sel.valid)),
            ea_topo=fold(ct_r.ea_topo),
            ea_valid=fold(ct_r.ea_valid).at[e0:].set(False),
            ea_ns_explicit=fold(ct_r.ea_ns_explicit),
            ea_ns_mask=fold(ct_r.ea_ns_mask),
        )
        new_fill = fill + jnp.sum(flags, dtype=jnp.int32)
    if mesh is not None:
        from kubernetes_tpu.parallel.mesh import constrain_cluster
        ct_out = constrain_cluster(mesh, ct_out)
    if winners_sharding is not None:
        constrain = partial(jax.lax.with_sharding_constraint,
                            shardings=winners_sharding)
        assignments, rounds, new_fill = (
            constrain(assignments), constrain(rounds), constrain(new_fill))
    return assignments, rounds, ct_out, new_fill


def pad_batch_to(pb_stack: PodBatch, shapes: list[tuple]):
    """Pad every leaf of a stacked PodBatch up to recorded target shapes so
    runtime drains reuse ONE compiled program regardless of each pop's
    bucket widths (pop composition varies; padding is inert behind validity
    flags). Returns None when any leaf EXCEEDS its target — the caller must
    rebuild/recompile at the wider shape."""
    leaves = jax.tree_util.tree_leaves(pb_stack)
    treedef = jax.tree_util.tree_structure(pb_stack)
    out = []
    for leaf, target in zip(leaves, shapes):
        a = np.asarray(leaf)
        if a.shape == tuple(target):
            out.append(a)
            continue
        if any(s > t for s, t in zip(a.shape, target)):
            return None
        if a.dtype == bool:
            fill = False
        elif np.issubdtype(a.dtype, np.floating):
            fill = 0.0
        else:
            fill = -1
        out.append(_pad_to(a, tuple(target), fill))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shapes(pb_stack: PodBatch) -> list[tuple]:
    return [tuple(np.asarray(l).shape)
            for l in jax.tree_util.tree_leaves(pb_stack)]


def build_drain_context(ct: ClusterTensors, pbs: list[PodBatch],
                        nom_bucket: int = 0, mesh=None):
    """Host-side one-time prep for the device-resident drain: unify the batch
    buckets, chain extension slots (content is placeholder — drain_step
    refills it), stage everything into HBM. Returns
    ``(ct_all_device, e0, fill0)`` or None when base epod slots aren't packed
    (fold targets assume [0,fill) occupied, [fill,e0) free — true after any
    full encode; host-side patches with deletes can leave holes).

    ``nom_bucket``: size of the RESIDENT nominee-reservation tensors. The
    base encode carries zero nominees; giving the context a fixed M lets
    preemption storms patch reservations device-side (apply_ctx_patch)
    instead of dropping to the per-batch overlay path.

    ``mesh``: optional ("pods","nodes") Mesh — the encoding is device_put
    SHARDED (node-axis arrays split over "nodes", everything else
    replicated; parallel/mesh.py cluster_shardings) so drain_step lowers to
    GSPMD collectives and the resident context lives distributed across the
    mesh's HBM instead of one chip's."""
    pbs_u = unify_batches(pbs)
    ct_all, e0 = extend_cluster_drain(ct, pbs_u)
    valid = np.asarray(ct_all.epod_valid)[:e0]
    fill0 = int(valid.sum())
    if fill0 and not valid[:fill0].all():
        return None  # holes: device fold would overwrite occupied slots
    if nom_bucket:
        R = int(np.asarray(ct_all.requested).shape[1])
        ct_all = ct_all.replace(
            nom_node=np.full(nom_bucket, -1, np.int32),
            nom_prio=np.zeros(nom_bucket, np.int32),
            nom_req=np.zeros((nom_bucket, R), np.int32),
            nom_valid=np.zeros(nom_bucket, bool))
    if mesh is not None:
        from kubernetes_tpu.parallel.mesh import shard_cluster
        ct_dev = shard_cluster(mesh, ct_all)
    else:
        ct_dev = _stage(ct_all)
    return ct_dev, e0, fill0


def _apply_patch(ct_all: ClusterTensors, patch: dict) -> ClusterTensors:
    """Traceable body of the churn-patch scatter: pod slot rewrites/clears,
    node row rewrites/retires, nominee reservation diffs, and the dense
    requested[N,R] delta. Shared by the standalone ``apply_ctx_patch``
    dispatch (rebuild-time nominee staging) and the fused
    drain (``drain_step``'s third input), so the two paths can never drift.

    Reference shape: the incremental half of ``Cache.UpdateSnapshot``
    (pkg/scheduler/internal/cache/cache.go) — churn moves only what changed."""
    # Out-of-range sentinel: scatter mode="drop" ignores the row. UNSIGNED
    # on purpose — signed scatter indices make jnp emit a negative-wrap
    # `select(i < 0, i + dim, i)` that is dead here (idx() already maps
    # negatives to BIG), and the dead branch's `dim` constant proved
    # trace-unstable across interpreter runs. A flipped dead constant
    # re-keys the persistent executable cache, so a restarted scheduler
    # would pay a genuine recompile for a program it already has on disk.
    # Unsigned indices skip the wrap lowering entirely.
    BIG = jnp.uint32(1 << 30)

    def idx(a):
        return jnp.where(a < 0, BIG, a.astype(jnp.uint32))

    ps = idx(patch["pod_slot"])
    ns_ = idx(patch["node_row"])
    ms = idx(patch["nom_slot"])
    N = ct_all.node_valid.shape[0]

    # node rows being reset (fresh assignment of a freed/new row) clear the
    # pod-contributed state patches cannot reconstruct (ports/volumes are
    # guarded unpatchable, so a resettable row never has live entries)
    reset = jnp.zeros(N, bool).at[ns_].set(patch["n_reset"], mode="drop")
    requested = jnp.where(reset[:, None], 0, ct_all.requested) \
        + patch["req_delta"]

    def sc(base, i, vals):
        return base.at[i].set(vals, mode="drop")

    return ct_all.replace(
        requested=requested,
        label_value_num=patch["label_value_num"],
        # ---- pod slots
        epod_node=sc(ct_all.epod_node, ps, patch["pod_node"]),
        epod_ns=sc(ct_all.epod_ns, ps, patch["pod_ns"]),
        epod_labels=sc(ct_all.epod_labels, ps, patch["pod_labels"]),
        epod_valid=sc(ct_all.epod_valid, ps, patch["pod_valid"]),
        ea_sel=SelectorSet(
            key=sc(ct_all.ea_sel.key, ps, patch["ea_sel_key"]),
            op=sc(ct_all.ea_sel.op, ps, patch["ea_sel_op"]),
            vals=sc(ct_all.ea_sel.vals, ps, patch["ea_sel_vals"]),
            expr_valid=sc(ct_all.ea_sel.expr_valid, ps,
                          patch["ea_sel_expr_valid"]),
            valid=sc(ct_all.ea_sel.valid, ps, patch["ea_sel_valid"])),
        ea_topo=sc(ct_all.ea_topo, ps, patch["ea_topo"]),
        ea_valid=sc(ct_all.ea_valid, ps, patch["ea_valid"]),
        ea_ns_explicit=sc(ct_all.ea_ns_explicit, ps,
                          patch["ea_ns_explicit"]),
        ea_ns_mask=sc(ct_all.ea_ns_mask, ps, patch["ea_ns_mask"]),
        # ---- node rows
        allocatable=sc(ct_all.allocatable, ns_, patch["n_alloc"]),
        node_valid=sc(ct_all.node_valid, ns_, patch["n_valid"]),
        unschedulable=sc(ct_all.unschedulable, ns_, patch["n_unsched"]),
        node_labels=sc(ct_all.node_labels, ns_, patch["n_labels"]),
        taint_key=sc(ct_all.taint_key, ns_, patch["n_taint_key"]),
        taint_val=sc(ct_all.taint_val, ns_, patch["n_taint_val"]),
        taint_effect=sc(ct_all.taint_effect, ns_, patch["n_taint_effect"]),
        taint_valid=sc(ct_all.taint_valid, ns_, patch["n_taint_valid"]),
        node_images=sc(ct_all.node_images, ns_, patch["n_images"]),
        attach_limit=sc(ct_all.attach_limit, ns_, patch["n_attach_limit"]),
        attach_used=jnp.where(reset, 0, ct_all.attach_used),
        port_valid=jnp.where(reset[:, None], False, ct_all.port_valid),
        used_rwo_valid=jnp.where(reset[:, None], False,
                                 ct_all.used_rwo_valid),
        # ---- nominee reservations
        nom_node=sc(ct_all.nom_node, ms, patch["nom_node"]),
        nom_prio=sc(ct_all.nom_prio, ms, patch["nom_prio"]),
        nom_req=sc(ct_all.nom_req, ms, patch["nom_req"]),
        nom_valid=sc(ct_all.nom_valid, ms, patch["nom_valid"]),
    )


@partial(jax.jit, donate_argnums=(0,), static_argnames=("mesh",))
def apply_ctx_patch(ct_all: ClusterTensors, patch: dict, mesh=None
                    ) -> ClusterTensors:
    """Standalone churn-patch dispatch (rebuild-time nominee staging).
    ``mesh``: same output-sharding pin as ``drain_step`` —
    the patched encoding must leave this program carrying exactly the
    shardings the next drain dispatch expects, so donation aliases in
    place instead of resharding the resident arrays."""
    out = _apply_patch(ct_all, patch)
    if mesh is not None:
        from kubernetes_tpu.parallel.mesh import constrain_cluster
        out = constrain_cluster(mesh, out)
    return out


def prepare_drain(ct: ClusterTensors, pbs: list[PodBatch], stage: bool = True):
    """Host-side drain prep: unify batch buckets, chain extension slots,
    stack batches, and (by default) stage everything onto the device via a
    jitted identity — so repeated drains over the same cluster state pay zero
    re-transfer (a long-lived scheduler keeps cluster tensors resident in
    HBM; see sched/cache.py's incremental patches for the connected path).
    Returns an opaque (ct_all, pb_stack, e0) tuple for gang_drain."""
    pbs_u = unify_batches(pbs)
    ct_all, e0 = extend_cluster_drain(ct, pbs_u)
    pb_stack = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *pbs_u)
    if stage:
        ct_all, pb_stack = _stage((ct_all, pb_stack))
    return ct_all, pb_stack, e0


def gang_drain(ct: ClusterTensors = None, pbs: list[PodBatch] = None,
               seed: int = 0,
               fit_strategy: str = "LeastAllocated",
               topo_keys: tuple[int, ...] = (), weights=None,
               enabled_filters=None, max_rounds: int = 64, prepared=None,
               plugins: tuple = ()):
    """Schedule a whole queue of batches as ONE device program.

    ``lax.scan`` over the batch axis, each step a full gang convergence,
    carrying (requested[N,R], epod slot state) batch to batch — so capacity
    AND relational effects of earlier batches are visible to later ones, and
    the host pays exactly one dispatch + one readback for the entire drain
    (the per-batch dispatch/sync round-trips the previous design paid are the
    dominant cost on remote-attached TPUs, ~115ms each measured).

    Returns (assignments [B,P] np.int32 with -1 unschedulable,
    rounds [B] np.int32, requested_final [N,R] np.int32).

    ``prepared``: the result of prepare_drain() — pass it to amortize host
    prep + device staging across repeated drains of the same queue shape.
    """
    if prepared is None:
        prepared = prepare_drain(ct, pbs, stage=False)
    ct_all, pb_stack, e0 = prepared
    weights_t = tuple(sorted(weights.items())) if weights else ()
    filters_t = tuple(sorted(enabled_filters)) if enabled_filters else ()
    out = _gang_drain_compiled(
        ct_all, pb_stack, e0=e0, seed=seed, fit_strategy=fit_strategy,
        topo_keys=topo_keys, weights=weights_t, enabled_filters=filters_t,
        max_rounds=max_rounds, plugins=plugins)
    # one batched readback (sequential np.asarray fetches pay a full
    # host<->device round trip each on remote-attached TPUs)
    # ktpu-lint: disable=KTL005 -- legacy non-resident drain entry: one batched readback per drain is its documented cost
    return jax.device_get(out)
