"""Relational plugins: PodTopologySpread and InterPodAffinity on the device.

Reference semantics:
  PodTopologySpread  podtopologyspread/{common,filtering,scoring}.go
  InterPodAffinity   interpodaffinity/{filtering,scoring}.go (incl. the
                     existing-pod anti-affinity *symmetry* veto)

The reference precomputes per-domain pod counts in PreFilter with pods x nodes
Go loops. The TPU design factors the counting into a one-hot matmul and a
scatter/gather over interned domain VALUES:

    match[E,P,T]   selector match of each term against existing pods
    cnt_pn[P,T,N]  = match x onehot(epod_node)        (contraction over E)
    cnt_val[P,T,V] = scatter-add of cnt_pn by each node's domain value
    cnt_dom[P,T,N] = cnt_val gathered back per node

The aggregation is per *distinct topology key* (zone, hostname, ...), a
static Python tuple at trace time — there are only ever a handful, so the
loop unrolls. Memory is O(P*T*(N+V)) and the counts are float32 sums of
integers, exact to 2^24. Not a cnt_pn x same_domain_k[N,N] matmul: a
TPU's default matmul precision rounds the count operand to bfloat16 — 301
matching pods on a node read as 300 and a hard spread admits a node the
oracle refuses (chip_smoke.py count_precision) — and at 5,000 nodes the
scatter/gather is also the faster of the two on the v5e (PERF.md, PR 21).

Namespace semantics: a term with no explicit namespaces applies to the
owning pod's own namespace; terms with ``namespaces``/``namespaceSelector``
carry an encode-time-resolved namespace-id mask (``*_ns_explicit`` +
``*_ns_mask`` — see encode/termprep.py), matched here by gather.

Spread eligibility: nodes failing the incoming pod's nodeSelector/nodeAffinity
(nodeAffinityPolicy=Honor, the default) or carrying untolerated taints
(nodeTaintsPolicy=Honor) are excluded from skew counts and the global
minimum. ``minDomains``: when fewer eligible domains exist, the global
minimum is 0 (filtering.go minMatchNum).
"""

from __future__ import annotations

import jax.numpy as jnp

from kubernetes_tpu.encode.snapshot import ClusterTensors, PodBatch
from kubernetes_tpu.ops.exprs import eval_selector_set

def _gather_ns(ns_mask, ids):
    """ns_mask [..., T, NSB] gathered at interned ids [M] -> [..., T, M]
    (False for out-of-range ids: they were interned after the mask was
    built, so no term's resolved set can contain them)."""
    NSB = ns_mask.shape[-1]
    hit = jnp.take(ns_mask, jnp.clip(ids, 0, NSB - 1), axis=-1)
    return hit & ((ids >= 0) & (ids < NSB))


def _term_match_epods(ct: ClusterTensors, sel, pod_ns,
                      ns_explicit=None, ns_mask=None):
    """Selector match per (existing pod, pod, term) incl. namespace + validity.
    sel: SelectorSet with leading dims [P,T]. -> [E,P,T] float32."""
    m = eval_selector_set(sel, ct.epod_labels)               # [E,P,T]
    own_ok = ct.epod_ns[:, None] == pod_ns[None, :]          # [E,P]
    if ns_explicit is None:
        ns_ok = own_ok[:, :, None]
    else:
        exp = _gather_ns(ns_mask, ct.epod_ns)                # [P,T,E]
        exp = jnp.moveaxis(exp, 2, 0)                        # [E,P,T]
        ns_ok = jnp.where(ns_explicit[None], exp, own_ok[:, :, None])
    return (m & ns_ok & ct.epod_valid[:, None, None]).astype(jnp.float32)


def _self_ns_ok(pb: PodBatch, ns_explicit, ns_mask):
    """Does each pod's own namespace fall in its terms' namespace sets?
    -> [P,T] (True for implicit own-namespace terms)."""
    NSB = ns_mask.shape[-1]
    idx = jnp.clip(pb.pod_ns, 0, NSB - 1)[:, None, None]     # [P,1,1]
    hit = jnp.take_along_axis(ns_mask, idx, axis=2)[..., 0]  # [P,T]
    hit = hit & ((pb.pod_ns >= 0) & (pb.pod_ns < NSB))[:, None]
    return jnp.where(ns_explicit, hit, True)


def _count_pn(ct: ClusterTensors, sel, pod_ns, ns_explicit=None, ns_mask=None):
    """cnt_pn [P,T,N] f32: matching existing pods per (pod, term) per NODE
    (before domain aggregation): selector match [E,P,T] contracted against
    the node one-hot on the MXU. XLA fuses this chain well; a hand-written
    Pallas kernel that kept the match tensor in VMEM was measured 120x
    SLOWER than this path on v5e (16k epods x 1k pods x 4 terms x 5k nodes:
    14.7s vs 122ms/eval — tiny per-grid-step dots starved the MXU, and
    MXU-sized tiles spilled ~74MiB of Mosaic VMEM stack) and was deleted in
    round 4 (the figures above are that comparison's record)."""
    N = ct.node_valid.shape[0]
    match_ept = _term_match_epods(ct, sel, pod_ns, ns_explicit, ns_mask)
    onehot = (ct.epod_node[:, None] == jnp.arange(N)[None, :]).astype(jnp.float32)
    return jnp.einsum("ept,en->ptn", match_ept, onehot)       # [P,T,N]


def _domain_counts(ct: ClusterTensors, cnt_pn, term_topo, topo_keys,
                   elig=None, want_domains=False):
    """-> (cnt_dom [P,T,N] f32, node_has_key [P,T,N] bool,
           num_domains [P,T] f32 | None).

    cnt_dom[p,t,n] = # existing pods matching term (p,t) whose node shares
    node n's domain for the term's topology key (``cnt_pn`` [P,T,N] from
    ``_count_pn``). Nodes lacking the key have has_key False and count 0.
    ``elig`` [P,T,N] restricts which nodes' pods participate (spread
    node-inclusion policies); ``want_domains`` additionally counts distinct
    domains with >=1 eligible node.
    """
    if elig is not None:
        cnt_pn = cnt_pn * elig.astype(jnp.float32)
    cnt_dom = jnp.zeros_like(cnt_pn)
    has_key = jnp.zeros(cnt_pn.shape, bool)
    num_dom = jnp.zeros(cnt_pn.shape[:2], jnp.float32) if want_domains else None
    K = ct.node_labels.shape[1]
    V = ct.label_value_num.shape[0]
    for k in topo_keys:
        if k < 0 or k >= K:
            continue
        dv = ct.node_labels[:, k]                             # [N]
        present = dv >= 0
        sel = term_topo == k                                  # [P,T]
        dv_safe = jnp.clip(dv, 0, max(V - 1, 0))
        # scatter per-VALUE, gather per node: O(P*T*(N+V)), no [N,N]
        src = cnt_pn * present[None, None, :].astype(jnp.float32)
        cnt_val = jnp.zeros(cnt_pn.shape[:2] + (V,), jnp.float32) \
            .at[:, :, dv_safe].add(src)                       # [P,T,V]
        agg = cnt_val[:, :, dv_safe] * present[None, None, :]
        cnt_dom = jnp.where(sel[..., None], agg, cnt_dom)
        has_key = has_key | (sel[..., None] & present[None, None, :])
        if want_domains:
            ek = (present[None, None, :] if elig is None
                  else elig & present[None, None, :])         # [P,T,N]
            # distinct domains = distinct values hit by >=1 eligible node
            hit = jnp.zeros(cnt_pn.shape[:2] + (V,), jnp.float32) \
                .at[:, :, dv_safe].add(ek.astype(jnp.float32))
            nd_k = jnp.sum((hit > 0.0).astype(jnp.float32), axis=-1)
            num_dom = jnp.where(sel, nd_k, num_dom)
    return cnt_dom, has_key, num_dom


# ------------------------------------------------------------------- spread

def _spread_policy_elig(ct: ClusterTensors, pb: PodBatch):
    """Per-constraint node participation [P,S,N]: valid nodes passing
    nodeAffinityPolicy (Honor default: pod's nodeSelector + required node
    affinity) and nodeTaintsPolicy (Honor: NoSchedule/NoExecute tolerated;
    Ignore default). XLA CSE dedupes these against the filter pipeline's
    identical masks inside one jit program."""
    from kubernetes_tpu.ops.filters import (node_affinity_mask,
                                            taint_toleration_mask,
                                            tenant_pair_mask)
    na = node_affinity_mask(ct, pb)                           # [P,N]
    tt = taint_toleration_mask(ct, pb)                        # [P,N]
    ok = (~pb.sc_honor_affinity[..., None] | na[:, None, :])
    ok &= (~pb.sc_honor_taints[..., None] | tt[:, None, :])
    # fleet isolation: a sibling tenant's nodes neither count toward skew
    # nor anchor the global minimum / minDomains — each tenant's spread
    # math is exactly its standalone cluster's
    tmask = tenant_pair_mask(ct, pb)
    if tmask is not None:
        ok &= tmask[:, None, :]
    return ok & ct.node_valid[None, None, :]


def _spread_skew(ct: ClusterTensors, pb: PodBatch, topo_keys: tuple[int, ...]):
    """-> (skew [P,S,N] f32, has_key [P,S,N] bool): per constraint and node,
    count(node's domain) + self - min(eligible domain counts), exactly what
    filtering.go compares with maxSkew."""
    pol = _spread_policy_elig(ct, pb)                         # [P,S,N]
    cnt_pn = _count_pn(ct, pb.sc_sel, pb.pod_ns)              # [P,S,N]
    cnt, has_key, num_dom = _domain_counts(
        ct, cnt_pn, pb.sc_topo, topo_keys, elig=pol, want_domains=True)
    # does the pod match its own constraint selector? (it lands in the domain)
    self_m = eval_selector_set(pb.sc_sel, pb.pod_labels)      # [Pt,P,S] over all pods
    P = pb.pod_valid.shape[0]
    self_match = self_m[jnp.arange(P), jnp.arange(P), :]      # [P,S]
    big = jnp.float32(3.4e38)
    eligible = has_key & pol
    min_cnt = jnp.min(jnp.where(eligible, cnt, big), axis=-1, keepdims=True)
    min_cnt = jnp.where(jnp.any(eligible, axis=-1, keepdims=True), min_cnt, 0.0)
    # minDomains (DoNotSchedule only): fewer eligible domains than required
    # -> global minimum treated as 0
    min_unmet = (pb.sc_min_domains > 0) & \
        (num_dom < pb.sc_min_domains.astype(jnp.float32))     # [P,S]
    min_cnt = jnp.where(min_unmet[..., None], 0.0, min_cnt)
    return cnt + self_match[..., None].astype(jnp.float32) - min_cnt, has_key


def spread_mask_and_room(ct: ClusterTensors, pb: PodBatch,
                         topo_keys: tuple[int, ...] = ()):
    """DoNotSchedule constraints: count(domain) + self - min(domain counts)
    must not exceed maxSkew; nodes lacking the topology key are infeasible.
    -> (mask [P,N] bool, room [P,S,N] f32 | None). ``room`` = maxSkew - skew:
    how many MORE matching pods the node's domain takes before this pod's
    constraint refuses it, with the minimum as it stands now (>= 0 wherever
    the mask admits the node). The gang veto's hard-spread arm spends it
    (models/gang._relational_veto), so one round counts once. None when the
    batch carries no constraint."""
    if pb.sc_valid.shape[1] == 0:
        return jnp.ones(pb.pod_valid.shape + ct.node_valid.shape, bool), None
    skew, has_key = _spread_skew(ct, pb, topo_keys)
    room = pb.sc_maxskew[..., None].astype(jnp.float32) - skew
    ok = has_key & (room >= 0.0)
    active = (pb.sc_valid & pb.sc_hard)[..., None]            # soft/pad -> neutral
    return jnp.all(ok | ~active, axis=1), room                # [P,N], [P,S,N]


def spread_mask(ct: ClusterTensors, pb: PodBatch, topo_keys: tuple[int, ...] = ()):
    """The filter alone (see ``spread_mask_and_room``). -> [P,N] bool."""
    return spread_mask_and_room(ct, pb, topo_keys)[0]


def spread_score_raw(ct: ClusterTensors, pb: PodBatch, topo_keys: tuple[int, ...] = ()):
    """ScheduleAnyway constraints: raw = sum of matching counts in the node's
    domain (fewer is better; reverse-normalized by the caller)."""
    P, N = pb.pod_valid.shape[0], ct.node_valid.shape[0]
    if pb.sc_valid.shape[1] == 0:
        return jnp.zeros((P, N), jnp.float32)
    pol = _spread_policy_elig(ct, pb)
    cnt_pn = _count_pn(ct, pb.sc_sel, pb.pod_ns)
    cnt, has_key, _ = _domain_counts(ct, cnt_pn, pb.sc_topo, topo_keys,
                                     elig=pol)
    active = (pb.sc_valid & ~pb.sc_hard)[..., None]
    return jnp.sum(jnp.where(active & has_key, cnt, 0.0), axis=1)


# ------------------------------------------------------- inter-pod affinity

def interpod_required_mask(ct: ClusterTensors, pb: PodBatch,
                           topo_keys: tuple[int, ...] = ()):
    """Required affinity: every term needs >=1 matching existing pod in the
    node's domain. Required anti-affinity: no matching existing pod in the
    node's domain (nodes lacking the key satisfy anti trivially)."""
    P, N = pb.pod_valid.shape[0], ct.node_valid.shape[0]
    out = jnp.ones((P, N), bool)
    if pb.aff_valid.shape[1] > 0:
        cnt_pn = _count_pn(ct, pb.aff_sel, pb.pod_ns,
                           pb.aff_ns_explicit, pb.aff_ns_mask)
        cnt, has_key, _ = _domain_counts(ct, cnt_pn, pb.aff_topo, topo_keys)
        valid = pb.aff_valid[..., None]                         # [P,T,1]
        # filtering.go satisfyPodAffinity: every term's topology key must
        # exist on the node, unconditionally.
        has_all_keys = jnp.all(has_key | ~valid, axis=1)        # [P,N]
        sat = jnp.all((has_key & (cnt >= 1.0)) | ~valid, axis=1)
        # Bootstrap: only when NO term has a matching pair cluster-wide AND
        # the incoming pod matches ALL its own term selectors INCLUDING their
        # namespace sets (the first pod of a self-affine gang).
        self_m = eval_selector_set(pb.aff_sel, pb.pod_labels)   # [Pt,P,T]
        self_match = self_m[jnp.arange(P), jnp.arange(P), :]    # [P,T]
        self_match &= _self_ns_ok(pb, pb.aff_ns_explicit, pb.aff_ns_mask)
        none_any_all = jnp.all(~jnp.any(cnt >= 1.0, axis=-1) | ~pb.aff_valid, axis=1)
        self_all = jnp.all(self_match | ~pb.aff_valid, axis=1)
        bootstrap = none_any_all & self_all                     # [P]
        out &= has_all_keys & (sat | bootstrap[:, None])
    if pb.anti_valid.shape[1] > 0:
        cnt_pn = _count_pn(ct, pb.anti_sel, pb.pod_ns,
                           pb.anti_ns_explicit, pb.anti_ns_mask)
        cnt, has_key, _ = _domain_counts(ct, cnt_pn, pb.anti_topo, topo_keys)
        viol = has_key & (cnt >= 1.0)
        out &= jnp.all(~viol | ~pb.anti_valid[..., None], axis=1)
    return out


def interpod_symmetry_mask(ct: ClusterTensors, pb: PodBatch,
                           topo_keys: tuple[int, ...] = ()):
    """Existing pods' required anti-affinity vetoes the newcomer: if existing
    pod e has an anti term whose selector matches the incoming pod (and the
    incoming pod's namespace is in the term's set — own ns or explicit) and
    node n shares e's domain for that term's key -> n infeasible
    (interpodaffinity/filtering.go existingPodAntiAffinityMap)."""
    P, N = pb.pod_valid.shape[0], ct.node_valid.shape[0]
    if ct.ea_valid.shape[1] == 0:
        return jnp.ones((P, N), bool)
    # match of each existing anti term against incoming pods: [P,E,ET]
    m = eval_selector_set(ct.ea_sel, pb.pod_labels)           # [P,E,ET]
    own_ok = pb.pod_ns[:, None] == ct.epod_ns[None, :]        # [P,E]
    exp = _gather_ns(ct.ea_ns_mask, pb.pod_ns)                # [E,ET,P]
    exp = jnp.moveaxis(exp, 2, 0)                             # [P,E,ET]
    ns_ok = jnp.where(ct.ea_ns_explicit[None], exp, own_ok[:, :, None])
    m = m & ns_ok & ct.epod_valid[None, :, None] & ct.ea_valid[None]
    veto = jnp.zeros((P, N), bool)
    K = ct.node_labels.shape[1]
    V = ct.label_value_num.shape[0]
    for k in topo_keys:
        if k < 0 or k >= K:
            continue
        dv = ct.node_labels[:, k]                             # [N]
        dv_e = dv[jnp.clip(ct.epod_node, 0, max(N - 1, 0))]
        dv_e = jnp.where(ct.epod_node >= 0, dv_e, -1)         # [E]
        wm = jnp.any(m & (ct.ea_topo == k)[None], axis=-1)    # [P,E]
        # veto per VALUE then gather per node: no [E,N] materialization
        dve_safe = jnp.clip(dv_e, 0, max(V - 1, 0))
        src = (wm & (dv_e >= 0)[None, :]).astype(jnp.float32)
        vv = jnp.zeros((P, V), jnp.float32) \
            .at[:, dve_safe].add(src)                         # [P,V]
        dv_safe = jnp.clip(dv, 0, max(V - 1, 0))
        veto |= (vv[:, dv_safe] > 0.0) & (dv >= 0)[None, :]
    return ~veto


def interpod_score_raw(ct: ClusterTensors, pb: PodBatch,
                       topo_keys: tuple[int, ...] = ()):
    """Preferred (anti)affinity of the incoming pod: +/-weight per matching
    existing pod in the node's domain. -> raw [P,N] (min-max normalized later)."""
    P, N = pb.pod_valid.shape[0], ct.node_valid.shape[0]
    if pb.paff_valid.shape[1] == 0:
        return jnp.zeros((P, N), jnp.float32)
    cnt_pn = _count_pn(ct, pb.paff_sel, pb.pod_ns,
                       pb.paff_ns_explicit, pb.paff_ns_mask)
    cnt, has_key, _ = _domain_counts(ct, cnt_pn, pb.paff_topo, topo_keys)  # [P,C,N]
    w = jnp.where(pb.paff_valid, pb.paff_weight, 0.0)[..., None]
    return jnp.sum(jnp.where(has_key, cnt, 0.0) * w, axis=1)
