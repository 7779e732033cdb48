"""The gang veto's hard-spread arm is a quota a domain a round (models/gang.py
``_relational_veto``): of the pods one DoNotSchedule selector matches, a round
commits into a domain what ``maxSkew`` leaves room for against the minimum at
the round's opening.

The invariant is the module's own — committed state is always sequentially
valid. Held here by replay: drive ``gang_round`` one round at a time and put
each round's commits, IN RANK ORDER (priority descending, then batch index),
through the numpy oracle's filter against the state before the round plus the
commits before them. None may be refused."""

import copy
import functools
import random

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.encode.snapshot import SnapshotEncoder
from kubernetes_tpu.models.gang import GangState, extend_cluster, gang_round
from kubernetes_tpu.sched.oracle import OracleScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod

from test_gang import _unbound

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"


def _nodes(zones, per_zone):
    """``per_zone`` nodes in each of ``zones`` zones; the even zones are
    pool ``a``, the odd ones pool ``b``."""
    return [make_node(f"n{z}-{i}")
            .capacity({"cpu": "64", "memory": "256Gi", "pods": "110"})
            .label(HOST, f"n{z}-{i}").label(ZONE, f"z{z}")
            .label("pool", "ab"[z % 2]).obj()
            for z in range(zones) for i in range(per_zone)]


def _spread_pod(name, max_skew, labels=None, selector=None, **kw):
    labels = labels or {"app": "api"}
    return (make_pod(name).labels(labels).req({"cpu": "100m"})
            .spread(max_skew, ZONE, "DoNotSchedule",
                    selector or {"app": "api"}, **kw))


def _bound(name, node, labels, ns="default"):
    return (make_pod(name).namespace(ns).labels(labels).req({"cpu": "100m"})
            .node(node.metadata.name).obj())


def run_rounds_replayed(nodes, bound, pods, max_rounds=64, seed=0):
    """Drive ``gang_round`` as ``_converge`` does (cap doubling each live
    round), replaying every round's commits through the oracle in rank order.
    -> (assignment [len(pods)], commits a round as lists of pod indices, in
    rank order)."""
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, bound, pending_pods=pods)
    pb = enc.encode_pods(pods, meta)
    ct_ext = extend_cluster(ct, pb)
    P = int(pb.pod_valid.shape[0])
    state = GangState(requested=jnp.asarray(ct.requested),
                      committed=jnp.zeros(P, bool),
                      assignment=jnp.full(P, -1, jnp.int32),
                      tried=jnp.zeros(P, bool),
                      rounds=jnp.zeros((), jnp.int32))
    orc = OracleScheduler(nodes, [copy.deepcopy(b) for b in bound])
    per_round = []
    for i in range(max_rounds):
        was = np.asarray(state.committed)
        state, progress = gang_round(ct_ext, pb, state, seed=seed,
                                     topo_keys=meta.topo_keys,
                                     cap_scale=1 << min(i, 20))
        if int(progress) == 0:
            break
        now, assignment = np.asarray(state.committed), np.asarray(state.assignment)
        new = [j for j in np.flatnonzero(now & ~was) if j < len(pods)]
        new.sort(key=lambda j: (-pods[j].spec.priority, j))     # rank order
        for j in new:
            q = _unbound(pods[j])
            ni = int(assignment[j])
            assert orc.feasible_one(q, ni), (
                f"round {i}: {q.key} on {nodes[ni].metadata.name} is refused "
                f"by the oracle after this round's earlier commits "
                f"{[pods[x].key for x in new[:new.index(j)]]}")
            orc.assume(q, ni)
        per_round.append(new)
    return np.asarray(state.assignment)[:len(pods)], per_round


def _zone_of(nodes, ni):
    return nodes[ni].metadata.labels[ZONE]


def _zone_counts(nodes, assignment, idxs, zones):
    counts = {f"z{z}": 0 for z in range(zones)}
    for j in idxs:
        counts[_zone_of(nodes, int(assignment[j]))] += 1
    return counts


# -- the three regimes -------------------------------------------------------

def test_balanced_domains_at_maxskew_5_commit_five_a_domain_in_round_one():
    nodes = _nodes(zones=3, per_zone=4)
    pods = [_spread_pod(f"p{i}", 5).obj() for i in range(60)]
    assignment, per_round = run_rounds_replayed(nodes, [], pods)
    assert (assignment >= 0).all()
    assert _zone_counts(nodes, assignment, per_round[0], 3) == {
        "z0": 5, "z1": 5, "z2": 5}
    # up to 15 a round (a zone no pod proposed into gets none): 60 pods in
    # a handful of placing rounds, not the twenty of one a zone a round
    assert len(per_round) <= 8, [len(r) for r in per_round]


def test_maxskew_1_commits_one_a_domain_a_round():
    """What the pairwise arm did, unchanged: room is 0 over balanced
    domains."""
    nodes = _nodes(zones=3, per_zone=4)
    pods = [_spread_pod(f"p{i}", 1).obj() for i in range(12)]
    assignment, per_round = run_rounds_replayed(nodes, [], pods)
    assert (assignment >= 0).all()
    assert _zone_counts(nodes, assignment, per_round[0], 3) == {
        "z0": 1, "z1": 1, "z2": 1}
    for new in per_round:       # a zone no pod proposed into gets none
        assert max(_zone_counts(nodes, assignment, new, 3).values()) == 1
    assert len(per_round) >= 4


def test_a_domain_maxskew_above_the_minimum_waits_for_the_others():
    """z0 opens 5 above z1 and z2 (maxSkew 5): the filter leaves it no room,
    so nothing lands there until the minimum has moved — and the first round
    may put only what keeps every commit valid against the OPENING minimum:
    five in each of the two low zones."""
    nodes = _nodes(zones=3, per_zone=4)
    z0 = [n for n in nodes if n.metadata.labels[ZONE] == "z0"]
    bound = [_bound(f"b{i}", z0[i % len(z0)], {"app": "api"}) for i in range(5)]
    pods = [_spread_pod(f"p{i}", 5).obj() for i in range(40)]
    assignment, per_round = run_rounds_replayed(nodes, bound, pods)
    assert (assignment >= 0).all()
    assert _zone_counts(nodes, assignment, per_round[0], 3) == {
        "z0": 0, "z1": 5, "z2": 5}
    # z0 takes pods only once z1 and z2 stand level with its 5
    first_z0 = next(i for i, new in enumerate(per_round)
                    if _zone_counts(nodes, assignment, new, 3)["z0"])
    assert first_z0 >= 1
    total = _zone_counts(nodes, assignment, range(len(pods)), 3)
    total["z0"] += 5
    assert max(total.values()) - min(total.values()) <= 5, total


# -- a pod turned away commits nothing, and the count is the filter's -----------

def _pinned(name, labels, host, selector, **kw):
    """A maxSkew 1 pod pinned to ``host`` by a nodeSelector its constraint
    ignores (nodeAffinityPolicy Ignore: every node still counts)."""
    return (make_pod(name).labels(labels).req({"cpu": "100m"})
            .node_selector({HOST: host})
            .spread(1, ZONE, "DoNotSchedule", selector,
                    node_affinity_policy="Ignore", **kw).obj())


def test_a_pod_turned_away_raises_no_minimum():
    """Two zones, maxSkew 1. y (tier) and x (app+tier) spread over ``tier``
    and both choose z0: y is kept, x is turned away. r and q (app) spread
    over ``app`` and both choose z1: r is kept, and q could follow only if
    z0's ``app`` count rose too — x is the one ``app`` pod there, and x
    commits nothing. (Held for the day the minimum rises inside the round,
    ROADMAP S0r: a level fed from the accepted pods, not the kept ones,
    keeps q, 2 above z0.)"""
    nodes = _nodes(zones=2, per_zone=1)
    pods = [_pinned("y", {"tier": "front"}, "n0-0", {"tier": "front"}),
            _pinned("x", {"app": "api", "tier": "front"}, "n0-0",
                    {"tier": "front"}),
            _pinned("r", {"app": "api"}, "n1-0", {"app": "api"}),
            _pinned("q", {"app": "api"}, "n1-0", {"app": "api"})]
    assignment, per_round = run_rounds_replayed(nodes, [], pods)
    assert per_round == [[0, 2]], per_round
    assert list(assignment) == [0, -1, 1, -1]


def test_a_pod_on_a_node_the_constraint_leaves_out_raises_no_minimum():
    """q (pool a, nodeAffinityPolicy Honor) counts pods on pool a's nodes
    alone. z0 has a full pool-a node and a pool-b node, z1 a pool-a node. p
    is kept on z0's pool-b node, r on z1's node; q could follow r only if
    z0's count, as q's filter reads it, rose — and p is not on a node q's
    constraint counts. (Held for ROADMAP S0r like the case above.)"""
    small = {"cpu": "50m", "memory": "256Gi", "pods": "110"}   # fits no pod
    big = {"cpu": "64", "memory": "256Gi", "pods": "110"}
    nodes = [make_node(name).capacity(cap).label(HOST, name)
             .label(ZONE, zone).label("pool", pool).obj()
             for name, cap, zone, pool in [("n0a", small, "z0", "a"),
                                           ("n0b", big, "z0", "b"),
                                           ("n1a", big, "z1", "a")]]
    app = {"app": "api"}
    q = (make_pod("q").labels(app).req({"cpu": "100m"})
         .node_selector({"pool": "a"})
         .spread(1, ZONE, "DoNotSchedule", app).obj())
    pods = [_pinned("p", app, "n0b", app), _pinned("r", app, "n1a", app), q]
    assignment, per_round = run_rounds_replayed(nodes, [], pods)
    assert per_round[0] == [0, 1], per_round
    assert int(assignment[2]) == -1     # 2 above z0's pool-a count of 0


# -- the replay over random batches -------------------------------------------

def _random_case(rng):
    zones = rng.randint(3, 6)
    nodes = _nodes(zones, per_zone=3)
    # two selectors that match each other's pods: a pod may carry both labels
    label_sets = [{"app": "api"}, {"tier": "front"},
                  {"app": "api", "tier": "front"}]
    selectors = [{"app": "api"}, {"tier": "front"}]
    # pre-existing counts, unbalanced: zone z holds up to 2*z matching pods
    bound = []
    for z in range(zones):
        zn = [n for n in nodes if n.metadata.labels[ZONE] == f"z{z}"]
        for i in range(rng.randint(0, 2 * z)):
            bound.append(_bound(f"b{z}-{i}", rng.choice(zn),
                                rng.choice(label_sets),
                                ns=rng.choice(["default", "other"])))
    pods = []
    for i in range(rng.randint(20, 40)):
        w = (make_pod(f"p{i}").namespace(rng.choice(["default", "other"]))
             .labels(rng.choice(label_sets)).req({"cpu": "100m"})
             .priority(rng.choice([0, 0, 0, 10])))
        r = rng.random()
        if r < 0.8:
            w.spread(rng.choice([1, 2, 5]), ZONE, "DoNotSchedule",
                     rng.choice(selectors),
                     min_domains=rng.choice([None, None, 2, zones + 1]))
        if r < 0.15:    # a second hard constraint, on the other selector
            w.spread(rng.choice([2, 5]), ZONE, "DoNotSchedule",
                     rng.choice(selectors))
        if rng.random() < 0.2:
            w.pod_anti_affinity(HOST, rng.choice(selectors))
        if rng.random() < 0.2:
            # nodeAffinityPolicy Honor: only pool a's zones count for this
            # pod's skew and minimum, and only pods on them are counted
            w.node_selector({"pool": "a"})
        pods.append(w.obj())
    return nodes, bound, pods


@functools.lru_cache(maxsize=None)
def _replayed_random_case(seed):
    nodes, bound, pods = _random_case(random.Random(35000 + seed))
    return nodes, pods, run_rounds_replayed(nodes, bound, pods, seed=seed)


@pytest.mark.parametrize("seed", range(8))
def test_every_round_replays_in_rank_order_through_the_oracle(seed):
    _, _, (assignment, per_round) = _replayed_random_case(seed)
    assert per_round, "nothing placed at all"
    assert sum(len(r) for r in per_round) == int((assignment >= 0).sum())


def test_some_random_batch_commits_more_than_one_a_domain_a_round():
    """The replay cases are not all the one-a-domain regime."""
    hit = False
    for seed in range(8):
        nodes, pods, (assignment, per_round) = _replayed_random_case(seed)
        for new in per_round:
            spread = [j for j in new
                      if pods[j].spec.topology_spread_constraints]
            zs = [_zone_of(nodes, int(assignment[j])) for j in spread]
            hit |= len(zs) > len(set(zs))
    assert hit


# -- the number the arm spends -------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
def test_room_is_maxskew_less_the_oracles_skew(seed):
    """``spread_mask_and_room`` hands over ``maxSkew - skew`` a constraint a
    node from the filter's own counts, and ``evaluate`` the row of it at the
    pod's chosen node: held against the oracle's PreFilter numbers."""
    from kubernetes_tpu.models.schedule_step import evaluate
    from kubernetes_tpu.ops.topology import spread_mask, spread_mask_and_room
    nodes, bound, pods = _random_case(random.Random(35100 + seed))
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, bound, pending_pods=pods)
    pb = enc.encode_pods(pods, meta)
    mask, room = spread_mask_and_room(ct, pb, meta.topo_keys)
    mask, room = np.asarray(mask), np.asarray(room)
    np.testing.assert_array_equal(
        mask, np.asarray(spread_mask(ct, pb, meta.topo_keys)))
    orc = OracleScheduler(nodes, bound)
    checked = 0
    for j, pod in enumerate(pods):
        hard = orc._pod_ctx(pod)["spread"]
        for s, (sc, counts, min_count, self_match) in enumerate(hard):
            for ni, node in enumerate(nodes):
                dv = node.metadata.labels[sc.topology_key]
                want = sc.max_skew - (counts.get(dv, 0) + int(self_match)
                                      - min_count)
                assert room[j, s, ni] == want, (pod.key, s, node.metadata.name)
                checked += 1
        ok = all(room[j, s, ni] >= 0 for s in range(len(hard))
                 for ni in range(len(nodes)) if mask[j, ni])
        assert ok, pod.key      # the filter admits no node without room
    assert checked
    res = evaluate(ct, pb, topo_keys=meta.topo_keys)
    choice = np.asarray(res.choice)
    np.testing.assert_array_equal(
        np.asarray(res.spread_room),
        room[np.arange(room.shape[0]), :, choice])
    assert evaluate(ct, pb, topo_keys=meta.topo_keys,
                    enabled_filters=frozenset({"NodeResourcesFit"})
                    ).spread_room is None
