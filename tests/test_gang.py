"""Gang batcher: serial-mode parity vs oracle; rounds-mode validity + speed.

Serial mode must reproduce the oracle's ScheduleOne loop exactly (same
assignments). Rounds mode must produce a *sequentially valid* assignment
(capacity + relational constraints hold) in far fewer rounds than pods."""

import random

import numpy as np
import pytest

from kubernetes_tpu.encode.snapshot import SnapshotEncoder
from kubernetes_tpu.models.gang import gang_schedule
from kubernetes_tpu.sched.oracle import OracleScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod

from test_filters_parity import random_node, random_pod


def encode(nodes, pods, bound=None):
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, bound or [], pending_pods=pods)
    pb = enc.encode_pods(pods, meta)
    return ct, pb, meta


def names_of(assignment, nodes, pods):
    return {p.key: (nodes[a].metadata.name if a >= 0 else None)
            for p, a in zip(pods, assignment[:len(pods)])}


def check_validity(nodes, bound, pods, assignment):
    """Final-state validity: each assigned pod, removed from the final state,
    must still find its node feasible."""
    placed = []
    for p, a in zip(pods, assignment[:len(pods)]):
        if a >= 0:
            import copy
            q = copy.deepcopy(p)
            q.spec.node_name = nodes[a].metadata.name
            placed.append((q, int(a)))
    for i, (q, a) in enumerate(placed):
        others = [x for j, (x, _) in enumerate(placed) if j != i]
        orc = OracleScheduler(nodes, (bound or []) + others)
        mask, reasons = orc.feasible(_unbound(q))
        assert mask[a], (f"{q.key} invalid on {nodes[a].metadata.name}: "
                         f"{reasons.get(nodes[a].metadata.name)}")


def _unbound(pod):
    import copy
    q = copy.deepcopy(pod)
    q.spec.node_name = ""
    return q


def test_serial_matches_oracle_basic():
    nodes = [make_node(f"n{i}").capacity({"cpu": "4", "memory": "8Gi", "pods": "10"}).obj()
             for i in range(6)]
    pods = [make_pod(f"p{i}").req({"cpu": "500m", "memory": "512Mi"}).obj()
            for i in range(10)]
    ct, pb, meta = encode(nodes, pods)
    assignment, rounds = gang_schedule(ct, pb, topo_keys=meta.topo_keys, serial=True)
    oracle = OracleScheduler(nodes, []).schedule_all([_unbound(p) for p in pods])
    assert [int(a) for a in assignment[:len(pods)]] == [o if o is not None else -1
                                                        for o in oracle]


def test_serial_capacity_chain():
    # 3 pods want the same tiny node; serial order decides who wins
    nodes = [make_node("small").capacity({"cpu": "2"}).obj(),
             make_node("big").capacity({"cpu": "16"}).obj()]
    pods = [make_pod(f"p{i}").req({"cpu": "1500m"}).obj() for i in range(3)]
    ct, pb, meta = encode(nodes, pods)
    assignment, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys, serial=True)
    oracle = OracleScheduler(nodes, []).schedule_all([_unbound(p) for p in pods])
    assert [int(a) for a in assignment[:3]] == oracle


def test_rounds_capacity_exact_fill():
    nodes = [make_node(f"n{i}").capacity({"cpu": "2", "pods": "100"}).obj() for i in range(4)]
    pods = [make_pod(f"p{i}").req({"cpu": "1"}).obj() for i in range(12)]
    ct, pb, meta = encode(nodes, pods)
    assignment, rounds = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    a = assignment[:12]
    assert (a >= 0).sum() == 8  # 4 nodes x 2 cpu
    counts = np.bincount(a[a >= 0], minlength=4)
    assert (counts <= 2).all()
    check_validity(nodes, [], pods, assignment)


def test_rounds_anti_affinity_spreads_fast():
    nodes = [make_node(f"n{i}").capacity({"cpu": "8", "pods": "50"}).obj() for i in range(8)]
    pods = [make_pod(f"p{i}").label("app", "web")
            .pod_anti_affinity("kubernetes.io/hostname", {"app": "web"}).obj()
            for i in range(6)]
    ct, pb, meta = encode(nodes, pods)
    assignment, rounds = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    a = assignment[:6]
    assert (a >= 0).sum() == 6
    assert len(set(a.tolist())) == 6, "anti-affinity pods must land on distinct hosts"
    assert rounds <= 4, f"expected near-parallel acceptance, took {rounds} rounds"
    check_validity(nodes, [], pods, assignment)


def test_rounds_anti_affinity_exhausts_hosts():
    nodes = [make_node(f"n{i}").capacity({"cpu": "8", "pods": "50"}).obj() for i in range(3)]
    pods = [make_pod(f"p{i}").label("app", "web")
            .pod_anti_affinity("kubernetes.io/hostname", {"app": "web"}).obj()
            for i in range(5)]
    ct, pb, meta = encode(nodes, pods)
    assignment, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    a = assignment[:5]
    assert (a >= 0).sum() == 3  # only 3 hosts available
    assert len(set(a[a >= 0].tolist())) == 3
    check_validity(nodes, [], pods, assignment)


def test_rounds_required_affinity_colocates():
    nodes = [make_node(f"n{i}").capacity({"cpu": "8", "pods": "50"})
             .label("zone", f"z{i % 2}").obj() for i in range(4)]
    pods = [make_pod(f"p{i}").label("app", "db")
            .pod_affinity("zone", {"app": "db"}).obj() for i in range(4)]
    ct, pb, meta = encode(nodes, pods)
    assignment, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    a = assignment[:4]
    assert (a >= 0).all()
    zones = {nodes[i].metadata.labels["zone"] for i in a}
    assert len(zones) == 1, f"affine gang split across zones {zones}"
    check_validity(nodes, [], pods, assignment)


@pytest.mark.parametrize("max_skew", [1, 5])
def test_rounds_hard_spread(max_skew):
    nodes = [make_node(f"n{i}").capacity({"cpu": "8", "pods": "50"})
             .label("zone", f"z{i % 3}").obj() for i in range(6)]

    def spread_pods(skew):
        return [make_pod(f"p{i}").label("app", "api")
                .spread(skew, "zone", "DoNotSchedule", {"app": "api"}).obj()
                for i in range(9)]

    pods = spread_pods(max_skew)
    ct, pb, meta = encode(nodes, pods)
    assignment, rounds = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    a = assignment[:9]
    assert (a >= 0).all()
    zone_counts = {}
    for i in a:
        z = nodes[i].metadata.labels["zone"]
        zone_counts[z] = zone_counts.get(z, 0) + 1
    assert max(zone_counts.values()) - min(zone_counts.values()) <= max_skew, zone_counts
    check_validity(nodes, [], pods, assignment)
    if max_skew > 1:
        # the veto's hard-spread arm commits what maxSkew leaves room for:
        # the same pods at maxSkew 1 go one a zone a round
        ct1, pb1, meta1 = encode(nodes, spread_pods(1))
        _, rounds_1 = gang_schedule(ct1, pb1, topo_keys=meta1.topo_keys)
        assert rounds < rounds_1, (rounds, rounds_1)


def test_priority_order_respected_under_scarcity():
    nodes = [make_node("only").capacity({"cpu": "2"}).obj()]
    pods = [make_pod("low").req({"cpu": "1500m"}).priority(1).obj(),
            make_pod("high").req({"cpu": "1500m"}).priority(100).obj()]
    ct, pb, meta = encode(nodes, pods)
    assignment, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    assert assignment[1] == 0 and assignment[0] == -1


def test_serial_does_not_retry_failed_pods():
    """ScheduleOne semantics: a pod that fails is not retried within the batch,
    even if a later commit would have made it feasible."""
    nodes = [make_node("n0").capacity({"cpu": "8"}).label("zone", "z1").obj()]
    A = make_pod("a").pod_affinity("zone", {"app": "web"}).obj()  # needs app=web
    B = make_pod("b").label("app", "web").obj()
    ct, pb, meta = encode(nodes, [A, B])
    a, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys, serial=True)
    oracle = OracleScheduler(nodes, []).schedule_all([_unbound(A), _unbound(B)])
    assert [int(x) for x in a[:2]] == [-1, 0]
    assert oracle == [None, 0]


def test_profile_weights_and_fit_strategy_wiring():
    nodes = [make_node("fuller").capacity({"cpu": "4", "pods": "10"}).obj(),
             make_node("empty").capacity({"cpu": "4", "pods": "10"}).obj()]
    bound = [make_pod("seed").req({"cpu": "2"}).node("fuller").obj()]
    p = make_pod("p").req({"cpu": "1"}).obj()
    ct, pb, meta = encode(nodes, [p], bound)
    a_least, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    a_most, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys,
                              fit_strategy="MostAllocated")
    assert int(a_least[0]) == 1 and int(a_most[0]) == 0


@pytest.mark.parametrize("seed", range(3))
def test_fuzz_serial_parity(seed):
    rng = random.Random(2000 + seed)
    nodes = [random_node(rng, i) for i in range(rng.randint(2, 8))]
    names = [n.metadata.name for n in nodes]
    bound = []
    for i in range(rng.randint(0, 4)):
        p = random_pod(rng, 100 + i, names)
        p.spec.node_name = rng.choice(names)
        bound.append(p)
    pods = [random_pod(rng, i, names) for i in range(rng.randint(2, 8))]
    for p in pods:
        p.spec.priority = 0  # equal priority -> list order == rank order
        p.spec.node_name = ""
    ct, pb, meta = encode(nodes, pods, bound)
    assignment, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys, serial=True)
    oracle = OracleScheduler(nodes, bound).schedule_all([_unbound(p) for p in pods])
    assert [int(a) for a in assignment[:len(pods)]] == [o if o is not None else -1
                                                        for o in oracle], \
        f"seed={seed}"


@pytest.mark.parametrize("seed", range(3))
def test_fuzz_rounds_validity(seed):
    rng = random.Random(3000 + seed)
    nodes = [random_node(rng, i) for i in range(rng.randint(3, 8))]
    pods = []
    for i in range(rng.randint(3, 10)):
        w = make_pod(f"p{i}").req({"cpu": rng.choice(["250m", "1"])}).label("app", rng.choice("ab"))
        if rng.random() < 0.4:
            w.pod_anti_affinity("kubernetes.io/hostname", {"app": rng.choice("ab")})
        if rng.random() < 0.3:
            w.spread(1, "zone", "DoNotSchedule", {"app": rng.choice("ab")})
        pods.append(w.obj())
    ct, pb, meta = encode(nodes, pods)
    assignment, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    check_validity(nodes, [], pods, assignment)
