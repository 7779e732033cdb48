"""gang_drain: the whole queue as one device program (models/gang.py).

The reference's sequential loop gives later pods visibility of earlier
placements for free; the drain must reproduce that across batch boundaries —
capacity (requested carries) AND relational state (committed pods become
valid epods for later batches' spread/affinity/anti-affinity terms).
"""

import numpy as np
import pytest

from kubernetes_tpu.encode.snapshot import SnapshotEncoder
from kubernetes_tpu.models.gang import gang_drain, gang_schedule
from kubernetes_tpu.sched.oracle import OracleScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _encode(nodes, pods_all, batch):
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, [], pending_pods=pods_all)
    batches = [pods_all[i:i + batch] for i in range(0, len(pods_all), batch)]
    pbs = [enc.encode_pods(b, meta) for b in batches]
    return ct, pbs, batches, meta


def _zone_nodes(n, per_zone=3, cpu="4"):
    return [make_node(f"n{i}")
            .capacity({"cpu": cpu, "memory": "8Gi", "pods": "20"})
            .label("kubernetes.io/hostname", f"n{i}")
            .label("topology.kubernetes.io/zone", f"z{i // per_zone}")
            .obj() for i in range(n)]


def test_single_batch_matches_gang_schedule():
    nodes = _zone_nodes(8)
    pods = [make_pod(f"p{i}").req({"cpu": "500m"}).obj() for i in range(6)]
    ct, pbs, batches, meta = _encode(nodes, pods, batch=8)
    want, _ = gang_schedule(ct, pbs[0], topo_keys=meta.topo_keys)
    got, rounds, _ = gang_drain(ct, pbs, topo_keys=meta.topo_keys)
    np.testing.assert_array_equal(want, got[0])


def test_cross_batch_anti_affinity():
    """8 pods with required hostname anti-affinity in 2 batches of 4 must land
    on 8 distinct nodes — batch 2 must see batch 1's placements."""
    nodes = _zone_nodes(8)
    pods = [make_pod(f"p{i}").label("grp", "g").req({"cpu": "500m"})
            .pod_anti_affinity("kubernetes.io/hostname", {"grp": "g"}).obj()
            for i in range(8)]
    ct, pbs, batches, meta = _encode(nodes, pods, batch=4)
    a, rounds, _ = gang_drain(ct, pbs, topo_keys=meta.topo_keys)
    placed = [int(a[b][i]) for b in range(len(batches))
              for i in range(len(batches[b]))]
    assert all(x >= 0 for x in placed)
    assert len(set(placed)) == 8, f"cross-batch anti-affinity violated: {placed}"


def test_cross_batch_capacity_carry():
    """2-cpu nodes, 1-cpu pods: at most 2 per node even across batches."""
    nodes = _zone_nodes(4, cpu="2")
    pods = [make_pod(f"p{i}").req({"cpu": "1"}).obj() for i in range(8)]
    ct, pbs, batches, meta = _encode(nodes, pods, batch=3)
    a, _, requested = gang_drain(ct, pbs, topo_keys=meta.topo_keys)
    placed = [int(a[b][i]) for b in range(len(batches))
              for i in range(len(batches[b]))]
    assert all(x >= 0 for x in placed)
    counts = np.bincount(placed, minlength=4)
    assert counts.max() <= 2, counts


def _hard_spread_drain(nodes, max_skew, n=8, batch=4):
    pods = [make_pod(f"p{i}").label("app", "a").req({"cpu": "250m"})
            .spread(max_skew, "topology.kubernetes.io/zone", "DoNotSchedule",
                    {"app": "a"}).obj() for i in range(n)]
    ct, pbs, batches, meta = _encode(nodes, pods, batch=batch)
    a, rounds, _ = gang_drain(ct, pbs, topo_keys=meta.topo_keys)
    placed = [int(a[b][i]) for b in range(len(batches))
              for i in range(len(batches[b]))]
    return placed, int(np.sum(rounds))


@pytest.mark.parametrize("max_skew", [1, 5])
def test_cross_batch_hard_spread(max_skew):
    """Hard zone spread over 4 zones, 2 batches of 4. At maxSkew 1 every
    zone must end with exactly 2 — requires batch 2 to count batch 1's pods.
    At maxSkew 5 the zones stay within 5 of each other and the same pods
    take fewer rounds: a round commits what maxSkew leaves room for."""
    nodes = _zone_nodes(8, per_zone=2)
    placed, rounds = _hard_spread_drain(nodes, max_skew)
    assert all(x >= 0 for x in placed)
    zones = [placed[i] // 2 for i in range(8)]
    counts = np.bincount(zones, minlength=4)
    assert counts.max() - counts.min() <= max_skew, counts
    if max_skew > 1:
        _, rounds_1 = _hard_spread_drain(nodes, 1)
        assert rounds < rounds_1, (rounds, rounds_1)


def test_drain_validity_vs_oracle():
    """Every drain placement, checked one pod at a time against the oracle
    with all other placed pods bound, must be feasible."""
    import copy
    import random
    rng = random.Random(7)
    nodes = _zone_nodes(9)
    pods = []
    for i in range(18):
        b = make_pod(f"p{i}").req({"cpu": f"{rng.choice([250, 500, 750])}m"})
        b = b.label("app", f"g{i % 3}")
        if i % 4 == 0:
            b = b.spread(2, "topology.kubernetes.io/zone", "DoNotSchedule",
                         {"app": f"g{i % 3}"})
        if i % 5 == 0:
            b = b.pod_anti_affinity("kubernetes.io/hostname",
                                    {"app": f"g{i % 3}"})
        pods.append(b.obj())
    ct, pbs, batches, meta = _encode(nodes, pods, batch=5)
    a, _, _ = gang_drain(ct, pbs, topo_keys=meta.topo_keys)
    placed = []
    flat = [(p, int(a[b][i])) for b, chunk in enumerate(batches)
            for i, p in enumerate(chunk)]
    for p, ni in flat:
        if ni >= 0:
            q = copy.deepcopy(p)
            q.spec.node_name = nodes[ni].metadata.name
            placed.append((q, ni))
    for i, (q, ni) in enumerate(placed):
        others = [x for j, (x, _) in enumerate(placed) if j != i]
        orc = OracleScheduler(nodes, others)
        unbound = copy.deepcopy(q)
        unbound.spec.node_name = ""
        mask, reasons = orc.feasible(unbound)
        assert mask[ni], (f"{q.key} invalid on node {ni}: "
                          f"{reasons.get(nodes[ni].metadata.name)}")
