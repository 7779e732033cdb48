"""Workload generators for the five BASELINE.json benchmark configs.

Mirrors the shape of scheduler_perf's YAML-driven workloads
(test/integration/scheduler_perf/config/performance-config.yaml):
createNodes -> createPods with templated specs. Deterministic via seed:
every generator derives ALL randomness from its own ``random.Random(seed)``
(never the module-level RNG), so the same (params, seed) yields the same
objects — pinned by the same-seed-twice test, and relied on by the
scenario engine, which reuses these shapes as trace template pools.
"""

from __future__ import annotations

import random

from kubernetes_tpu.testing.wrappers import make_node, make_pod

ZONES = [f"zone-{i}" for i in range(10)]


def nodes_basic(n: int, cpu: str = "32", mem: str = "128Gi", pods: str = "110"):
    out = []
    for i in range(n):
        out.append(make_node(f"node-{i}")
                   .capacity({"cpu": cpu, "memory": mem, "pods": pods})
                   .label("topology.kubernetes.io/zone", ZONES[i % len(ZONES)])
                   .obj())
    return out


def scheduling_basic(pods: int = 100, nodes: int = 100, seed: int = 0):
    """SchedulingBasic: uniform pods onto uniform nodes."""
    rng = random.Random(seed)
    ns = nodes_basic(nodes)
    ps = [make_pod(f"pod-{i}")
          .req({"cpu": rng.choice(["100m", "250m", "500m"]),
                "memory": rng.choice(["128Mi", "256Mi", "512Mi"])}).obj()
          for i in range(pods)]
    return ns, ps


def noderesources_fit(pods: int = 5000, nodes: int = 1000, seed: int = 0):
    """Config 2: cpu+mem requests onto heterogeneous nodes (pure Fit/score)."""
    rng = random.Random(seed)
    ns = []
    for i in range(nodes):
        cpu = rng.choice(["8", "16", "32", "64"])
        mem = rng.choice(["32Gi", "64Gi", "128Gi"])
        ns.append(make_node(f"node-{i}").capacity(
            {"cpu": cpu, "memory": mem, "pods": "110"}).obj())
    ps = [make_pod(f"pod-{i}")
          .req({"cpu": rng.choice(["250m", "500m", "1", "2"]),
                "memory": rng.choice(["256Mi", "1Gi", "4Gi"])}).obj()
          for i in range(pods)]
    return ns, ps


def pod_anti_affinity(pods: int = 1000, nodes: int = 500, seed: int = 0):
    """SchedulingPodAntiAffinity: required hostname anti-affinity per group —
    the textbook serial-scheduler killer."""
    rng = random.Random(seed)
    ns = nodes_basic(nodes)
    groups = max(pods // (nodes // 2), 2)
    ps = []
    for i in range(pods):
        g = f"g{i % groups}"
        ps.append(make_pod(f"pod-{i}").label("group", g)
                  .req({"cpu": "100m", "memory": "128Mi"})
                  .pod_anti_affinity("kubernetes.io/hostname", {"group": g}).obj())
    return ns, ps


def preferred_topology_spreading(pods: int = 5000, nodes: int = 5000, seed: int = 0):
    """PreferredTopologySpreading: soft zone spread + hard hostname spread."""
    rng = random.Random(seed)
    ns = nodes_basic(nodes)
    ps = []
    for i in range(pods):
        ps.append(make_pod(f"pod-{i}").label("app", f"svc-{i % 50}")
                  .req({"cpu": "100m", "memory": "128Mi"})
                  .spread(1, "topology.kubernetes.io/zone", "ScheduleAnyway",
                          {"app": f"svc-{i % 50}"}).obj())
    return ns, ps


def mixed_heterogeneous(pods: int = 10000, nodes: int = 5000, seed: int = 0):
    """Config 5: 10k heterogeneous pods (affinity+spread+taints) on 5k nodes."""
    rng = random.Random(seed)
    ns = []
    for i in range(nodes):
        w = (make_node(f"node-{i}")
             .capacity({"cpu": rng.choice(["16", "32", "64"]),
                        "memory": rng.choice(["64Gi", "128Gi"]), "pods": "110"})
             .label("topology.kubernetes.io/zone", ZONES[i % len(ZONES)])
             .label("disk", rng.choice(["ssd", "hdd"])))
        if i % 20 == 0:
            w.taint("dedicated", "infra", "NoSchedule")
        ns.append(w.obj())
    ps = []
    for i in range(pods):
        w = (make_pod(f"pod-{i}").label("app", f"svc-{i % 100}")
             .req({"cpu": rng.choice(["100m", "250m", "500m", "1"]),
                   "memory": rng.choice(["128Mi", "512Mi", "1Gi"])}))
        r = rng.random()
        if r < 0.2:
            w.spread(2, "topology.kubernetes.io/zone", "ScheduleAnyway",
                     {"app": f"svc-{i % 100}"})
        elif r < 0.3:
            w.node_selector({"disk": "ssd"})
        elif r < 0.35:
            w.toleration(key="dedicated", operator="Equal", value="infra",
                         effect="NoSchedule")
        elif r < 0.4:
            w.preferred_pod_affinity(50, "topology.kubernetes.io/zone",
                                     {"app": f"svc-{i % 100}"})
        ps.append(w.obj())
    return ns, ps


def huge_cluster(pods: int = 4096, nodes: int = 16384, seed: int = 0):
    """Past 8192 nodes, where an [N,N] same-domain matrix stops fitting:
    ops/topology.py's O(N+V) scatter-per-value domain counting at the
    50k-node scaling design point. Hard AND soft spread constraints so
    both the filter and the scoring use of it execute."""
    rng = random.Random(seed)
    ns = []
    for i in range(nodes):
        ns.append(
            make_node(f"hn{i}")
            .capacity({"cpu": "16", "memory": "64Gi", "pods": "110"})
            .label("topology.kubernetes.io/zone", f"zone-{i % 64}")
            .obj())
    ps = []
    for i in range(pods):
        w = (make_pod(f"hp{i}").req({"cpu": "500m", "memory": "1Gi"})
             .label("app", f"s{i % 32}"))
        if rng.random() < 0.5:
            w.spread(1, "topology.kubernetes.io/zone", "DoNotSchedule",
                     {"app": f"s{i % 32}"})
        else:
            w.spread(2, "topology.kubernetes.io/zone", "ScheduleAnyway",
                     {"app": f"s{i % 32}"})
        ps.append(w.obj())
    return ns, ps


WORKLOADS = {
    "SchedulingBasic": scheduling_basic,
    "NodeResourcesFit": noderesources_fit,
    "SchedulingPodAntiAffinity": pod_anti_affinity,
    "PreferredTopologySpreading": preferred_topology_spreading,
    "MixedHeterogeneous": mixed_heterogeneous,
    "HugeCluster": huge_cluster,
}
