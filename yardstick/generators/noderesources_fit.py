"""BASELINE config 2, NodeResourcesFit: cpu and memory requests onto
heterogeneous nodes (pure Fit and score)."""

import random

from ._objects import node, pod

CONSTRAINTS = ("capacity",)


def generate(seed: int, nodes: int, pods: int) -> tuple[list, list]:
    rng = random.Random(seed)
    ns = []
    for i in range(nodes):
        cpu = rng.choice(["8", "16", "32", "64"])
        mem = rng.choice(["32Gi", "64Gi", "128Gi"])
        ns.append(node(f"node-{i}", {"cpu": cpu, "memory": mem,
                                     "pods": "110"}))
    return ns, [
        pod(f"pod-{i}", {"cpu": rng.choice(["250m", "500m", "1", "2"]),
                         "memory": rng.choice(["256Mi", "1Gi", "4Gi"])})
        for i in range(pods)]
