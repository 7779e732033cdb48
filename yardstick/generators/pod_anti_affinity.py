"""BASELINE config 3, SchedulingPodAntiAffinity as benchmarks/workloads.py
has it: required hostname anti-affinity inside label groups."""

from ._objects import HOSTNAME, pod, selector, uniform_nodes

CONSTRAINTS = ("capacity", "antiaffinity")


def generate(seed: int, nodes: int, pods: int) -> tuple[list, list]:
    groups = max(pods // (nodes // 2), 2)
    ps = []
    for i in range(pods):
        g = {"group": f"g{i % groups}"}
        ps.append(pod(
            f"pod-{i}", {"cpu": "100m", "memory": "128Mi"}, g,
            affinity={"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"topologyKey": HOSTNAME,
                     "labelSelector": selector(g)}]}}))
    return uniform_nodes(nodes), ps
