"""BASELINE config 5, MixedHeterogeneous: heterogeneous pods (soft zone
spread, nodeSelector, tolerations, preferred affinity) on heterogeneous
nodes, one in twenty tainted."""

import random

from ._objects import ZONE, ZONES, node, pod, selector, spread

CONSTRAINTS = ("capacity", "taints", "nodeselector")


def generate(seed: int, nodes: int, pods: int) -> tuple[list, list]:
    rng = random.Random(seed)
    ns = []
    for i in range(nodes):
        capacity = {"cpu": rng.choice(["16", "32", "64"]),
                    "memory": rng.choice(["64Gi", "128Gi"]), "pods": "110"}
        labels = {ZONE: ZONES[i % len(ZONES)],
                  "disk": rng.choice(["ssd", "hdd"])}
        taints = ([{"key": "dedicated", "value": "infra",
                    "effect": "NoSchedule"}] if i % 20 == 0 else None)
        ns.append(node(f"node-{i}", capacity, labels, taints))
    ps = []
    for i in range(pods):
        app = {"app": f"svc-{i % 100}"}
        requests = {"cpu": rng.choice(["100m", "250m", "500m", "1"]),
                    "memory": rng.choice(["128Mi", "512Mi", "1Gi"])}
        r = rng.random()
        spec: dict = {}
        if r < 0.2:
            spec["topologySpreadConstraints"] = [
                spread(2, ZONE, "ScheduleAnyway", app)]
        elif r < 0.3:
            spec["nodeSelector"] = {"disk": "ssd"}
        elif r < 0.35:
            spec["tolerations"] = [{"key": "dedicated", "operator": "Equal",
                                    "value": "infra",
                                    "effect": "NoSchedule"}]
        elif r < 0.4:
            spec["affinity"] = {"podAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 50, "podAffinityTerm": {
                        "topologyKey": ZONE,
                        "labelSelector": selector(app)}}]}}
        ps.append(pod(f"pod-{i}", requests, app, **spec))
    return ns, ps
