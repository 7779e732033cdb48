"""BASELINE config 1, SchedulingBasic: uniform pods onto uniform nodes."""

import random

from ._objects import pod, uniform_nodes

CONSTRAINTS = ("capacity",)


def generate(seed: int, nodes: int, pods: int) -> tuple[list, list]:
    rng = random.Random(seed)
    return uniform_nodes(nodes), [
        pod(f"pod-{i}", {"cpu": rng.choice(["100m", "250m", "500m"]),
                         "memory": rng.choice(["128Mi", "256Mi", "512Mi"])})
        for i in range(pods)]
