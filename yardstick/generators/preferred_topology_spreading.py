"""BASELINE config 4, PreferredTopologySpreading: soft zone spread."""

from ._objects import ZONE, pod, spread, uniform_nodes

CONSTRAINTS = ("capacity",)


def generate(seed: int, nodes: int, pods: int) -> tuple[list, list]:
    ps = []
    for i in range(pods):
        app = {"app": f"svc-{i % 50}"}
        ps.append(pod(f"pod-{i}", {"cpu": "100m", "memory": "128Mi"}, app,
                      topologySpreadConstraints=[
                          spread(1, ZONE, "ScheduleAnyway", app)]))
    return uniform_nodes(nodes), ps
