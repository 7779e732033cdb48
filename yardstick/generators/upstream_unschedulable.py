"""Upstream scheduler_perf Unschedulable
(test/integration/scheduler_perf/config/performance-config.yaml, templates
pod-large-cpu.yaml and pod-default.yaml): nodes are upstream's default node
(4 CPU, 32 Gi, 110 pods); the pending pods ask for 9 CPU and fit on none of
them, so they are created and not awaited (``skipWaitToCompletion``) and
stay in the scheduler's unschedulable pool; the measured, initial and
warm-up pods are pod-default.yaml (100m, 500Mi). Nothing is random:
``seed`` is accepted and unused, as the upstream workload has no random
part."""

from ._objects import node, pod

CONSTRAINTS = ("capacity",)

NODE = {"cpu": "4", "memory": "32Gi", "pods": "110"}
DEFAULT = {"cpu": "100m", "memory": "500Mi"}
LARGE_CPU = {"cpu": "9", "memory": "500Mi"}


def generate_phases(seed: int, nodes: int, counts: dict) -> tuple[list, dict]:
    ns = [node(f"node-{i}", NODE) for i in range(nodes)]
    return ns, {phase: [pod(f"large-cpu-pod-{i}", LARGE_CPU)
                        if phase == "pending" else
                        pod(f"{phase}-pod-{i}", DEFAULT) for i in range(n)]
                for phase, n in counts.items()}
