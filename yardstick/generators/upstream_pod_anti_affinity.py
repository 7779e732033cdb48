"""Upstream scheduler_perf SchedulingPodAntiAffinity
(test/integration/scheduler_perf/config/performance-config.yaml, template
pod-with-pod-anti-affinity.yaml): every pod is ``color: green`` and repels
``color: green`` on kubernetes.io/hostname over namespaces sched-0 and
sched-1, so a node takes one pod. Nodes are upstream's default node (4 CPU,
32 Gi, 110 pods) with unique hostnames. Nothing is random: ``seed`` is
accepted and unused, as the upstream workload has no random part."""

from ._objects import HOSTNAME, node, pod, selector

CONSTRAINTS = ("capacity", "antiaffinity")

GREEN = {"color": "green"}


def _repel_green() -> dict:
    return {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            {"topologyKey": HOSTNAME, "labelSelector": selector(GREEN),
             "namespaces": ["sched-1", "sched-0"]}]}}


def generate(seed: int, nodes: int, pods: int) -> tuple[list, list]:
    ns = [node(f"node-{i}", {"cpu": "4", "memory": "32Gi", "pods": "110"})
          for i in range(nodes)]
    ps = [pod(f"pod-{i}", {"cpu": "100m", "memory": "500Mi"},
              {**GREEN, "name": "test"}, affinity=_repel_green())
          for i in range(pods)]
    return ns, ps
