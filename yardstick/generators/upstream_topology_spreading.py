"""Upstream scheduler_perf TopologySpreading
(test/integration/scheduler_perf/config/performance-config.yaml, workload
5000Nodes, template pod-with-topology-spreading.yaml): nodes are upstream's
default node (4 CPU, 32 Gi, 110 pods) labelled topology.kubernetes.io/zone
moon-1, moon-2, moon-3 in turn; the measured pods are ``color: blue`` and
must stay within ``maxSkew: 5`` of each other over the zones
(``DoNotSchedule``, selector ``color: blue``); the initial pods are
upstream's pod-default.yaml, the same requests and no ``color`` label, so
no constraint counts them.

The two kinds of pod differ, so the harness tells this generator the count
of every phase (``generate_phases``): the measured pods carry the
constraint, the initial and warm-up pods nothing. ``build`` takes the counts
themselves. Nothing is random: ``seed`` is accepted and unused, as the
upstream workload has no random part.

``generate`` and ``measured`` (the total only, and a scan of ``configs/`` for
the one file with these sizes) are what the harness called before PR 36. It
no longer does; three cases of tests/test_topologyspread_deployment.py
still do, and a benchmark PR may not edit a file under tests/: they go with
those cases (PERF.md section 7)."""

import json
import os

from ._objects import ZONE, node, pod, spread

CONSTRAINTS = ("capacity", "topologyspread")

BLUE = {"color": "blue"}
MOONS = ("moon-1", "moon-2", "moon-3")
MAX_SKEW = 5
REQUESTS = {"cpu": "100m", "memory": "500Mi"}

_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
_NAME = __name__.rpartition(".")[2]


def build(nodes: int, spreading: int, default: int) -> tuple[list, list]:
    """``spreading`` constrained pods first, then ``default`` plain ones."""
    ns = [node(f"node-{i}", {"cpu": "4", "memory": "32Gi", "pods": "110"},
               {ZONE: MOONS[i % len(MOONS)]}) for i in range(nodes)]
    ps = [pod(f"spreading-pod-{i}", REQUESTS, BLUE,
              topologySpreadConstraints=[
                  spread(MAX_SKEW, ZONE, "DoNotSchedule", BLUE)])
          for i in range(spreading)]
    ps += [pod(f"pod-{i}", REQUESTS) for i in range(default)]
    return ns, ps


def generate_phases(seed: int, nodes: int, counts: dict) -> tuple[list, dict]:
    m, i = counts["measure"], counts["init"]
    ns, ps = build(nodes, m, i + counts["warmup"])
    return ns, {"measure": ps[:m], "init": ps[m:m + i],
                "warmup": ps[m + i:], "pending": []}


def measured(nodes: int, pods: int) -> int:
    """``measurePods`` of the configuration that generates ``pods`` pods
    for ``nodes`` nodes with this generator."""
    found = {}
    for name in sorted(os.listdir(_CONFIGS)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(_CONFIGS, name)) as f:
            c = json.load(f)
        if (c.get("generator") == _NAME and int(c["nodes"]) == nodes
                and sum(int(c[k]) for k in ("measurePods", "initPods",
                                            "warmupPods")) == pods):
            found[name] = int(c["measurePods"])
    if len(set(found.values())) != 1:
        raise ValueError(
            f"{_NAME}: {nodes} nodes and {pods} pods should be one "
            f"configuration's sizes, found {found or 'none'} under configs/")
    return next(iter(found.values()))


def generate(seed: int, nodes: int, pods: int) -> tuple[list, list]:
    spreading = measured(nodes, pods)
    return build(nodes, spreading, pods - spreading)
