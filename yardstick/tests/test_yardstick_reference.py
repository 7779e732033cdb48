"""The plain reference refuses each kind of invalid end state, hand-made,
and passes a valid one."""

import pytest

from yardstick import reference
from yardstick.generators._objects import HOSTNAME, node, pod, selector

GREEN = {"color": "green"}


def placed(p, where):
    p["spec"]["nodeName"] = where
    return p


def green(name, ns="sched-1"):
    return pod(name, {"cpu": "100m"}, GREEN, namespace=ns, affinity={
        "podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"topologyKey": HOSTNAME, "labelSelector": selector(GREEN),
                 "namespaces": ["sched-1", "sched-0"]}]}})


def nodes():
    return [node("a", {"cpu": "2", "memory": "4Gi", "pods": "3"},
                 {"disk": "ssd"}),
            node("b", {"cpu": "2", "memory": "4Gi", "pods": "3"},
                 {"disk": "hdd"},
                 taints=[{"key": "dedicated", "value": "infra",
                          "effect": "NoSchedule"}]),
            node("c", {"cpu": "2", "memory": "4Gi", "pods": "3"})]


CASES = {
    "capacity": [placed(pod(f"p{i}", {"cpu": "900m", "memory": "1Gi"}), "a")
                 for i in range(3)],
    "taints": [placed(pod("p", {"cpu": "100m"}), "b")],
    "nodeselector": [placed(pod("p", {"cpu": "100m"},
                                nodeSelector={"disk": "hdd"}), "a")],
    "antiaffinity": [placed(green("g0", "sched-0"), "a"),
                     placed(green("g1"), "a")],
}


@pytest.mark.parametrize("kind", sorted(CASES))
def test_reference_refuses(kind):
    problems = reference.load([kind])[kind](nodes(), CASES[kind])
    assert problems, f"{kind}.py passed a state it must refuse"


def test_reference_refuses_too_many_pods():
    pods = [placed(pod(f"p{i}", {"cpu": "1m"}), "a") for i in range(4)]
    assert any("pods" in p for p in
               reference.load(["capacity"])["capacity"](nodes(), pods))


def test_reference_passes_a_valid_state():
    pods = [placed(pod("p0", {"cpu": "900m", "memory": "1Gi"},
                       nodeSelector={"disk": "ssd"}), "a"),
            placed(pod("p1", {"cpu": "1", "memory": "1Gi"}, tolerations=[
                {"key": "dedicated", "operator": "Equal", "value": "infra",
                 "effect": "NoSchedule"}]), "b"),
            placed(green("g0", "sched-0"), "a"), placed(green("g1"), "c"),
            pod("pending", {"cpu": "64"})]
    for kind, check in reference.load(sorted(CASES)).items():
        assert check(nodes(), pods) == [], kind


def test_a_kind_without_a_file_is_refused():
    with pytest.raises(SystemExit):
        reference.load(["spread"])


def test_reference_and_generators_import_nothing_from_the_program():
    import ast
    import glob
    import os
    here = os.path.dirname(reference.__file__)
    gens = os.path.join(os.path.dirname(here), "generators")
    for path in glob.glob(os.path.join(here, "*.py")) + glob.glob(
            os.path.join(gens, "*.py")):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node_ in ast.walk(tree):
            names = []
            if isinstance(node_, ast.Import):
                names = [a.name for a in node_.names]
            elif isinstance(node_, ast.ImportFrom) and not node_.level:
                names = [node_.module or ""]
            for name in names:
                assert name.split(".")[0] not in (
                    "kubernetes_tpu", "benchmarks", "jax"), (path, name)
