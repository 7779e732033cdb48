"""The generators are copies: object for object what benchmarks/workloads.py
gives today at the same seed (server-stamped metadata aside), the same seed
gives the same objects and schedule twice, and every generator declares
constraint kinds that have a reference file."""

import importlib

import pytest

from yardstick import harness, reference
from yardstick.drivers import arrivals, burst

BASELINE = {"scheduling_basic": "scheduling_basic",
            "noderesources_fit": "noderesources_fit",
            "pod_anti_affinity": "pod_anti_affinity",
            "preferred_topology_spreading": "preferred_topology_spreading",
            "mixed_heterogeneous": "mixed_heterogeneous"}
ALL = sorted(BASELINE) + ["upstream_pod_anti_affinity"]


def gen(name):
    return importlib.import_module(f"yardstick.generators.{name}")


def client_side(obj) -> dict:
    d = obj.to_dict()
    for k in ("uid", "resourceVersion", "creationTimestamp"):
        d["metadata"].pop(k, None)
    return d


@pytest.mark.parametrize("name", sorted(BASELINE))
@pytest.mark.parametrize("seed", [0, 2147483659])
def test_copy_matches_the_program_side_generator(name, seed):
    from benchmarks import workloads
    theirs_n, theirs_p = getattr(workloads, BASELINE[name])(
        pods=300, nodes=60, seed=seed)
    ours_n, ours_p = gen(name).generate(seed, 60, 300)
    assert [client_side(n) for n in theirs_n] == ours_n
    assert [client_side(p) for p in theirs_p] == ours_p


@pytest.mark.parametrize("name", ALL)
def test_same_seed_same_objects_and_kinds_have_files(name):
    g = gen(name)
    assert g.generate(11, 40, 90) == g.generate(11, 40, 90)
    assert set(reference.load(g.CONSTRAINTS)) == set(g.CONSTRAINTS)


def test_upstream_template_reaches_the_programs_api_types():
    from kubernetes_tpu.api import Pod
    _, pods = gen("upstream_pod_anti_affinity").generate(0, 4, 2)
    term = Pod.from_dict(pods[0]).to_dict()["spec"]["affinity"][
        "podAntiAffinity"]["requiredDuringSchedulingIgnoredDuringExecution"]
    assert term[0]["namespaces"] == ["sched-1", "sched-0"]
    assert term[0]["topologyKey"] == "kubernetes.io/hostname"


PARAMS = {"rate_pods_per_s": 600, "group": 16, "senders": 4, "grace_s": 10}


def test_arrivals_schedule_same_gaps_every_seed_in_another_order():
    a = arrivals.plan(PARAMS, {}, 5, 20)
    assert a == arrivals.plan(PARAMS, {}, 5, 20)
    b = arrivals.plan(PARAMS, {}, 6, 20)
    assert len(a["groups"]) == len(b["groups"]) == 750
    assert a["groups"] != b["groups"]

    def gaps(plan):
        due = [0.0] + [d for d, _ in plan["groups"]]
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))
    assert gaps(a) == gaps(b)
    assert 19.9 < a["groups"][-1][0] < 20.0 and a["deadline_s"] == 30.0


def test_burst_plan_is_the_configurations_measure_pods():
    plan = burst.plan({"chunk": 2500, "concurrency": 4},
                      {"measurePods": 6000}, 0, 20)
    assert [n for _, n in plan["groups"]] == [2500, 2500, 1000]
    assert plan["deadline_s"] == 20.0


def test_build_gives_each_phase_its_namespace():
    config = harness._json(harness.os.path.join(
        harness.HERE, "configs", "rehearsal-antiaffinity.json"))
    world = harness.build(config, burst, {"chunk": 32, "concurrency": 2},
                          3, 5)
    spaces = {phase: {p["metadata"]["namespace"] for p in pods}
              for phase, pods in world["phases"].items() if pods}
    assert spaces == {"measure": {"sched-1"}, "init": {"sched-0"}}
    assert len(world["phases"]["measure"]) == config["measurePods"]
    names = [p["metadata"]["name"] for ps in world["phases"].values()
             for p in ps]
    assert len(names) == len(set(names))
