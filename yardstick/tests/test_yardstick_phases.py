"""Phases by name, pods that stay pending, pods that leave (PR 36): what
``harness.build`` hands a generator, and what the verdicts make of a pod
that is not listed after the window. The three listed deployments must get
from ``build`` the objects the harness of PR 35 gave them, hash for hash:
the table below was written down from that tree before the edit."""

import hashlib
import importlib
import json
import os

import pytest

from yardstick import harness, reference, verdicts
from yardstick.generators._objects import node, pod

# sha256 of json.dumps(objects, sort_keys=True), from commit 866fdce
PARENT = {
    ("mixed-5000n", 7): {
        "nodes": "d877b3440f6ebdca5461044dcfc49c23b1bf609244d6f83c2ab5b615a1d65a36",
        "measure": "865dcb86fd4bd171bdd9eac78ae4132328a29528d937f9a6cef024f327067113",
        "init": "4f53cda18c2baa0c0354bb5f9a3ecbe5ed12ab4d8e11ba873c2f11161202b945",
        "warmup": "10d3360ba3c8f6b254226bd5c8b8959b2d8de0146e517f9223bb85238825c318",
        "plan": "919343112e126061f55c8108a0ae8e9d60416a2d16032abfa79144bc7c17e998",
        "n": [5000, 10000, 0, 1024]},
    ("mixed-5000n", 2147483659): {
        "nodes": "89866b26a1e00849cda371eef9247ab8d8106f3b687817e2e907cc2d099e64af",
        "measure": "dbfc13fa6f935abd00b1a4eab24a9c2158530d8e38493702b4193f83c9247ce9",
        "init": "4f53cda18c2baa0c0354bb5f9a3ecbe5ed12ab4d8e11ba873c2f11161202b945",
        "warmup": "3a3aa34695d149e12c690984875edf1c5ef476b5d751973e771a891dc169440b",
        "plan": "919343112e126061f55c8108a0ae8e9d60416a2d16032abfa79144bc7c17e998",
        "n": [5000, 10000, 0, 1024]},
    ("antiaffinity-5000n", 7): {
        "nodes": "c8b689bc1bcc210eaa95445f3319cb43f1aa557095e0e95d1dd6ed1680ed64ce",
        "measure": "3087769e6a1338f8c7d578dd91098be654a4f4e678c5007133eff14b8f4a6109",
        "init": "9d3293f0dcb0e82a4bcb2a28bd629e850237d789c8788d2279ebec86307eb934",
        "warmup": "4f53cda18c2baa0c0354bb5f9a3ecbe5ed12ab4d8e11ba873c2f11161202b945",
        "plan": "39db615d08770eb657f200261ed64fdb88751c89c913108a7598d1d4e94a0973",
        "n": [5000, 3500, 1000, 0]},
    ("topologyspread-5000n", 7): {
        "nodes": "10b94f1e0c440fe729a5c87162c084e9e11911e03d2826a47e22552317fb0da2",
        "measure": "21d5d844f4523f444db57fca29752afb18da174d76e9a65bbb7cb2fd4dbd1725",
        "init": "394bce95cdd4ecf476d579a003cfb3115f8bc26037548b8cced02da6e055aa84",
        "warmup": "4f53cda18c2baa0c0354bb5f9a3ecbe5ed12ab4d8e11ba873c2f11161202b945",
        "plan": "6e215762d50d1b8aebc70bba08d4ec26cf29cf0d8a550f90f5858358cd50f604",
        "n": [5000, 2000, 5000, 0]},
}
AWAITED = ("measure", "init", "warmup")


def digest(objects) -> str:
    return hashlib.sha256(
        json.dumps(objects, sort_keys=True).encode()).hexdigest()


def world(config: dict, seed: int = 7) -> dict:
    params = harness._json(os.path.join(harness.HERE, "traffic",
                                        "burst.json"))
    driver = importlib.import_module("yardstick.drivers.burst")
    return harness.build(config, driver, params, seed, 20.0)


def config_of(name: str) -> dict:
    return harness._json(os.path.join(harness.HERE, "configs",
                                      name + ".json"))


# ------------------------------------------------- (a) the listed cells

@pytest.mark.parametrize("name,seed", sorted(PARENT))
def test_build_gives_a_listed_deployment_the_parents_objects(name, seed):
    w = world(config_of(name), seed)
    want = PARENT[name, seed]
    assert [len(w["nodes"])] + [len(w["phases"][p]) for p in AWAITED] == (
        want["n"])
    assert digest(w["nodes"]) == want["nodes"]
    for phase in AWAITED:
        assert digest(w["phases"][phase]) == want[phase], phase
    assert digest(w["plan"]) == want["plan"]
    # and nothing new is asked of it: no pod pending, none may leave
    assert w["phases"]["pending"] == [] and w["leavers"] == ()


def test_the_zone_spread_generator_is_told_its_phases():
    """The same objects by ``generate_phases`` as by the scan of configs/
    that it replaces in the harness, and at sizes no configuration has."""
    gen = importlib.import_module(
        "yardstick.generators.upstream_topology_spreading")
    c = config_of("topologyspread-5000n")
    counts = {"measure": c["measurePods"], "init": c["initPods"],
              "warmup": c["warmupPods"], "pending": 0}
    nodes, phases = gen.generate_phases(0, c["nodes"], counts)
    old_nodes, old_pods = gen.generate(0, c["nodes"], sum(counts.values()))
    assert nodes == old_nodes
    assert phases["measure"] + phases["init"] + phases["warmup"] == old_pods
    _, odd = gen.generate_phases(0, 7, {"measure": 3, "init": 2,
                                        "warmup": 1, "pending": 0})
    assert [len(odd[p]) for p in AWAITED] == [3, 2, 1]
    assert all("topologySpreadConstraints" in p["spec"]
               for p in odd["measure"])
    assert not any("topologySpreadConstraints" in p["spec"]
                   for p in odd["init"] + odd["warmup"])


# ------------------------------------------ (b) pendingPods and generate

def test_pending_pods_need_a_generator_that_is_told_the_phases():
    c = dict(config_of("rehearsal-mixed"), pendingPods=4)
    c["namespaces"] = dict(c["namespaces"], pending="sched-0")
    with pytest.raises(SystemExit, match="generate_phases"):
        world(c)


def test_a_generator_that_miscounts_a_phase_is_refused():
    c = dict(config_of("rehearsal-topologyspread"), pendingPods=2)
    c["namespaces"] = dict(c["namespaces"], pending="sched-0")
    with pytest.raises(SystemExit, match="were asked for"):
        world(c)  # it has generate_phases and emits no pending pod


def test_a_measured_pod_may_not_be_a_leaver():
    with pytest.raises(SystemExit, match="a measured pod"):
        world(dict(config_of("rehearsal-mixed"), leavers=["measure"]))


def test_the_pending_phase_has_its_own_pods_and_namespace():
    c = config_of("rehearsal-pending")
    w = world(c)
    assert {p: len(w["phases"][p]) for p in harness.PHASES} == {
        "measure": 64, "init": 0, "warmup": 16, "pending": 8}
    for p in w["phases"]["pending"]:
        assert p["metadata"]["namespace"] == c["namespaces"]["pending"]
        assert p["spec"]["containers"][0]["resources"]["requests"][
            "cpu"] == "9"
    names = [reference.key(p) for group in w["phases"].values()
             for p in group]
    assert len(set(names)) == len(names)


# --------------------------------------------------------- (c) read_back

def listed(name, where=None):
    p = pod(name, {"cpu": "100m"})
    if where:
        p["spec"]["nodeName"] = where
    return p


SEEN = {"default/a": [10.0, "n0"]}


@pytest.mark.parametrize("case,after,gone,may_leave,confirmed", [
    ("listed on the same node", [listed("a", "n0")], {}, set(), True),
    ("listed on another node", [listed("a", "n7")], {}, set(), False),
    ("missing and not a leaver", [], {"default/a": [11.0, {}]}, set(),
     False),
    ("missing, a leaver, DELETED after the bind", [],
     {"default/a": [11.0, {}]}, {"default/a"}, True),
    ("missing, a leaver, never seen DELETED", [], {}, {"default/a"}, False),
    ("missing, a leaver, DELETED before the bind it was seen with", [],
     {"default/a": [9.0, {}]}, {"default/a"}, False),
    ("a leaver that stayed, on another node", [listed("a", "n7")],
     {"default/a": [11.0, {}]}, {"default/a"}, False),
])
def test_read_back_and_the_pods_that_leave(case, after, gone, may_leave,
                                           confirmed):
    verdict, wrong = verdicts.read_back(SEEN, after, gone, may_leave)
    assert verdict[0] == "bind_read_back"
    assert verdict[1] is confirmed, case
    assert wrong == (set() if confirmed else {"default/a"})
    assert verdict[3] == len(wrong)


def test_a_measured_pod_that_is_gone_is_failed():
    """Only the phases under ``leavers`` may leave, and ``measure`` is
    never one: the bind of a measured pod that was deleted is unconfirmed,
    and an unconfirmed bind is not bound."""
    measured = pod("m", {"cpu": "100m"}, namespace="sched-1")
    victim = pod("v", {"cpu": "100m"}, namespace="sched-0")
    seen = {"sched-1/m": [10.0, "n0"], "sched-0/v": [1.0, "n0"]}
    gone = {"sched-1/m": [11.0, measured], "sched-0/v": [9.0, victim]}
    _, wrong = verdicts.read_back(seen, [], gone, {"sched-0/v"})
    assert wrong == {"sched-1/m"}
    due, bound = harness.per_pod(
        [(0.0, "sched-1", [measured])],
        {"binds": seen, "t0": 9.0}, 20.0, wrong)
    assert due == [0.0] and bound == [None]


# ------------------------------------------------------ (d) left_pending

def test_left_pending_wants_every_such_pod_listed_and_unbound():
    keys = ["default/p0", "default/p1"]
    ok = verdicts.left_pending(keys, [listed("p0"), listed("p1"),
                                      listed("other", "n0")], SEEN)
    assert ok[:2] == ("left_pending", True) and ok[3] == 0


@pytest.mark.parametrize("after,seen,said", [
    ([listed("p0"), listed("p1", "n3")], {}, "bound to 'n3'"),
    ([listed("p0")], {}, "default/p1: not listed"),
    # the watcher saw a bind that the store no longer shows
    ([listed("p0"), listed("p1")], {"default/p1": [5.0, "n4"]},
     "bound to 'n4'"),
])
def test_left_pending_names_the_pod_that_was_placed(after, seen, said):
    verdict = verdicts.left_pending(["default/p0", "default/p1"], after,
                                    seen)
    assert verdict[1] is False and verdict[3] == 1
    assert said in verdict[2] and "fit nowhere" in verdict[2]


# --------------------------------------------------------- (e) end_state

def test_end_state_hands_the_gone_pods_to_a_check_that_asks(monkeypatch):
    calls = {}

    def two(nodes, pods):
        calls["two"] = (nodes, pods)
        return []

    def three(nodes, pods, gone):
        calls["three"] = (nodes, pods, gone)
        return [f"{reference.key(g)} left" for g in gone]

    monkeypatch.setattr(reference, "load",
                        lambda kinds: {"two": two, "three": three})
    nodes = [node("n0", {"cpu": "1", "memory": "1Gi", "pods": "1"})]
    pods, gone = [listed("a", "n0")], [listed("v", "n0")]
    out = verdicts.end_state(("two", "three"), nodes, pods, gone)
    assert calls["two"] == (nodes, pods)
    assert calls["three"] == (nodes, pods, gone)
    assert [(n, ok, c) for n, ok, _, c in out] == [
        ("end_state.two", True, 0), ("end_state.three", False, 1)]
    # no pod left: the three-parameter check is still called, with none
    verdicts.end_state(("three",), nodes, pods)
    assert calls["three"] == (nodes, pods, [])


def test_the_kind_files_here_keep_two_parameters():
    for name in sorted(os.listdir(os.path.join(harness.HERE, "reference"))):
        if name.endswith(".py") and not name.startswith("_"):
            mod = importlib.import_module(
                f"yardstick.reference.{name[:-3]}")
            assert not reference.takes_gone(mod.check), name
    assert reference.takes_gone(lambda nodes, pods, gone: [])
