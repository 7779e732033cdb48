"""The antiaffinity-5000n deployment at a small size, on the CPU: upstream
scheduler_perf SchedulingPodAntiAffinity's own pods (the benchmark's
generator, namespaces as its configuration names them) through the served
path, judged by the benchmark's plain reference and by the sentinel's
oracle check with every winner checked. Every pod is ``color: green`` and
repels ``color: green`` on hostname over sched-0 and sched-1: a node takes
one pod."""

import json
import os
import time

import pytest

from kubernetes_tpu.api import Node, Pod
from kubernetes_tpu.audit.sentinel import verify_drain_winners
from kubernetes_tpu.client.clientset import HTTPClient
from kubernetes_tpu.config.types import SchedulerConfiguration
from kubernetes_tpu.sched.oracle import OracleScheduler
from kubernetes_tpu.sched.runner import SchedulerRunner
from kubernetes_tpu.store.apiserver import APIServer
from yardstick.generators import _objects, upstream_pod_anti_affinity
from yardstick.reference import antiaffinity, bound_by_node, capacity

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "yardstick", "configs",
                       "antiaffinity-5000n.json")) as _f:
    NAMESPACES = json.load(_f)["namespaces"]
N_INIT, N_MEASURE = 16, 64


def wait_for(pred, timeout=120.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return bool(pred())


class Served:
    """An in-process apiserver holding ``nodes`` and a running scheduler at
    batchSize 16 x 2 drain batches; ``scheduler_cfg`` adds to its
    configuration."""

    def __init__(self, nodes: list, namespaces, **scheduler_cfg):
        self.server = APIServer().start()
        self.client = HTTPClient(self.server.url)
        spaces = self.client.resource("namespaces", None)
        have = {n["metadata"]["name"] for n in spaces.list()}
        for ns in sorted(set(namespaces) - have):
            spaces.create({"apiVersion": "v1", "kind": "Namespace",
                           "metadata": {"name": ns}})
        self.client.nodes().create_many(nodes)
        self.runner = SchedulerRunner(
            HTTPClient(self.server.url),
            SchedulerConfiguration(batch_size=16, max_drain_batches=2,
                                   backoff_initial_s=0.05,
                                   backoff_max_s=0.2, **scheduler_cfg))
        self.runner.start()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.runner.stop()
        self.server.stop()

    def create(self, ns: str, pods: list) -> None:
        for p in pods:
            p["metadata"]["namespace"] = ns
        self.client.pods(ns).create_many(pods)

    def pods(self) -> list:
        return self.client.resource("pods", None).list()

    def bound(self) -> int:
        return sum(1 for p in self.pods() if p["spec"].get("nodeName"))


@pytest.mark.parametrize("n_nodes,want_bound", [
    (96, 80),   # nine tenths occupied, as the deployment ends
    (80, 80),   # exactly full: every node takes its one pod
    (72, 72),   # over-full: 8 pods stay unschedulable, none doubles up
])
def test_one_green_pod_a_node_through_the_served_path(n_nodes, want_bound):
    nodes, pods = upstream_pod_anti_affinity.generate(
        7, n_nodes, N_MEASURE + N_INIT)
    measure, init = pods[:N_MEASURE], pods[N_MEASURE:]
    with Served(nodes, NAMESPACES.values()) as dep:
        # initial pods are scheduled, not pre-bound (upstream's createPods)
        dep.create(NAMESPACES["init"], init)
        assert wait_for(lambda: dep.bound() == N_INIT), dep.bound()
        before = {p["metadata"]["name"]: p["spec"]["nodeName"]
                  for p in dep.pods()}
        dep.create(NAMESPACES["measure"], measure)
        assert wait_for(lambda: dep.bound() == want_bound), dep.bound()
        dep.runner.scheduler.wait_for_bindings(10.0)
        # the pods left over are judged unschedulable, not lost: the bound
        # count holds while the loop retries them
        time.sleep(0.5)
        listed, node_objs = dep.pods(), dep.client.nodes().list()
    bound = [p for p in listed if p["spec"].get("nodeName")]
    assert len(bound) == want_bound
    assert len(listed) - len(bound) == N_INIT + N_MEASURE - want_bound
    # the benchmark's plain reference, every bound pod
    assert capacity.check(node_objs, listed) == []
    assert antiaffinity.check(node_objs, listed) == []
    assert all(len(ps) == 1 for ps in bound_by_node(listed).values())
    # the sentinel's judgment, every winner of the window checked against
    # the oracle on the state the initial pods left
    typed_nodes = [Node.from_dict(n) for n in node_objs]
    held = [Pod.from_dict(p) for p in bound
            if p["metadata"]["name"] in before]
    winners = [(Pod.from_dict(p), p["spec"]["nodeName"]) for p in bound
               if p["metadata"]["name"] not in before]
    assert len(held) == N_INIT and len(winners) == want_bound - N_INIT
    assert verify_drain_winners(typed_nodes, held, winners, [],
                                max_checked=len(winners)) == []
    # and it refutes a doubled node: the check is not vacuous
    pod, _ = winners[0]
    assert verify_drain_winners(typed_nodes, held,
                                [(pod, held[0].spec.node_name)], [],
                                max_checked=1)


def test_a_green_pod_outside_the_terms_namespaces_does_not_repel():
    """The term names sched-1 and sched-0. A green pod in another
    namespace shares a node with a generator pod; one in sched-0 does not.
    The program, the benchmark's reference and sched/oracle.py agree."""
    nodes, pods = upstream_pod_anti_affinity.generate(7, 2, 2)
    incoming, resident = pods
    outsider = _objects.pod("outsider", {"cpu": "100m", "memory": "500Mi"},
                            upstream_pod_anti_affinity.GREEN,
                            namespace="elsewhere",
                            nodeName=nodes[0]["metadata"]["name"])
    resident["spec"]["nodeName"] = nodes[1]["metadata"]["name"]
    with Served(nodes, [*NAMESPACES.values(), "elsewhere"]) as dep:
        dep.create("elsewhere", [outsider])
        dep.create(NAMESPACES["init"], [resident])
        assert wait_for(lambda: dep.bound() == 2)
        dep.create(NAMESPACES["measure"], [incoming])
        assert wait_for(lambda: dep.bound() == 3), dep.bound()
        listed, node_objs = dep.pods(), dep.client.nodes().list()
    by_name = {p["metadata"]["name"]: p for p in listed}
    landed = by_name[incoming["metadata"]["name"]]
    # the program: beside the outsider, away from the sched-0 pod
    assert landed["spec"]["nodeName"] == nodes[0]["metadata"]["name"]
    # the reference: that end state is valid, and the same three pods with
    # the outsider moved into a namespace of the list are not
    assert antiaffinity.check(node_objs, listed) == []
    assert capacity.check(node_objs, listed) == []
    moved = json.loads(json.dumps(listed))
    for p in moved:
        if p["metadata"]["name"] == "outsider":
            p["metadata"]["namespace"] = NAMESPACES["init"]
    assert antiaffinity.check(node_objs, moved)
    # the oracle: the same answer for the same pod on the same state
    oracle = OracleScheduler(
        [Node.from_dict(n) for n in node_objs],
        [Pod.from_dict(by_name[n]) for n in
         ("outsider", resident["metadata"]["name"])])
    unbound = json.loads(json.dumps(landed))
    del unbound["spec"]["nodeName"]
    mask, _reasons = oracle.feasible(Pod.from_dict(unbound))
    feasible = {n["metadata"]["name"]: bool(ok)
                for n, ok in zip(node_objs, mask)}
    assert feasible == {nodes[0]["metadata"]["name"]: True,
                        nodes[1]["metadata"]["name"]: False}
