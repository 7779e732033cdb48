"""The garbage collector's policy for the scheduler's process
(sched/gcpolicy.py): a fixed young-generation threshold from a runner's
start(), the set-up heap frozen when its loop first starts, both given back
on stop(); the two series that say it engaged."""

import gc
import warnings
import weakref

import pytest

from kubernetes_tpu.client.clientset import HTTPClient
from kubernetes_tpu.config.types import SchedulerConfiguration
from kubernetes_tpu.sched.gcpolicy import GC_POLICY, YOUNG_THRESHOLD
from kubernetes_tpu.sched.runner import SchedulerRunner
from kubernetes_tpu.store.apiserver import APIServer
from kubernetes_tpu.testing.wrappers import make_node, make_pod
from test_host_account import series, wait_for

GENERATIONS = ['scheduler_gc_collections_total{generation="%d"}' % g
               for g in range(3)]
PAUSE = "scheduler_gc_pause_seconds_total"


@pytest.fixture
def server():
    # a runner that an earlier test of this process started and never
    # stopped (a test that failed before its stop()) still holds the
    # policy; these tests are about one runner, so take its holds away
    # rather than fail five more tests for it
    while GC_POLICY._holders:
        warnings.warn("an earlier test leaked a started SchedulerRunner")
        GC_POLICY.release()
    srv = APIServer().start()
    try:
        yield srv
    finally:
        srv.stop()


def build(server, **cfg):
    return SchedulerRunner(HTTPClient(server.url),
                           SchedulerConfiguration(batch_size=8, **cfg))


def test_policy_is_taken_at_start_frozen_once_and_given_back(
        server, monkeypatch):
    found = gc.get_threshold()
    hooks = list(gc.callbacks)
    # this interpreter starts with a few hundred objects frozen already
    at_first = gc.get_freeze_count()
    runner = build(server)
    try:
        # a runner that is built and never started owes nobody a stop()
        assert gc.get_threshold() == found and gc.callbacks == hooks
        runner.start(start_loop=False)
        assert gc.get_threshold() == (YOUNG_THRESHOLD, *found[1:])
        assert gc.get_freeze_count() == at_first  # set-up is not over
        runner.start_loop()
        assert gc.get_freeze_count() > at_first + 10_000
        assert gc.get_threshold()[0] == YOUNG_THRESHOLD
        assert gc.isenabled()
        # a lease regained, a watchdog revive: the same runner starts its
        # loop again and freezes nothing more
        calls = []
        monkeypatch.setattr(GC_POLICY, "freeze", lambda: calls.append(1))
        runner._stop_loop()
        runner._start_loop()
        assert calls == []
    finally:
        runner.stop()
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold() == found
    assert gc.callbacks == hooks
    runner.stop()  # a second stop gives nothing back twice
    assert GC_POLICY._holders == 0 and gc.get_threshold() == found


def test_the_last_of_two_runners_gives_the_policy_back(server):
    found = gc.get_threshold()
    first, second = build(server), build(server)
    try:
        first.start()
        second.start(start_loop=False)
        first.stop()
        assert gc.get_threshold()[0] == YOUNG_THRESHOLD
        assert gc.get_freeze_count() > 0
        assert GC_POLICY._on_gc in gc.callbacks
        second.kill()
    finally:
        first.stop()
        second.stop()
    assert gc.get_freeze_count() == 0 and gc.get_threshold() == found
    assert GC_POLICY._on_gc not in gc.callbacks


def test_no_full_collection_while_pods_pass_the_informer_handler(server):
    """2,000 pods through the served informer path — watch event, store,
    Pod.from_dict, flight record, precompile, queue — with the apiserver
    allocating in this same interpreter: young collections only. The pods
    are gated, so the loop leaves them parked."""
    client = HTTPClient(server.url)
    client.nodes().create(make_node("n0").capacity(
        {"cpu": "16", "memory": "32Gi", "pods": "64"}).obj().to_dict())
    before_build = series()
    runner = build(server)
    try:
        # every label is there from the start, at whatever it had reached
        assert all(g in before_build for g in GENERATIONS), before_build
        assert PAUSE in before_build
        runner.start()
        informer = runner.factory.informer("pods", None)
        before, seen = series(), informer.events
        for wave in range(4):
            client.pods("default").create_many([
                make_pod(f"w{wave}-p{i}").req({"cpu": "100m"})
                .label("app", f"a{i % 7}").scheduling_gate("hold")
                .obj().to_dict() for i in range(500)])
        assert wait_for(lambda: informer.events >= seen + 2000)
        assert wait_for(
            lambda: len(runner.queue.unschedulable_pods()) == 2000)
        after = series()
    finally:
        runner.stop()
    assert after[GENERATIONS[2]] - before[GENERATIONS[2]] == 0
    assert after[PAUSE] >= before[PAUSE]


def test_pause_series_grows_with_a_collection_and_the_hook_goes(server):
    runner = build(server)
    try:
        runner.start()
        before = series()
        gc.collect(0)
        gc.collect(1)
        gc.collect(1)
        after = series()
        assert after[PAUSE] > before[PAUSE]
        assert after[GENERATIONS[0]] - before[GENERATIONS[0]] == 1
        assert after[GENERATIONS[1]] - before[GENERATIONS[1]] == 2
        assert after[GENERATIONS[2]] == before[GENERATIONS[2]]
    finally:
        runner.stop()
    assert GC_POLICY._on_gc not in gc.callbacks
    # the totals stay in the exposition and stand still: nobody counts now
    stopped = series()
    gc.collect()
    now = series()
    assert [now[k] for k in (PAUSE, *GENERATIONS)] == [
        stopped[k] for k in (PAUSE, *GENERATIONS)]


def test_a_cycle_made_after_the_freeze_is_still_collected(server):
    class Node:
        pass

    runner = build(server)
    try:
        runner.start()
        assert gc.get_freeze_count() > 0 and gc.isenabled()
        a, b = Node(), Node()
        a.other, b.other = b, a
        gone = weakref.ref(a)
        del a, b
        assert gone() is not None  # a cycle: only the collector frees it
        gc.collect()
        assert gone() is None
    finally:
        runner.stop()
