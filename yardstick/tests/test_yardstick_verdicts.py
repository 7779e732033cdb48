"""``correct`` judges answers and who gave them; unfinished work is
``failed``; a tampered bind is named."""

from yardstick import verdicts
from yardstick.drivers import arrivals, burst
from yardstick.generators._objects import node, pod


def listed(name, where):
    p = pod(name, {"cpu": "100m"})
    if where:
        p["spec"]["nodeName"] = where
    return p


def test_read_back_confirms_what_the_store_holds():
    seen = {"default/a": [1.0, "n0"], "default/b": [2.0, "n1"]}
    verdict, wrong = verdicts.read_back(
        seen, [listed("a", "n0"), listed("b", "n1"), listed("c", None)])
    assert verdict[0] == "bind_read_back" and verdict[1] and not wrong


def test_a_tampered_bind_is_named():
    seen = {"default/a": [1.0, "n0"], "default/b": [2.0, "n1"]}
    verdict, wrong = verdicts.read_back(
        seen, [listed("a", "n0"), listed("b", "n7")])
    assert verdict[0] == "bind_read_back" and verdict[1] is False
    assert wrong == {"default/b"} and "n7" in verdict[2]
    # and a bind the store lost altogether
    verdict, wrong = verdicts.read_back(seen, [listed("a", "n0")])
    assert verdict[1] is False and wrong == {"default/b"}


def test_end_state_names_the_kind_that_failed():
    nodes = [node("n0", {"cpu": "1", "memory": "1Gi", "pods": "1"})]
    pods = [listed("a", "n0"), listed("b", "n0")]
    found = verdicts.end_state(("capacity", "taints"), nodes, pods)
    assert {n: ok for n, ok, _, _ in found} == {
        "end_state.capacity": False, "end_state.taints": True}
    # the number compared is the count of what is wrong; its limit is 0
    assert [n > 0 for _, _, _, n in found] == [True, False]


def test_own_judges_and_device():
    ok = verdicts.own_judges({"violations": 0, "parity": {
        "divergences": 0, "pending": 0, "samples": {"drain": 1}}})
    assert all(v[1] for v in ok)
    bad = verdicts.own_judges({"violations": 2, "parity": {
        "divergences": 0, "pending": 1}})
    assert [v[1] for v in bad] == [False, False]
    armed = {"armed": True, "platforms": ["tpu"]}
    calm = {"degradedIndex": 0, "breakerTrips": 0}
    assert verdicts.device_answers("tpu", armed, calm, {})[1]
    # pace and regime never enter: rebuilds, compiles, other loop errors
    assert verdicts.device_answers("tpu", armed, calm, {
        "ctx.rebuilds": 3.0, "compile.real": 2.0,
        'scheduler_loop_errors_total{site="nomination_gc"}': 1.0})[1]
    assert not verdicts.device_answers("tpu", armed, calm, {
        'scheduler_loop_errors_total{site="device_drain"}': 1.0})[1]
    assert not verdicts.device_answers(
        "tpu", {"armed": True, "platforms": ["cpu"]}, calm, {})[1]
    assert not verdicts.device_answers(
        "tpu", armed, {"degradedIndex": 1, "breakerTrips": 1}, {})[1]


def test_burst_rate_over_the_burst_or_over_seconds_when_cut():
    whole = {"seconds": 20.0, "deadline_s": 20.0, "due": [0.0] * 4,
             "bound": [1.0, 2.0, 4.0, 8.0]}
    assert burst.metrics(whole) == {"bound_rate": 0.5}
    cut = dict(whole, bound=[1.0, 2.0, None, None])
    assert burst.metrics(cut) == {"bound_rate": 0.1}
    assert burst.metrics(dict(whole, bound=[None] * 4)) == {
        "bound_rate": 0.0}


def test_arrivals_latency_from_due_and_a_floor_for_the_unbound():
    obs = {"seconds": 10.0, "deadline_s": 20.0,
           "due": [1.0, 2.0, 3.0, 4.0], "bound": [1.5, 3.0, 3.25, None]}
    got = arrivals.metrics(obs)
    assert got == {"bind_p50_s": 0.5, "bind_p99_s": 16.0}
