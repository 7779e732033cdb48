"""The ``series_ratio`` reader, and the eleven metrics of the host's own
account (pop wait, queue wait, informer CPU share and handler time an
event, blocked time in encode and stage, cycle, dispatch host work,
resolver fetch, resolve tail, bind call inside bind bulk) end to end on a
rehearsal configuration (CPU, tiny)."""

import glob
import json
import os

import pytest

from conftest import ROOT, run_cell as cell

from yardstick.readers import series_ratio

NEW = ("pop_wait_ms_per_drain", "queue_wait_mean_ms", "informer_cpu_share",
       "encode_stage_blocked_ms_per_drain", "sched_cycle_ms_per_drain",
       "dispatch_host_ms_per_drain", "resolver_fetch_ms_per_drain",
       "bind_call_ms_per_drain", "bind_bulk_ms_per_drain",
       "resolve_tail_ms_per_drain", "informer_handler_us_per_event")


@pytest.mark.parametrize("counters,args,want", [
    # a mean from a histogram's sum and count, in ms
    ({"w_sum": 3.0, "w_count": 6.0},
     {"num": ["w_sum"], "den": ["w_count"], "scale": 1000.0}, 500.0),
    # several series on either side; scale defaults to 1
    ({"a": 1.0, "b": 2.0, "c": 4.0, "d": 8.0},
     {"num": ["a", "b"], "den": ["c", "d"]}, 0.25),
    # a numerator series that grew by nothing is a 0, not a gap
    ({"a": 0.0, "c": 4.0}, {"num": ["a"], "den": ["c"]}, 0.0),
    # a numerator series the program does not expose: nothing to read
    ({"a": 1.0, "c": 4.0}, {"num": ["a", "b"], "den": ["c"]}, None),
    # nothing happened in the window, or the denominator is absent
    ({"a": 1.0, "c": 0.0}, {"num": ["a"], "den": ["c"]}, None),
    ({"a": 1.0}, {"num": ["a"], "den": ["c"]}, None),
])
def test_series_ratio(counters, args, want):
    got = series_ratio.read({"counters": counters}, args)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_new_metric_files_come_in_pairs():
    for name in NEW:
        specs = {}
        for kind in ("burst", "arrivals"):
            with open(os.path.join(ROOT, "yardstick", "layer_metrics",
                                   f"{name}.{kind}.json")) as f:
                specs[kind] = json.load(f)
            assert specs[kind]["kinds"] == [kind]
        assert specs["burst"]["moves"] == "bound_rate"
        assert specs["arrivals"]["moves"] == "bind_p99_s"
        for k in ("layer", "unit", "better", "source", "reader", "args"):
            assert specs["burst"][k] == specs["arrivals"][k], (name, k)
    # and every file, paired or not, is named for the one kind it reports
    # and moves that kind's end-to-end metric
    moves = {"burst": "bound_rate", "arrivals": "bind_p99_s"}
    every = glob.glob(os.path.join(ROOT, "yardstick", "layer_metrics",
                                   "*.json"))
    assert len(every) >= 21 + 2 * len(NEW)
    for path in every:
        with open(path) as f:
            spec = json.load(f)
        base, _, kind = spec["name"].rpartition(".")
        assert os.path.basename(path) == spec["name"] + ".json"
        assert base and spec["kinds"] == [kind], spec["name"]
        assert spec["moves"] == moves[kind], spec["name"]


def test_traced_burst_reports_the_hosts_own_account():
    rc, lines, last = cell("rehearsal-mixed.burst", 2147483693, 30, trace=1)
    assert rc == 0, lines[-5:]
    assert last["correct"] is True and last["failed"] == 0, lines[-4:]
    got = last["metrics"]
    for name in NEW:
        assert name + ".burst" in got, (name, sorted(got))
        assert got[name + ".burst"]["value"] >= 0.0
    # the loop thread is pop_wait + cycle, end to end
    assert got["sched_cycle_ms_per_drain.burst"]["value"] > 0.0
    assert got["queue_wait_mean_ms.burst"]["value"] > 0.0
    assert 0.0 < got["informer_cpu_share.burst"]["value"] < 1.0
    assert (got["encode_stage_blocked_ms_per_drain.burst"]["value"]
            <= got["encode_stage_ms_per_drain.burst"]["value"] + 1e-6)
    # the HTTP call is a part of the binder's whole chunk
    assert (0.0 < got["bind_call_ms_per_drain.burst"]["value"]
            <= got["bind_bulk_ms_per_drain.burst"]["value"])
    assert got["informer_handler_us_per_event.burst"]["value"] > 0.0
    assert not any(n.endswith(".arrivals") for n in got)
