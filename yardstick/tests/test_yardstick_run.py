"""run.py end to end on the rehearsal configurations (CPU, tiny)."""

import json
import os

import pytest

from conftest import KEYS, ROOT, run_cell as cell


@pytest.mark.parametrize("workload,seconds,names", [
    ("rehearsal-mixed.burst", 30, {"bound_rate", "setup_s"}),
    ("rehearsal-antiaffinity.burst", 30, {"bound_rate", "setup_s"}),
    ("rehearsal-mixed.rehearsal-arrivals", 3,
     {"bind_p50_s", "bind_p99_s", "setup_s"}),
])
def test_rehearsal_end_to_end(workload, seconds, names):
    rc, lines, last = cell(workload, 2147483659, seconds)
    assert rc == 0, lines[-5:]
    assert KEYS <= set(last)
    assert last["correct"] is True and last["failed"] == 0, lines[-4:]
    assert last["attempted"] > 0
    assert set(last["metrics"]) == names
    for m in last["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert last["device"]["platform"] == "cpu"
    assert any(ln.startswith("counters: ") for ln in lines)
    with open(os.path.join(ROOT, "yardstick", "out",
                           f"{workload}.2147483659.json")) as f:
        full = json.load(f)
    assert {v["name"] for v in full["verdicts"]} >= {
        "bind_read_back", "auditor", "device_answers"}


def test_traced_run_reports_per_layer_metrics():
    rc, lines, last = cell("rehearsal-mixed.rehearsal-arrivals", 7, 3,
                           trace=1)
    assert rc == 0, lines[-5:]
    assert last["correct"] is True
    got = set(last["metrics"])
    assert {"pods_per_drain.arrivals", "encode_stage_ms_per_drain.arrivals",
            "gen_late_p99_ms.arrivals", "ctx_rebuilds.arrivals",
            "window_compiles.arrivals"} <= got
    assert not any(name.endswith(".burst") for name in got)
    assert "setup_s" not in got


def test_a_burst_cut_by_its_deadline_is_failed_not_incorrect():
    rc, lines, last = cell("rehearsal-mixed.burst", 5, 0.05)
    assert rc == 0, lines[-5:]
    assert last["failed"] > 0 and last["correct"] is True, lines[-4:]
    assert last["attempted"] == 256


def test_a_real_cell_refuses_a_cpu():
    rc, lines, last = cell("mixed-5000n.burst", 1, 1)
    assert rc == 3 and last is None
    assert not any(ln.startswith("{") for ln in lines)
