"""BENCHMARK.json and the data files agree, and every file a cell needs is
found by name."""

import glob
import importlib
import json
import os
import re

from conftest import ROOT

from yardstick import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_files_and_modules():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell["listed"] is not None and cell["chips"] == w["chips"]
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            config = json.load(f)
        assert not config.get("rehearsal")
        assert config["reduced"] == configs[w["config"]]["reduced"]
        assert config["source"] == configs[w["config"]]["source"]
        importlib.import_module(
            f"yardstick.generators.{config['generator']}")
        params = harness._json(os.path.join(
            harness.HERE, "traffic", w["traffic"] + ".json"))
        driver = importlib.import_module(
            f"yardstick.drivers.{params['kind']}")
        listed = harness.listed_metrics(cell, "end_to_end")
        assert set(driver.E2E) | {"setup_s"} == listed


def test_per_layer_entries_mirror_the_layer_metric_files():
    b = bench()
    kinds = {w["name"]: harness._json(os.path.join(
        harness.HERE, "traffic", w["traffic"] + ".json"))["kind"]
        for w in b["workloads"]}
    files = {}
    for path in glob.glob(os.path.join(harness.HERE, "layer_metrics",
                                       "*.json")):
        spec = harness._json(path)
        assert os.path.basename(path) == spec["name"] + ".json"
        importlib.import_module(f"yardstick.readers.{spec['reader']}").read
        files[spec["name"]] = spec
    e2e = {m["name"]: m for m in b["end_to_end"]}
    # the driver's rule: an entry lists the cells in which its reader finds
    # something to read, each of a kind the file reports and each reporting
    # the end-to-end metric it moves; a file with no entry waits for a cell
    assert {m["name"] for m in b["per_layer"]} <= set(files)
    for m in b["per_layer"]:
        spec = files[m["name"]]
        for k in ("unit", "better", "source", "layer", "moves"):
            assert m[k] == spec[k], (m["name"], k)
        assert m["workloads"] and len(set(m["workloads"])) == len(
            m["workloads"])
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert kinds[w] in spec["kinds"], (m["name"], w)
            assert w in moved.get("workloads", kinds), (m["name"], w)
    waiting = set(files) - {m["name"] for m in b["per_layer"]}
    # what waits today: every .arrivals twin (no arrivals cell is listed)
    # and the explainer's metric (no listed cell holds a pod that cannot
    # be placed since PR 35)
    assert {n for n in waiting if n.endswith(".burst")} == {
        "explain_ms_per_drain.burst"}


def test_names_and_limits_of_the_contract():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in b[group]:
            assert NAME.match(entry["name"]), entry["name"]
            for k in ("why", "layer", "source"):
                if k in entry:
                    assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 0
    assert 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_nothing_in_the_harness_names_a_cell_a_config_or_a_metric():
    b = bench()
    words = ({w["name"] for w in b["workloads"]}
             | {c["name"] for c in b["configs"]}
             | {m["name"] for m in b["per_layer"]}
             | {m["name"] for m in b["end_to_end"]} - {"setup_s"})
    for name in ("run.py", "harness.py", "procs.py", "program.py",
                 "verdicts.py"):
        with open(os.path.join(harness.HERE, name)) as f:
            text = f.read()
        for word in words:
            assert word not in text, (name, word)
