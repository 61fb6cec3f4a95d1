"""Every entry of BENCHMARK.json resolves to files of its own under bench/,
and the file keeps to the benchmark's contract on names and keys."""
from __future__ import annotations

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

BENCH = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.isfile(os.path.join(ROOT, BENCH["command"][1]))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    res = run.resolve(BENCH, cell)
    assert res["cell"]["chips"] in (1, 4)
    app = res["traffic"]["app"]
    driver = run.load_module("drivers", app)
    assert hasattr(driver, "Driver")
    e2e = {m["name"] for m in res["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert res["per_layer"]
    for m in res["end_to_end"] + res["per_layer"]:
        assert callable(run.load_module("metrics", m["name"]).read)
    for m in res["per_layer"]:
        assert m["moves"] in e2e, (cell, m["name"])
    for key in ("graph", "partition", "engine", "reduced", "assumed"):
        assert key in res["config"]


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("bench/")
    data = run.load_json(os.path.join(ROOT, config["file"]))
    assert data["name"] == config["name"]
    assert sorted(data["reduced"]) == sorted(config["reduced"])
    assert config["name"] in {w["config"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric in BENCH["end_to_end"]:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")


def test_names_and_lines():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_json_under_bench_parses():
    for dirpath, _, files in os.walk(os.path.join(ROOT, "bench")):
        for f in files:
            if f.endswith(".json"):
                with open(os.path.join(dirpath, f)) as fh:
                    json.load(fh)
