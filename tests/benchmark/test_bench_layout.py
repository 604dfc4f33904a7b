"""Every cell names parts that exist, and BENCHMARK.json keeps its
contract's shape: a configuration, traffic mix or metric is added as
files found by name."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def cells():
    return [w["name"] for w in bench()["workloads"]]


def metrics():
    b = bench()
    return b["end_to_end"] + b["per_layer"]


def applies(metric, cell):
    return cell in metric.get("workloads", [cell])


@pytest.mark.parametrize("cell", cells())
def test_cell_parts_exist(cell):
    b = bench()
    w = next(w for w in b["workloads"] if w["name"] == cell)
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert w["chips"] in (1, 4)
    config = next(c for c in b["configs"] if c["name"] == w["config"])
    assert config["file"] == f"benchmark/configs/{w['config']}.json"
    assert load("configs", w["config"] + ".json")["name"] == w["config"]
    traffic = load("traffic", w["traffic"] + ".json")
    runner = importlib.import_module("benchmark.runners." + traffic["runner"])
    assert callable(runner.Run)
    from benchmark.metrics import load as load_reader

    for m in b["per_layer"]:
        if applies(m, cell):
            assert callable(load_reader(m["name"]))
    reported = [m for m in b["end_to_end"] if applies(m, cell)]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    assert any(applies(m, cell) for m in b["per_layer"])


@pytest.mark.parametrize("metric", [m["name"] for m in metrics()])
def test_metric_lists_only_cells_that_report_it(metric):
    b = bench()
    m = next(x for x in metrics() if x["name"] == metric)
    for cell in m.get("workloads", []):
        assert cell in cells()
    if "moves" in m:
        moved = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
        for cell in m.get("workloads", cells()):
            assert applies(moved, cell)


def test_names_units_and_keys():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for m in metrics():
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    layers = {m["layer"] for m in b["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)
    for c in b["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)
        assert key in load("configs", c["name"] + ".json").get("reduced", {})
    for w in b["workloads"]:
        assert 1 <= len(w["why"]) <= 200


def test_config_files_state_their_checks():
    for c in bench()["configs"]:
        cfg = load("configs", c["name"] + ".json")
        assert cfg["checks"] and all(v > 0 for v in cfg["checks"].values())
