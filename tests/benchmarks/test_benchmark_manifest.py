"""BENCHMARK.json against its contract, and against the benchmark's files."""

import json
import os
import re

import pytest

from benchmarks.lib import tables
from benchmarks.lib.tables import ROOT

MANIFEST = tables.manifest()

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert len(MANIFEST["command"]) <= 32
    for path in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path) and ".." not in path
        assert os.path.isdir(os.path.join(ROOT, path))
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:  # end to end
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves",
                               "workloads"}
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
        family = metric["name"].split(".")[0]
        assert os.path.isfile(
            os.path.join(ROOT, "benchmarks", "layer_metrics", family + ".py"))
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_no_two_names_alike():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[kind]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and 1 <= len(config["why"]) <= 200
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        stated = json.load(f)
    assert stated["name"] == config["name"]
    assert stated["source"] == config["source"] and len(config["source"]) <= 200
    assert stated["reduced"] == config["reduced"]
    widths = ("_dim", "_rank", "hidden", "intermediate", "head", "mlp_ratio")
    assert not [k for k in config["reduced"] if any(w in k for w in widths)]
    assert config["name"] in {w["config"] for w in MANIFEST["workloads"]}


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    stated = tables.load("workloads", cell["name"])
    assert (stated["config"], stated["traffic"], stated["chips"]) == (
        cell["config"], cell["traffic"], cell["chips"])
    traffic = tables.load("traffic", cell["traffic"])
    assert os.path.isfile(
        os.path.join(ROOT, "benchmarks", "drivers", traffic["driver"] + ".py"))
    # the cell reports set-up, one more end-to-end metric and a layer metric,
    # and the manifest lists the cell under each of them
    by_name = {m["name"]: m for m in METRICS}
    rate = by_name[stated["end_to_end"]["rate"]]
    assert cell["name"] in rate["workloads"]
    assert stated["per_layer"]
    for name in stated["per_layer"]:
        assert cell["name"] in by_name[name]["workloads"]
        assert by_name[name]["moves"] == rate["name"]
    for metric in MANIFEST["per_layer"]:
        if cell["name"] in metric.get("workloads", ()):
            assert metric["name"] in stated["per_layer"]
    limits = stated["correct"]["limits"]
    assert limits and all(v > 0 for v in limits.values())
