"""BENCHMARK.json against what the driver refuses before any run.

Every name, unit, layer, file and cross-reference is one parametrised
case, so a manifest that would be sent back as ``manifest_invalid`` fails
here first, on the CPU.
"""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    M = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LAYERS = {"launcher", "request_plane", "scheduler", "engine", "train_step",
          "kernels", "device"}
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = {w["name"]: w for w in M["workloads"]}
CONFIGS = {c["name"]: c for c in M["configs"]}
METRICS = M["end_to_end"] + M["per_layer"]


def line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_check_fits_the_day():
    """2 + 14 x cells runs of run_seconds + 60, 180 s a cell to compile and
    1200 spare, with the full 24 cells, inside 43200 s."""
    cells = 24
    total = ((2 + 14 * cells) * (M["run_seconds"] + 60) + cells * 180 + 1200)
    assert total <= 43200


@pytest.mark.parametrize("path", M["paths"])
def test_path(path):
    assert PATH.match(path) and not path.startswith("/")
    assert ".." not in path.split("/")
    assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("word", M["command"])
def test_command_word(word):
    assert line(word) and not word.startswith("/") and ".." not in word
    if os.path.exists(os.path.join(ROOT, word)):
        assert any(word.startswith(p + "/") for p in M["paths"])


def test_counts():
    assert 1 <= len(M["paths"]) <= 16 and 1 <= len(M["command"]) <= 32
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16
    assert 1 <= len(M["per_layer"]) <= 128


@pytest.mark.parametrize("name", sorted(
    [m["name"] for m in METRICS] + list(CELLS) + list(CONFIGS)
    + [w["traffic"] for w in M["workloads"]]
    + [k for c in M["configs"] for k in c["reduced"]]))
def test_name(name):
    assert NAME.match(name), name


def test_names_are_unique():
    for group in ([m["name"] for m in METRICS], list(CELLS), list(CONFIGS)):
        assert len(group) == len(set(group))
    assert len(CELLS) == len(M["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", ()):
        assert cell in CELLS


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


def test_setup_s_is_there_for_every_cell():
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["layer"]), "a layer is one token, no space"
    assert metric["layer"] in LAYERS
    assert metric["moves"] in E2E
    moved = E2E[metric["moves"]]
    for cell in CELLS:
        if reports(metric, cell):
            assert reports(moved, cell), (
                "%s is read in %s, which does not report %s"
                % (metric["name"], cell, metric["moves"]))
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and line(cell["why"])
    assert cell["config"] in CONFIGS
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           cell["name"] + ".json")) as f:
        wl = json.load(f)
    # the cell's file carries its own flags for the program's CLI: run.py
    # passes them on as they stand and knows none of them
    assert wl["config"] == cell["config"] and wl["kind"] in ("train", "serve")
    assert all(isinstance(a, str) for a in wl["cli"]) and "--mesh" in wl["cli"]
    mine = [m for m in M["end_to_end"] if reports(m, cell["name"])]
    assert any(m["name"] != "setup_s" for m in mine)
    assert any(reports(m, cell["name"]) for m in M["per_layer"])
    kernels = [m for m in M["per_layer"] if reports(m, cell["name"])
               and m["name"].endswith("_roofline")]
    for k in kernels:
        assert any("mfu" in m["name"] and m["moves"] == k["moves"]
                   and reports(m, cell["name"]) for m in M["per_layer"])


def test_four_chip_cells_are_few():
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert line(config["source"]) and line(config["why"])
    assert PATH.match(config["file"])
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert len(config["reduced"]) <= 16
    assert any(w["config"] == config["name"] for w in M["workloads"])
    with open(os.path.join(ROOT, config["file"])) as f:
        sizes = json.load(f)
    assert sizes["reduced"] == config["reduced"]
    assert sizes["source"] == config["source"]
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), \
            "a width (the vocabulary is the head's) is never reduced: %s" % key
    published = sizes["published"]
    for key, value in published.items():
        if key in sizes and key not in config["reduced"]:
            assert sizes[key] == value, key
    for key in config["reduced"]:
        assert sizes[key] != published[key]
    files = [c["file"] for c in M["configs"]]
    assert files.count(config["file"]) == 1


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    stems = (metric["name"], metric["name"].split(".")[0])
    assert any(os.path.exists(os.path.join(
        ROOT, "chipbench", "metrics", s + ".py")) for s in stems)
