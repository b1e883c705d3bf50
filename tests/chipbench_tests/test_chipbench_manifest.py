"""BENCHMARK.json against what the driver refuses before any run.

Every name, unit, layer, file and cross-reference is one parametrised
case, so a manifest that would be sent back as ``manifest_invalid`` fails
here first, on the CPU.

The rules on a configuration (``config_faults``) go further than the
driver's refusal list: what ``reduced`` may name and how far it may cut is
the ``model-configs`` guide's section 4, where a configuration is one
chip's share of a stated deployment (its experts, its heads, its slice of
the vocabulary), held to that guide's floors.
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


#: keys of ``reduced`` by what they count; a width is none of them
EXPERTS = ("num_experts", "n_routed_experts", "num_local_experts")
HEADS = re.compile(r"(^|_)heads?$")
DEPTH, VOCAB = "num_hidden_layers", "vocab_size"
LEAST_DEPTH, LEAST_EXPERTS, LEAST_HEADS, VOCAB_SHARE = 4, 8, 1, 8


def whole(value, least):
    return (isinstance(value, int) and not isinstance(value, bool)
            and value >= least)


def floor_of(key, published):
    """(the least a reduced ``key`` may be, that in words), or (None, None)
    for a key whose count the guide gives no floor."""
    if key == DEPTH:
        return LEAST_DEPTH, "%d layers" % LEAST_DEPTH
    if key == VOCAB and whole(published, 1):
        return -(-published // VOCAB_SHARE), "an eighth of the vocabulary"
    if key in EXPERTS:
        return LEAST_EXPERTS, "%d experts" % LEAST_EXPERTS
    if HEADS.search(key):
        return LEAST_HEADS, "%d head" % LEAST_HEADS
    return None, None


def config_faults(entry, sizes):
    """What is wrong with one configuration: ``entry`` as ``BENCHMARK.json``
    has it and ``sizes``, its file. No fault is the empty list.

    No width is ever reduced. Depth may be, and what one chip of a stated
    deployment holds of a layer: its experts, its heads, its rows of the
    vocabulary (the guide's section 4), each no further than its floor, and
    only so far that ``chips_per_layer`` such shares (for the vocabulary
    ``vocab_shards``, where the file gives it) make up the published count."""
    faults = []
    if set(entry) != {"name", "source", "file", "reduced", "why"}:
        faults.append("the entry's keys are %s" % sorted(entry))
    for key in ("source", "why"):
        if not line(entry.get(key)):
            faults.append("%s is no line of 1 to 200 characters" % key)
    if not PATH.match(entry.get("file", "")):
        faults.append("file is no path: %r" % entry.get("file"))
    reduced = entry.get("reduced", [])
    if len(reduced) > 16:
        faults.append("reduced has %d keys, over 16" % len(reduced))
    if sizes.get("reduced") != reduced:
        faults.append("the file's reduced is not the entry's")
    if sizes.get("source") != entry.get("source"):
        faults.append("the file's source is not the entry's")
    published = sizes.get("published", {})
    for key, value in published.items():
        if key in sizes and key not in reduced and sizes[key] != value:
            faults.append("%s differs from its published value and is not "
                          "in reduced" % key)
    chips = sizes.get("chips_per_layer")
    shards = {VOCAB: sizes.get("vocab_shards", chips)}
    for key in reduced:
        if key not in sizes or key not in published:
            faults.append("%s is reduced from no published value" % key)
            continue
        here, there = sizes[key], published[key]
        if here == there:
            faults.append("%s is in reduced and equals its published value"
                          % key)
        if (key.endswith(("_dim", "_rank", "_size")) and key != VOCAB) \
                or key.endswith("_per_tok"):
            faults.append("a width is never reduced: %s" % key)
            continue
        least, what = floor_of(key, there)
        if least is not None and not whole(here, least):
            faults.append("%s is %r, under the floor of %s"
                          % (key, here, what))
        if key == DEPTH:
            continue
        # anything but the depth is this chip's share of a layer
        over = shards.get(key, chips)
        if whole(chips, 2) and whole(over, 1) and whole(here, 1) \
                and here * over < there:
            faults.append("%s: %d shares of %r are less than the published "
                          "%r: what is held here is a share, not a smaller "
                          "model" % (key, over, here, there))
    if set(reduced) - {DEPTH}:
        if not (isinstance(sizes.get("deployment"), str)
                and sizes["deployment"].strip()):
            faults.append("a share of a layer states its deployment")
        if not whole(chips, 2):
            faults.append("a share of a layer states chips_per_layer, a "
                          "whole number of 2 or more: %r" % chips)
        if "vocab_shards" in sizes and not whole(sizes["vocab_shards"], 1):
            faults.append("vocab_shards is no whole number: %r"
                          % sizes["vocab_shards"])
    return faults


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config(config):
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert any(w["config"] == config["name"] for w in M["workloads"])
    with open(os.path.join(ROOT, config["file"])) as f:
        sizes = json.load(f)
    assert config_faults(config, sizes) == []
    files = [c["file"] for c in M["configs"]]
    assert files.count(config["file"]) == 1


#: the published sizes of two sparse models, as far as the rules read them
SPARSE_512 = {"hidden_size": 2048, "moe_intermediate_size": 512,
              "head_dim": 256, "num_attention_heads": 16,
              "num_key_value_heads": 2, "num_experts": 512,
              "num_experts_per_tok": 10, "num_hidden_layers": 48,
              "vocab_size": 151936}
SPARSE_384 = {"hidden_size": 7168, "moe_intermediate_size": 2048,
              "kv_lora_rank": 512, "num_attention_heads": 64,
              "n_routed_experts": 384, "num_experts_per_tok": 8,
              "num_hidden_layers": 61, "vocab_size": 163840}


def made_up(published, cut, **stated):
    """(entry, file) of a configuration that no manifest names: every
    published size but those of ``cut``, which are reduced to its values;
    ``stated`` is what else the file says (``chips_per_layer``, ...)."""
    entry = {"name": "made-up", "source": "https://example.org/config.json",
             "file": "chipbench/configs/made-up.json",
             "reduced": list(cut), "why": "a case of the rules"}
    sizes = dict(published, **cut)
    sizes.update(published=dict(published), reduced=list(cut),
                 source=entry["source"],
                 deployment="each layer divided over the chips stated")
    sizes.update(stated)
    return entry, {k: v for k, v in sizes.items() if v is not None}


SHARE_512 = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
SHARE_384 = {"num_hidden_layers": 4, "n_routed_experts": 12,
             "vocab_size": 20480}


@pytest.mark.parametrize("published,cut,stated", [
    (SPARSE_512, SHARE_512, {"chips_per_layer": 16, "vocab_shards": 8}),
    (SPARSE_384, SHARE_384, {"chips_per_layer": 32, "vocab_shards": 8}),
    (SPARSE_512, {"num_hidden_layers": 4}, {"deployment": None}),
    (SPARSE_512, {"num_experts": 64, "num_attention_heads": 2,
                  "num_key_value_heads": 1, "vocab_size": 18992},
     {"chips_per_layer": 8}),
], ids=["one_of_16_chips_32_of_512_experts_an_eighth_of_the_vocabulary",
        "one_of_32_chips_12_of_384_experts_an_eighth_of_the_vocabulary",
        "depth_alone_states_no_deployment",
        "heads_and_experts_over_8_chips_the_vocabulary_with_them"])
def test_a_share_the_rules_admit(published, cut, stated):
    assert config_faults(*made_up(published, cut, **stated)) == []


SIXTEEN = {"chips_per_layer": 16, "vocab_shards": 8}


@pytest.mark.parametrize("published,cut,stated,fault", [
    (SPARSE_512, {"hidden_size": 1024}, SIXTEEN,
     "a width is never reduced: hidden_size"),
    (SPARSE_512, {"moe_intermediate_size": 256}, SIXTEEN,
     "a width is never reduced: moe_intermediate_size"),
    (SPARSE_512, {"head_dim": 128}, SIXTEEN,
     "a width is never reduced: head_dim"),
    (SPARSE_384, {"kv_lora_rank": 256}, SIXTEEN,
     "a width is never reduced: kv_lora_rank"),
    (SPARSE_512, {"num_experts_per_tok": 2}, SIXTEEN,
     "a width is never reduced: num_experts_per_tok"),
    (SPARSE_512, dict(SHARE_512, vocab_size=18991), {"chips_per_layer": 16},
     "vocab_size is 18991, under the floor of an eighth of the vocabulary"),
    (SPARSE_512, dict(SHARE_512, num_experts=7),
     {"chips_per_layer": 128, "vocab_shards": 8},
     "num_experts is 7, under the floor of 8 experts"),
    (SPARSE_512, dict(SHARE_512, num_hidden_layers=3), SIXTEEN,
     "num_hidden_layers is 3, under the floor of 4 layers"),
    (SPARSE_512, {"num_key_value_heads": 0}, SIXTEEN,
     "num_key_value_heads is 0, under the floor of 1 head"),
    (SPARSE_512, SHARE_512, {"vocab_shards": 8},
     "a share of a layer states chips_per_layer"),
    (SPARSE_512, SHARE_512, {"chips_per_layer": 1, "vocab_shards": 8},
     "a share of a layer states chips_per_layer"),
    (SPARSE_512, SHARE_512, dict(SIXTEEN, deployment=""),
     "a share of a layer states its deployment"),
    (SPARSE_512, SHARE_512, {"chips_per_layer": 8, "vocab_shards": 8},
     "num_experts: 8 shares of 32 are less than the published 512"),
    (SPARSE_512, SHARE_512, {"chips_per_layer": 16, "vocab_shards": 4},
     "vocab_size: 4 shares of 18992 are less than the published 151936"),
    (SPARSE_512, dict(SHARE_512, num_experts=512), SIXTEEN,
     "num_experts is in reduced and equals its published value"),
    (SPARSE_512, dict(SHARE_512, rope_theta=1e6), SIXTEEN,
     "rope_theta is reduced from no published value"),
], ids=["hidden_size", "moe_intermediate_size", "head_dim", "kv_lora_rank",
        "num_experts_per_tok", "vocabulary_under_an_eighth", "7_experts",
        "depth_3", "no_head", "no_chips_per_layer", "one_chip_a_layer",
        "no_deployment", "experts_are_no_share", "vocabulary_is_no_share",
        "reduced_equals_published", "reduced_from_nothing"])
def test_a_configuration_the_rules_refuse(published, cut, stated, fault):
    """Each refusal has its own message, and a case that breaks one rule
    meets that rule and no other."""
    faults = config_faults(*made_up(published, cut, **stated))
    assert len(faults) == 1 and faults[0].startswith(fault), faults


def test_an_unreduced_key_equals_its_published_value():
    entry, sizes = made_up(SPARSE_512, SHARE_512, **SIXTEEN)
    sizes["num_attention_heads"] = 8
    assert config_faults(entry, sizes) == [
        "num_attention_heads differs from its published value and is not in "
        "reduced"]
    entry["source"] = "another"
    assert "the file's source is not the entry's" in config_faults(
        entry, sizes)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    stems = (metric["name"], metric["name"].split(".")[0])
    assert any(os.path.exists(os.path.join(
        ROOT, "chipbench", "metrics", s + ".py")) for s in stems)
