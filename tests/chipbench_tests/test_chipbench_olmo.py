"""The post-norm delta-rule hybrid in the benchmark: what came as new files
(``configs/olmo-hybrid-7b.json`` and ``tiny-olmo.json``,
``reference_olmo.py``, ``counts_olmo.py``, two cell files, one reader) held
to the harness that was there.

- the contract's serves-only shape;
- the configuration passes the rules (``config_faults``), repeats every
  number of the published config, keeps ``layer_types`` whole and states
  its deployment; the layers held are two whole periods;
- the counts, without jax, against the issue's reckoning at the cell's
  sizes;
- the cell's file has the issue's traffic letter for letter, and the
  metrics that list it are pinned;
- the new reader on a made-up reduced trace and made-up counters, by hand;
  a program that lacks what it reads gives nothing; no share over 100 %
  can come out of the counts at the chip's bandwidth;
- the run itself (``tiny_olmo_serve --rehearse``) is driven by
  ``test_chipbench_correct.py``, which takes every rehearsal serving cell.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import modules, work  # noqa: E402
from chipbench import counts_olmo as counts  # noqa: E402
from chipbench.run import metric_reader  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CFG = load("configs", "olmo-hybrid-7b.json")
CELL = load("workloads", "olmo_hybrid_decode_sat.json")
TINY = load("configs", "tiny-olmo.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAME = "olmo_hybrid_decode_sat"


@pytest.fixture(autouse=True)
def host_draw_restored():
    from veles_tpu import prng
    keep = prng.RandomGenerator.fill_normal
    yield
    prng.RandomGenerator.fill_normal = keep


@pytest.mark.parametrize("cfg", [CFG, TINY], ids=lambda c: c["name"])
def test_the_module_serves_only(cfg):
    mod = modules.reference_of(cfg, serving=True)
    assert mod.__name__ == "chipbench.reference_olmo"
    with pytest.raises(modules.ContractError) as lacks:
        modules.reference_of(cfg, training=True)
    assert "exports no train_reference" in str(lacks.value)
    assert modules.counts_of(cfg) is counts


def test_the_tiny_files_are_no_cell():
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert "tiny_olmo_serve" not in names
    assert "tiny-olmo" not in [c["name"] for c in MANIFEST["configs"]]
    tiny = load("workloads", "tiny_olmo_serve.json")
    assert tiny["config"] == "tiny-olmo" and tiny["who"].startswith("nobody")


# -- the configuration --------------------------------------------------------

def test_the_configuration_passes_the_rules():
    from test_chipbench_manifest import config_faults
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    assert config_faults(entry, CFG) == []
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 8
    assert "chips_per_layer" not in CFG, "no layer is shared"
    for key, value in CFG["published"].items():
        assert key in CFG, key
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # the list is the published one, whole; its first 8 are held
    assert len(CFG["layer_types"]) == CFG["published"][
        "num_hidden_layers"] == 32
    for key in ("deployment", "assumed", "departures", "precision"):
        assert CFG[key]
    assert CFG["weights_dtype"] == "bfloat16"


def test_the_catalog_s_row_is_repeated_number_for_number():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert CFG["source"] == row["source_url"]
    assert CFG["published"] == row["config"]


def test_the_layers_held_are_two_whole_periods():
    mod = modules.reference_of(CFG)
    kinds = counts.layer_kinds(CFG)
    assert kinds == mod.layer_kinds(CFG) == (["linear"] * 3 + ["full"]) * 2
    layers = mod.layer_list(CFG)
    assert [ly["type"] for ly in layers] == (
        ["embedding"] + ["hybrid_block"] * 8 + ["final_norm", "lm_head"])
    linear, full = layers[1], layers[4]
    assert (linear["mixer"], linear["n_key_heads"], linear["n_value_heads"],
            linear["key_dim"], linear["value_dim"], linear["conv_taps"],
            linear["beta_scale"]) == ("delta_rule", 30, 30, 96, 192, 4, 2.0)
    assert (full["mixer"], full["n_heads"], full["n_kv_heads"],
            full["head_dim"], full["rotary_factor"], full["qk_norm"]) == (
                "softmax", 30, 30, 128, 0.0, True)
    for block in (linear, full):
        assert (block["norm"], block["ffn"], block["dense_hidden"],
                block["eps"]) == ("post", "dense", 11008, 1e-6)


# -- the counts ---------------------------------------------------------------

def test_the_counts_are_the_issue_s_reckoning():
    """Section 3 of the issue: a delta-rule layer 88.75 M + 126.81 M, a
    full layer 58.98 M + 126.81 M, the 8 layers 1,665 M, embedding and head
    770.7 M, 2,436 M in all = 4.87 GB of bfloat16; a position 30,720 bytes
    in the full layers; a slot's state 6 x 2.21 MB."""
    M = 1e6
    assert counts.linear_matrix_params(CFG) / M == pytest.approx(88.70, abs=.01)
    assert counts.layer_params(CFG, "linear") / M == pytest.approx(
        215.6, abs=0.1)
    assert counts.swiglu_matrix_params(CFG) / M == pytest.approx(
        126.81, abs=0.01)
    assert counts.full_matrix_params(CFG) / M == pytest.approx(58.98, abs=.01)
    assert counts.layer_params(CFG, "full") / M == pytest.approx(
        185.8, abs=0.1)
    blocks = sum(counts.layer_params(CFG, k) for k in counts.layer_kinds(CFG))
    assert blocks / M == pytest.approx(1665, abs=1)
    assert 2 * 100352 * 3840 / M == pytest.approx(770.7, abs=0.1)
    assert counts.model_params(CFG) / M == pytest.approx(2436, abs=1)
    assert 2 * counts.model_params(CFG) / 1e9 == pytest.approx(4.87, abs=.01)
    assert counts.kv_bytes_per_token(CFG, 2) == 30720
    assert 4 * counts.state_elements(CFG) / M == pytest.approx(2.21, abs=.01)
    tails = 6 * 3 * 11520 * 2
    assert counts.state_bytes_per_slot(CFG) == 6 * 4 * 30 * 96 * 192 + tails
    # a decode step's budget: 64 rows' states read and written, 1.70 GB,
    # and their q, k, v, g, beta and o beside them
    assert 2 * 4 * counts.state_elements(CFG) * 64 * 6 / 1e9 == (
        pytest.approx(1.70, abs=0.005))
    assert counts.delta_step_bytes(CFG, 64 * 6) / 1e9 == pytest.approx(
        1.725, abs=0.001)
    # the dims every module gives
    d = work.dims(CFG)
    assert (d["layers"], d["vocab"], d["window"]) == (8, 100352, None)


def test_a_token_s_operations_count_the_delta_rule():
    """The delta rule's mixing is in ``attention_flops_forward`` at any
    context; the full layers' products grow with it."""
    one = counts.attention_flops_forward(CFG, 1, 0)
    assert one == counts.delta_flops_per_token(CFG) == (
        7 * 30 * 96 * 192 * 6)
    assert counts.attention_flops_forward(CFG, 1, 100) == one + (
        4.0 * 2 * 30 * 128 * 100)


# -- the cell -----------------------------------------------------------------

def test_the_cell_file():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == NAME)
    assert entry["chips"] == 1 and entry["config"] == CELL["config"] == (
        "olmo-hybrid-7b")
    assert CELL["kind"] == "serve"
    assert CELL["traffic"] == {
        "loop": "closed", "clients": 64, "pool": 128, "pool_seed": 7,
        "prompt_len": {"dist": "uniform", "min": 128, "max": 1024},
        "output_len": {"dist": "uniform", "min": 256, "max": 512},
        "sampled_share": 0.5, "temperature": 0.8}
    assert CELL["cli"] == [
        "--mesh", "data=1", "--serve-generate", "0", "--serve-slots", "64",
        "--serve-buckets", "256,512,1024", "--serve-max-context", "1536",
        "root.common.engine.precision_type=bfloat16"]
    assert (CELL["check_requests"], CELL["check_pad"],
            CELL["trace_seconds"]) == (6, 1536, 6)
    assert CELL["control_precision"] == "float8_e4m3fn"
    assert set(CELL["limits"]) == {"served_logit_gap"}
    assert CELL["scopes"]["serve_delta"] == ["delta_rule"]


def test_the_cell_s_metrics_are_pinned():
    assert NAME in next(m for m in MANIFEST["end_to_end"]
                        if m["name"] == "out_tokens_per_s")["workloads"]
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if NAME in m.get("workloads", ())]
    assert mine == [
        "decode_batch_mean", "decode_step_ms", "mfu.decode",
        "device_idle_pct.decode", "tick_ms.admit", "tick_ms.prefill",
        "tick_ms.prepare", "tick_ms.dispatch", "tick_ms.device",
        "tick_ms.emit", "tick_ms.unaccounted", "stream_write_ms",
        "scope_ms.page_writeback", "scope_ms.page_gather",
        "unfed_ms.first_token", "unfed_ms.drain", "request_ms.queue",
        "request_ms.prefill", "request_ms.ttft", "request_ms.first_write",
        "prefill_positions_mean", "scope_ms.serve_delta",
        "serve_delta_roofline"]
    new = MANIFEST["per_layer"][-2:]
    assert [m["name"] for m in new] == ["scope_ms.serve_delta",
                                        "serve_delta_roofline"]
    for m in new:
        assert (m["moves"], m["layer"], m["source"], m["workloads"]) == (
            "out_tokens_per_s", "kernels", "device_trace", [NAME])
    assert new[1]["unit"] == "%" and new[1]["better"] == "higher"
    # every metric that lists the cell has a reader
    for name in mine:
        assert callable(metric_reader(name))


def test_the_warm_up_builds_every_bucket_and_both_rungs():
    from chipbench import traffic
    from veles_tpu.serving.pages import pages_for, view_ladder, view_rung
    warm = traffic.warmup_requests(CELL["traffic"], [256, 512, 1024],
                                   CFG["vocab_size"])
    lengths = sorted({len(w["prompt"]) for w in warm})
    assert lengths == [256, 512, 1024]
    ladder = view_ladder(pages_for(1536, 16), 16, 256)
    assert ladder == (96, 48)
    assert {view_rung(ladder, n + 4, 16) for n in lengths} == {96, 48}
    sizes = traffic.pool(CELL["traffic"], CELL["traffic"]["pool"])
    assert max(p + n for p, n, _ in sizes) <= CELL["check_pad"] == 1536


# -- the reader ---------------------------------------------------------------

def ctx(metric, report):
    return {"report": report, "cfg": CFG, "wl": dict(CELL, chips=1),
            "work": work, "peaks": PEAKS, "chips": 1, "metric": metric}


def report(rows=64 * 6 * 100, seconds=0.5, steps=100):
    """A made-up slice: 100 decode steps of 64 live rows, the update's
    scopes in ``jit_step`` (two blocks' worth), a prefill's beside them."""
    return {"slice": {"counters": {
                "veles_serving_state_rows_total": rows}},
            "trace": {"scopes": {
                "jit_step(1)": {"blk0/delta_rule": [100, seconds / 2],
                                "blk1/delta_rule": [100, seconds / 2],
                                "blk3/full_attn": [100, 0.2]},
                "jit_prefill(2)": {"blk0/delta_rule": [10, 9.0]}},
                "modules": {"jit_step(1)": [steps, 1.5],
                            "jit_prefill(2)": [10, 20.0]}}}


def test_the_roofline_by_hand():
    r = report()
    rows = 64 * 6 * 100
    flops = 7 * 30 * 96 * 192 * rows
    nbytes = rows * (8 * 30 * 96 * 192 + 4 * 30 * (2 * 96 + 2 * 192 + 2))
    least = max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12, "bound by the bytes"
    got = metric_reader("serve_delta_roofline")(ctx("serve_delta_roofline",
                                                    r))
    assert got == pytest.approx(100.0 * least / 0.5)
    assert 0 < got <= 100.0
    ms = metric_reader("scope_ms.serve_delta")(ctx("scope_ms.serve_delta", r))
    assert ms == pytest.approx(1000.0 * 0.5 / 100)


def test_a_program_without_the_counter_or_the_scope_gives_nothing():
    reader = metric_reader("serve_delta_roofline")
    r = report()
    r["slice"]["counters"] = {}
    assert reader(ctx("serve_delta_roofline", r)) is None
    r = report()
    r["trace"]["scopes"]["jit_step(1)"] = {"blk3/full_attn": [100, 0.2]}
    assert reader(ctx("serve_delta_roofline", r)) is None
    assert metric_reader("scope_ms.serve_delta")(
        ctx("scope_ms.serve_delta", r)) is None
    r = report()
    r["trace"]["scopes"] = None
    assert reader(ctx("serve_delta_roofline", r)) is None
    assert reader(dict(ctx("serve_delta_roofline", report()),
                       peaks=None)) is None


def test_no_share_over_100_at_the_chip_s_own_bandwidth():
    """The least time is the state's bytes at 819 GB/s: a device time as
    short as that reads 100 %, and no shorter time is possible."""
    rows = 64 * 6 * 100
    floor = counts.delta_step_bytes(CFG, rows) / 819e9
    got = metric_reader("serve_delta_roofline")(
        ctx("serve_delta_roofline", report(rows=rows, seconds=floor)))
    assert got == pytest.approx(100.0)
