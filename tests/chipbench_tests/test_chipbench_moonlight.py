"""The latent-attention sparse decoder in the benchmark: what came as new
files (``configs/moonlight-16b-a3b.json`` and ``tiny-mla.json``,
``reference_mla.py``, ``counts_mla.py``, two cell files, one reader) held
to the harness that was there.

- the contract's trains-only shape;
- the configuration passes the rules (``config_faults``), repeats every
  number of the published config and states its deployment; the layer
  list is the published first six layers;
- the counts, without jax, against the reckoning the configuration's
  ``deployment`` states: parameters a layer, the step's operations and
  their shares;
- the cell's file has its traffic, scopes and metrics as set;
- the new reader on a made-up reduced trace by hand and on the recorded
  capture of a train step; a program that lacks what it reads gives
  nothing; no share over 100 % can come out of the counts.
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

from chipbench import modules, reduce, work  # noqa: E402
from chipbench import counts_mla as counts  # noqa: E402
from chipbench.run import metric_reader  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CFG = load("configs", "moonlight-16b-a3b.json")
CELL = load("workloads", "moonlight_train8k.json")
TINY = load("configs", "tiny-mla.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAME = "moonlight_train8k"


@pytest.mark.parametrize("cfg", [CFG, TINY], ids=lambda c: c["name"])
def test_the_module_trains_only(cfg):
    mod = modules.reference_of(cfg, training=True)
    assert mod.__name__ == "chipbench.reference_mla"
    with pytest.raises(modules.ContractError) as lacks:
        modules.reference_of(cfg, serving=True)
    assert "exports no served_gaps" in str(lacks.value)
    assert modules.counts_of(cfg) is counts


def test_the_tiny_files_are_no_cell():
    assert "tiny_mla_train" not in [w["name"] for w in MANIFEST["workloads"]]
    assert "tiny-mla" not in [c["name"] for c in MANIFEST["configs"]]
    tiny = load("workloads", "tiny_mla_train.json")
    assert tiny["config"] == "tiny-mla" and tiny["who"].startswith("nobody")


# -- the configuration --------------------------------------------------------

def test_the_configuration_passes_the_rules():
    from test_chipbench_manifest import config_faults
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "moonlight-16b-a3b")
    assert config_faults(entry, CFG) == []
    assert CFG["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (6, 8, 20480)
    assert (CFG["chips_per_layer"], CFG["vocab_shards"],
            CFG["router_width"], CFG["experts_held_first"]) == (8, 8, 64, 0)
    for key, value in CFG["published"].items():
        assert key in CFG, key
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    for key in ("deployment", "assumed", "departures", "precision"):
        assert CFG[key]
    assert "rope_scaling" in CFG["assumed"]
    assert (CFG["reference"], CFG["counts"]) == (
        "chipbench/reference_mla.py", "chipbench/counts_mla.py")


def test_the_catalog_s_row_is_repeated_number_for_number():
    path = os.environ.get("MODEL_CATALOG", "")
    if not os.path.exists(path):
        pytest.skip("MODEL_CATALOG names no architectures.jsonl catalog")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Moonlight-16B-A3B")
    assert CFG["source"] == row["source_url"]
    assert CFG["published"] == row["config"]


def test_the_layers_held_are_the_published_first_six():
    layers = modules.reference_of(CFG).layer_list(CFG, lr=1e-4)
    assert [ly["type"] for ly in layers] == (
        ["embedding"] + ["hybrid_block"] * 6 + ["final_norm", "lm_head"])
    for block in layers[1:7]:
        assert (block["mixer"], block["n_heads"], block["kv_rank"],
                block["qk_nope_dim"], block["qk_rope_dim"],
                block["v_head_dim"], block["rope_base"], block["eps"]) == (
                    "latent", 16, 512, 128, 64, 128, 50000, 1e-5)
    assert (layers[1]["ffn"], layers[1]["dense_hidden"]) == ("dense", 11264)
    for block in layers[2:7]:
        assert (block["ffn"], block["router"], block["n_experts"],
                block["experts_held"], block["top_k"],
                block["expert_hidden"], block["shared_hidden"],
                block["shared_gate"], block["routed_scale"]) == (
                    "experts", "sigmoid", 64, list(range(8)), 6, 1408, 2816,
                    False, 2.446)


# -- the counts ---------------------------------------------------------------

def test_the_counts_are_the_reckoning():
    """One MLA 13,763,072; a routed expert 8,650,752; the shared SwiGLU
    17,301,504; the router 131,072 and its 64 corrections; layer 0
    82,973,184; an expert layer holding 8 of 64 100,405,824; the
    vocabulary's eighth 83,906,560; with the final norm's 2,048 the six
    layers and the vocabulary are 668,910,912 = 10.70 GB at 16 bytes."""
    assert counts.latent_matrix_params(CFG) + 512 == 13_763_072
    assert counts.expert_matrix_params(CFG) == 8_650_752
    assert counts.shared_matrix_params(CFG) == 17_301_504
    assert counts.router_params(CFG) == 131_072 + 64
    assert counts.dense_layer_params(CFG) == 82_973_184
    assert counts.expert_layer_params(CFG) == 100_405_824
    assert counts.vocab_params(CFG) == 83_906_560
    assert counts.model_params(CFG) == 668_908_864 + 2048
    assert 16 * counts.model_params(CFG) / 1e9 == pytest.approx(10.70,
                                                                abs=0.01)
    d = work.dims(CFG)
    assert (d["layers"], d["vocab"], d["window"], d["hd"], d["dk"],
            d["dv"], d["moe_layers"], d["experts_held"],
            d["experts_routed"], d["top_k"], d["f"], d["shared_f"]) == (
                6, 20480, None, 192, 192, 128, 5, 8, 64, 6, 1408, 2816)
    # a latent cache's position: the latent and the rope key, 6 layers
    assert counts.kv_bytes_per_token(CFG, 2) == 576 * 2 * 6


def test_the_step_s_operations_and_their_shares():
    """21.6 TFLOP a step of 8,192 tokens, 110 ms at the peak; attention's
    products 29 %, MLA's projections 19 %, the experts 27 %."""
    t = 8192
    step = work.train_flops_per_token(CFG, t) * t
    assert step / 1e12 == pytest.approx(21.59, abs=0.01)
    assert step / 197e12 * 1e3 == pytest.approx(109.6, abs=0.1)
    attn = 3 * counts.attention_flops_forward(CFG, t, (t + 1) / 2)
    assert attn == 3 * 2.0 * 6 * 16 * t * (t + 1) / 2 * (192 + 128)
    assert attn / step == pytest.approx(0.287, abs=0.001)
    mla = 6 * t * 6 * counts.latent_matrix_params(CFG)
    assert mla / step == pytest.approx(0.188, abs=0.001)
    experts = 6 * t * 5 * (2048 * 64 + counts.shared_matrix_params(CFG)
                           + 6 * 8 / 64 * counts.expert_matrix_params(CFG))
    assert experts / step == pytest.approx(0.272, abs=0.001)


def test_a_layer_s_flash_work_by_hand():
    """Forward QK^T at 192 and PV at 128; backward dV, dP at 128, dQ, dK
    at 192; q, k, dQ, dK at 192 and v, o, dO, dV at 128, bfloat16."""
    ctx = (8192 + 1) / 2
    assert counts.flash_flops(CFG, 8192, 1) == 2.0 * 16 * 8192 * ctx * (
        192 + 128 + 128 + 128 + 192 + 192)
    assert counts.flash_bytes(CFG, 8192, 1) == 16 * 8192 * 2 * (
        4 * 192 + 4 * 128)


# -- the cell -----------------------------------------------------------------

def test_the_cell_file():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == NAME)
    assert entry["chips"] == 1 and entry["config"] == CELL["config"] == (
        "moonlight-16b-a3b")
    assert len(entry["why"]) <= 200
    assert CELL["kind"] == "train"
    assert (CELL["seq_len"], CELL["minibatch"], CELL["rows_per_epoch"],
            CELL["steps_per_dispatch"]) == (8192, 1, 8, 1)
    assert CELL["cli"] == ["--mesh", "data=1", "--mixed-precision"]
    assert (CELL["optimizer"], CELL["learning_rate"], CELL["trace_seconds"],
            CELL["control_precision"]) == ("adam", 1e-4, 6, "float8_e4m3fn")
    assert CELL["scopes"] == {
        "latent": ["latent_q", "latent_kv", "rope", "attn_out"],
        "experts": ["experts"], "route": ["router", "dispatch", "combine"]}
    assert set(CELL["limits"]) == {"loss_gap", "grad_norm_gap",
                                   "delta_norm_gap"}
    assert CELL["limits_from"]


def test_the_cell_s_metrics_are_listed():
    """The cell is on the standing training lists. Its own two metrics,
    ``scope_ms.latent`` and ``latent_flash_roofline``, have readers and no
    entry yet: a new entry goes at the end of ``per_layer``, where
    ``test_chipbench_olmo.py`` holds Olmo's two as the last."""
    assert NAME in next(m for m in MANIFEST["end_to_end"]
                        if m["name"] == "train_tokens_per_s_chip")[
                            "workloads"]
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if NAME in m.get("workloads", ())]
    assert mine == [
        "train_step_ms", "mfu.train", "device_idle_pct.train",
        "flash_step_ms", "scope_ms.experts", "scope_ms.route",
        "expert_tokens_mean", "expert_load_peak"]
    for name in mine + ["scope_ms.latent", "latent_flash_roofline"]:
        assert callable(metric_reader(name))


# -- the reader ---------------------------------------------------------------

def ctx(metric, report, cfg=CFG):
    return {"report": report, "cfg": cfg, "wl": dict(CELL, chips=1),
            "work": work, "peaks": PEAKS, "chips": 1, "metric": metric}


def report(fwd_s=0.5, bwd_s=1.0, steps=10):
    """A made-up slice of 10 steps: each layer's three flash calls, another
    Pallas call beside them, and the latent scopes forward and backward."""
    def call(seconds):
        return {"count": 6 * steps, "seconds": seconds, "operands": [],
                "results": []}
    return {"slice": {"steps": steps, "tokens": 8192 * steps},
            "trace": {"kernels": {
                "veles_flash_fwd.3": call(fwd_s),
                "veles_flash_bwd_dkv.4": call(bwd_s / 2),
                "veles_flash_bwd_dq.5": call(bwd_s / 2),
                "full_attn.6": call(9.0)},
                "scopes": {"jit__train_step_fn": {
                    "forward/latent_q": [60, 0.1],
                    "forward/latent_kv": [60, 0.05],
                    "forward/rope": [60, 0.02],
                    "forward/attn_out": [60, 0.08],
                    "backward/latent_q": [60, 0.2],
                    "backward/attn": [60, 0.3],
                    "veles_flash_fwd": [60, 0.5]}}}}


def test_the_roofline_by_hand():
    flops = 6 * 2.0 * 16 * 8192 * 4096.5 * (3 * 192 + 3 * 128) * 10
    nbytes = 6 * 16 * 8192 * 2 * (4 * 192 + 4 * 128) * 10
    least = max(flops / 197e12, nbytes / 819e9)
    assert flops / 197e12 > nbytes / 819e9, "bound by the operations"
    got = metric_reader("latent_flash_roofline")(
        ctx("latent_flash_roofline", report()))
    assert got == pytest.approx(100.0 * least / 1.5)
    assert 0 < got <= 100.0
    ms = metric_reader("scope_ms.latent")(ctx("scope_ms.latent", report()))
    assert ms == pytest.approx(1000.0 * (0.1 + 0.05 + 0.02 + 0.08 + 0.2)
                               / 10)


def test_no_share_over_100_at_the_chip_s_own_peak():
    least = 6 * counts.flash_flops(CFG, 8192, 10) / 197e12
    got = metric_reader("latent_flash_roofline")(
        ctx("latent_flash_roofline", report(fwd_s=least / 3,
                                            bwd_s=2 * least / 3)))
    assert got == pytest.approx(100.0)


def test_a_program_without_the_calls_or_the_counts_gives_nothing():
    reader = metric_reader("latent_flash_roofline")
    r = report()
    r["trace"]["kernels"] = {"full_attn.6": r["trace"]["kernels"][
        "full_attn.6"]}
    assert reader(ctx("latent_flash_roofline", r)) is None
    assert reader(dict(ctx("latent_flash_roofline", report()),
                       peaks=None)) is None
    r = report()
    del r["trace"]
    assert reader(ctx("latent_flash_roofline", r)) is None
    qwen = load("configs", "qwen3-next-80b-a3b.json")
    assert reader(ctx("latent_flash_roofline", report(), cfg=qwen)) is None
    r = report()
    r["trace"]["scopes"] = None
    assert metric_reader("scope_ms.latent")(ctx("scope_ms.latent", r)) \
        is None


def test_the_reader_finds_the_calls_of_a_recorded_capture_by_name():
    """The recorded train step (internlm2_train4k's, 8 layers, 24 Pallas
    calls) was captured before the kernels had names: the reader finds
    nothing there and says so by giving nothing. Given the names, it finds
    the same calls whatever their shapes and charges them this
    configuration's work of one step."""
    recorded = load("testdata", "trace_train_step.json")
    t = reduce.reduce_trace(recorded["planes"], recorded["window_s"])
    r = {"trace": t, "slice": {"steps": 1, "tokens": 8192}}
    reader = metric_reader("latent_flash_roofline")
    assert len(t["kernels"]) == 24
    assert reader(ctx("latent_flash_roofline", r)) is None
    t["kernels"] = {"veles_flash_fwd.%d" % i: k
                    for i, k in enumerate(t["kernels"].values())}
    seconds = sum(k["seconds"] for k in t["kernels"].values())
    least = 6 * counts.flash_flops(CFG, 8192, 1) / 197e12
    assert reader(ctx("latent_flash_roofline", r)) == pytest.approx(
        100.0 * least / seconds)
