"""The sparse linear-attention hybrid in the benchmark: what came as new
files (``configs/qwen3-next-80b-a3b.json`` and ``tiny-hybrid.json``,
``reference_hybrid.py``, ``counts_hybrid.py``, two cell files, four
readers) held to the harness that was there.

- the contract's trains-only shape: the module loads, gives the training
  half, and a ``serve`` cell on it stops with one plain line;
- the counts, without jax, against hand counts at the cell's sizes;
- the configuration passes the rules on a share (``config_faults``) and
  repeats every number of the published config;
- a ``--rehearse`` run of ``tiny_hybrid_train`` is ``correct``, and with
  half the batch left out underneath it is not;
- the four new readers on a made-up reduced trace and made-up counters,
  by hand; a program or a configuration that lacks what they read gives
  nothing; the least time they divide is under what a chunked form of the
  same work takes at the chip's peak, so a share over 100 % cannot come
  out of the counts.
"""
import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import modules, work  # noqa: E402
from chipbench import counts_hybrid as counts  # noqa: E402
from chipbench.run import metric_reader  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CFG = load("configs", "qwen3-next-80b-a3b.json")
CELL = load("workloads", "qwen3next_train8k.json")
TINY = load("configs", "tiny-hybrid.json")
TINY_TRAIN = dict(load("workloads", "tiny_hybrid_train.json"), chips=1)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- the contract -------------------------------------------------------------

@pytest.mark.parametrize("cfg", [CFG, TINY], ids=lambda c: c["name"])
def test_the_module_trains_only(cfg):
    mod = modules.reference_of(cfg, training=True)
    for name in modules.REFERENCE + modules.TRAINING:
        assert callable(getattr(mod, name))
    assert not hasattr(mod, "served_gaps")
    with pytest.raises(modules.ContractError) as e:
        modules.reference_of(cfg, serving=True)
    assert "SERVING" in str(e.value) and "served_gaps" in str(e.value)
    assert modules.counts_of(cfg) is counts


def test_the_counts_import_no_jax():
    run = subprocess.run(
        [sys.executable, "-c",
         "import sys, json; sys.path.insert(0, %r); "
         "from chipbench import work; "
         "cfg = json.load(open('chipbench/configs/qwen3-next-80b-a3b.json'));"
         " print(work.model_params(cfg), work.dims(cfg)['layers']); "
         "assert 'jax' not in sys.modules" % ROOT],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-1000:]
    assert run.stdout.split() == ["625684080", "4"]


def test_the_tiny_files_are_no_cell():
    assert "tiny_hybrid_train" not in [w["name"]
                                       for w in MANIFEST["workloads"]]
    assert "tiny-hybrid" not in [c["name"] for c in MANIFEST["configs"]]
    assert TINY_TRAIN["config"] == "tiny-hybrid"


# -- the configuration --------------------------------------------------------

def test_the_configuration_is_a_share_the_rules_admit():
    from test_chipbench_manifest import config_faults
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert config_faults(entry, CFG) == []
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (4, 32, 18992)
    assert (CFG["chips_per_layer"], CFG["vocab_shards"]) == (16, 8)
    # every number of the published config under its own key, unchanged
    # but for the three that are reduced
    for key, value in CFG["published"].items():
        assert key in CFG, key
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # the router is as wide as published and keeps its experts a token
    assert CFG["num_routed_experts"] == CFG["published"]["num_experts"] == 512
    assert CFG["num_experts_per_tok"] == 10
    assert CFG["num_experts"] * CFG["chips_per_layer"] == 512
    assert CFG["vocab_size"] * CFG["vocab_shards"] == 151936
    for key in ("deployment", "assumed", "departures", "precision"):
        assert CFG[key]


def test_the_cell_file():
    entry = next(w for w in MANIFEST["workloads"]
                 if w["name"] == "qwen3next_train8k")
    assert entry["chips"] == 1 and entry["traffic"] == "train8k"
    assert (CELL["seq_len"], CELL["minibatch"], CELL["rows_per_epoch"],
            CELL["steps_per_dispatch"]) == (8192, 1, 8, 1)
    assert CELL["cli"] == ["--mesh", "data=1", "--mixed-precision"]
    assert CELL["control_precision"] == "float8_e4m3fn"
    assert set(CELL["scopes"]) == {"delta_rule", "experts", "route"}
    assert set(CELL["limits"]) == {"loss_gap", "grad_norm_gap",
                                   "delta_norm_gap"}
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if "qwen3next_train8k" in m.get("workloads", ())]
    assert mine == [
        "train_step_ms", "flash_roofline", "mfu.train",
        "device_idle_pct.train", "flash_step_ms", "scope_ms.delta_rule",
        "scope_ms.experts", "scope_ms.route", "delta_rule_roofline",
        "experts_roofline", "expert_tokens_mean", "expert_load_peak"]


# -- the counts, by hand ------------------------------------------------------

def test_counts_by_hand():
    d = 2048
    delta = (d * 12288 + d * 64 + 8192 * 4 + 4096 * d + 32 + 32 + 128)
    attn = d * 8192 + 2 * d * 512 + 4096 * d + 2 * 256
    sparse = d * 512 + 32 * 3 * d * 512 + 3 * d * 512 + d
    assert (delta, attn, sparse) == (33718464, 27263488, 104859648)
    blocks = 3 * delta + attn + 4 * (sparse + 2 * d)
    assert blocks == 547873856
    assert counts.model_params(CFG) == blocks + 2 * 18992 * d + 18992 \
        == 625684080
    s = counts.dims(CFG)
    assert (s["layers"], s["vocab"], s["window"], s["h"], s["hd"]) == (
        4, 18992, None, 16, 256)
    assert s["pattern"] == ["delta_rule"] * 3 + ["attention"]
    assert (s["experts_held"], s["experts_routed"], s["top_k"]) == (
        32, 512, 10)
    # a token multiplies: the mixers' matrices (the small leaves are no
    # products), router, shared expert and its gate, 0.625 routed experts,
    # the sliced head
    routed = 10 * 32 / 512 * 3 * d * 512
    assert routed == 0.625 * 3145728
    per_layer = d * 512 + 3 * d * 512 + d + routed
    assert counts.matmul_params(CFG) == (
        3 * (delta - 192) + (attn - 512) + 4 * per_layer + d * 18992)
    # attention: the softmax layer's two products a key, and the three
    # delta-rule layers' recurrence, 7 x 128 x 128 a token a value head
    assert counts.delta_rule_flops_forward(CFG, 1) == 7 * 32 * 128 * 128
    assert counts.attention_flops_forward(CFG, 10, 100.0) == (
        4.0 * 16 * 256 * 10 * 100.0 + 3 * 10 * 7 * 32 * 128 * 128)
    assert counts.kv_bytes_per_token(CFG, 2) == 2 * 2 * 256 * 2
    assert counts.delta_rule_flops(CFG, 8192) == 3 * 7 * 32 * 16384 * 8192
    assert counts.delta_rule_bytes(CFG, 8192) == (
        2 * (2048 + 2048 + 4096 + 4096 + 32 + 32) * 2 * 8192)
    assert counts.expert_flops(CFG, 5120) == 5120 * 3 * 2 * d * 512 * 3
    assert counts.expert_bytes(CFG, 5120, 1) == (
        3 * 32 * 3 * d * 512 * 4 + 5 * d * 2 * 5120)
    # a step's operations: 1.39 GFLOP a token forward and backward
    per_token = work.train_flops_per_token(CFG, 8192)
    assert per_token == pytest.approx(
        6 * counts.matmul_params(CFG) + 3 * counts.attention_flops_forward(
            CFG, 1, 4096.5))
    assert 1.35e9 < per_token < 1.45e9


# -- a rehearsal and the planted fault ----------------------------------------

@pytest.fixture(autouse=True)
def host_draw_restored():
    from veles_tpu import prng
    keep = prng.RandomGenerator.fill_normal
    yield
    prng.RandomGenerator.fill_normal = keep


def test_a_rehearsal_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    run = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), "--workload",
         "tiny_hybrid_train", "--seed", str(2 ** 31 + 5), "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["failed"] == 0
    assert set(line["checks"]) == {"loss_gap", "grad_norm_gap",
                                   "delta_norm_gap"}
    assert "compilations inside the window: 0" in run.stdout


def test_half_the_batch_left_out_is_not_correct():
    """``model_file.train_cell`` as the child runs it, on the CPU, with
    the second half of every batch masked out underneath: the gradient's
    gap passes its limit, and the window's counters are the program's."""
    from veles_tpu.backends import XLADevice
    from veles_tpu.config import root
    from chipbench import model_file
    spec = {"workload": TINY_TRAIN, "config": TINY, "seed": 5,
            "seconds": 0.3, "trace": 0, "trace_dir": None,
            "t_start": time.time(), "platform": "cpu", "study": False}
    state = {}

    def load_(builder, **kwargs):
        state["wf"] = builder(**kwargs)
        return state["wf"], False

    def main():
        wf = state["wf"]
        wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
        loader = wf.loader
        serve = loader.serve_next_minibatch

        def serve_half():
            serve()
            mask = loader.minibatch_mask.map_write()
            mask[len(mask) // 2:] = 0.0
        loader.serve_next_minibatch = serve_half
        wf.run()
    before = root.common.engine.get("mixed_precision", False)
    root.common.engine.mixed_precision = True
    try:
        report = model_file.train_cell(spec, load_, main, model_file.Probe())
    finally:
        root.common.engine.mixed_precision = before
    assert report["correct"] is False, report["checks"]
    n = report["checks"]["grad_norm_gap"]
    assert not n["value"] <= n["limit"]
    # the window's counters: one histogram sample a layer a step
    rise = report["counters"]
    assert rise["veles_moe_assignments_total"] > rise[
        "veles_moe_assignments_held_total"] > 0
    assert rise["veles_moe_peak_load_tokens_count"] % TINY[
        "num_hidden_layers"] == 0


# -- the readers --------------------------------------------------------------

def made_up(steps=5, held_a_layer_step=5000.0, peak=250.0, drains=8,
            delta_s=0.2, experts_s=0.1, counters=True, scopes=True):
    """A training report with a slice of ``steps`` steps of 8,192 tokens:
    the counters of ``drains`` steps' four layers, the capture's seconds
    under the scopes."""
    rise = {"veles_dispatches_total": float(steps)}
    if counters:
        samples = 4.0 * drains
        rise.update({
            "veles_moe_assignments_total": 81920.0 * samples,
            "veles_moe_assignments_held_total": held_a_layer_step * samples,
            "veles_moe_peak_load_tokens_sum": peak * samples,
            "veles_moe_peak_load_tokens_count": samples})
    rows = {"forward/delta_rule": [10, 0.25 * delta_s],
            "backward/delta_rule": [20, 0.75 * delta_s],
            "forward/experts": [10, 0.4 * experts_s],
            "backward/experts": [10, 0.6 * experts_s],
            "forward/router": [5, 0.01], "backward/head": [5, 0.3]}
    report = {"slice": {"steps": steps, "tokens": steps * 8192,
                        "window_s": 1.5, "counters": rise},
              "counters": {}, "trace": {
                  "window_s": 1.5, "busy_s": 1.4,
                  "scopes": {"jit__train_step_fn": rows} if scopes else None}}
    return {"report": report, "cfg": CFG, "wl": CELL, "work": work,
            "peaks": PEAKS, "chips": 1}


def test_the_routing_readers_by_hand():
    ctx = made_up()
    assert metric_reader("expert_tokens_mean")(ctx) == pytest.approx(
        5000.0 / 32)
    assert metric_reader("expert_load_peak")(ctx) == pytest.approx(
        250.0 / (5000.0 / 32))
    # no drain in the slice: the window's counters stand in
    late = made_up()
    late["report"]["counters"] = late["report"]["slice"]["counters"]
    late["report"]["slice"] = dict(late["report"]["slice"], counters={})
    assert metric_reader("expert_tokens_mean")(late) == pytest.approx(
        5000.0 / 32)


def test_the_roofline_readers_by_hand():
    ctx = made_up()
    tokens, steps = 5 * 8192, 5
    flops = 3 * (3 * 7 * 32 * 128 * 128 * tokens)
    nbytes = 3 * (2 * 12352 * 2 * tokens)
    least = max(flops / 197e12, nbytes / 819e9)
    assert metric_reader("delta_rule_roofline")(ctx) == pytest.approx(
        100.0 * least / 0.2)
    assignments = 5000.0 * 4 * steps
    flops = assignments * 3 * 2 * 2048 * 512 * 3
    nbytes = (4 * steps * 3 * 32 * 3 * 2048 * 512 * 4
              + 5 * 2048 * 2 * assignments)
    least = max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12      # 160 rows an expert: bytes
    assert metric_reader("experts_roofline")(ctx) == pytest.approx(
        100.0 * least / 0.1)
    assert metric_reader("scope_ms.delta_rule")(
        dict(ctx, metric="scope_ms.delta_rule")) == pytest.approx(
            1000 * 0.2 / 5)
    assert metric_reader("scope_ms.route")(
        dict(ctx, metric="scope_ms.route")) == pytest.approx(1000 * 0.01 / 5)


@pytest.mark.parametrize("name", ["delta_rule_roofline", "experts_roofline",
                                  "expert_tokens_mean", "expert_load_peak"])
def test_a_program_that_lacks_them_gives_nothing(name):
    """The parent's program has neither the counters nor the scopes, a
    dense configuration's counts neither function, a rehearsal no peaks:
    nothing is returned and nothing raised."""
    read = metric_reader(name)
    assert read(made_up(counters=False, scopes=False)) is None
    bare = made_up(counters=False, scopes=False)
    bare["report"] = {"slice": None, "counters": {}}
    assert read(bare) is None
    dense = dict(made_up(), cfg=load("configs", "tiny.json"))
    assert read(dense) is None
    if name.endswith("_roofline"):
        assert read(dict(made_up(), peaks=None)) is None
        assert read(made_up(scopes=False)) is None


def test_no_share_over_100_can_come_out_of_the_counts():
    """The delta rule is counted by its recurrence: the chunked form the
    program runs does more operations (per token and value head, at chunk
    64: K K^T and Q K^T 2 x 2 x 64 x 128, the triangular inverse's ten
    64-cubed products a chunk, T times V and K, and four products with the
    state), so at the chip's peak it takes longer than the least time the
    reader divides; and the experts' least time is what their own counted
    operations and bytes take at the peaks, which no device time is under."""
    c, dk, dv = 64, 128, 128
    chunked = (2 * 2 * c * dk + 10 * 2 * c * c * c / c
               + 2 * c * (dk + dv) + 4 * 2 * dk * dv)
    assert chunked > 7 * dk * dv
    tokens = 8192
    recurrence = counts.delta_rule_flops(CFG, tokens)
    assert recurrence < 3 * 32 * chunked * tokens
    ctx = made_up()
    fastest = made_up(delta_s=3 * 3 * 32 * chunked * 5 * 8192 / 197e12)
    assert metric_reader("delta_rule_roofline")(fastest) < 100.0
    experts = metric_reader("experts_roofline")
    at_peak = experts(ctx) / 100.0 * 0.1
    assert experts(made_up(experts_s=at_peak)) == pytest.approx(100.0)
