"""The window/full sparse decoder in the benchmark: what came as new files
(``configs/mimo-v2.5.json`` and ``tiny-mimo.json``, ``reference_mimo.py``,
``counts_mimo.py``, two cell files, one reader) held to the harness that
was there.

- the contract's serves-only shape: the module gives the serving half, and
  a ``train`` cell on it stops with one plain line;
- the configuration passes the rules on a share (``config_faults``),
  repeats every number of the published config and keeps the two pattern
  lists whole; the cell's file has the issue's traffic letter for letter;
- the counts, without jax, against hand counts at the cell's sizes;
- the reference's own continuation is correct, its float8 control and each
  planted fault are not;
- the run itself (``tiny_mimo_serve --rehearse``, and a served token altered
  underneath) is driven by ``test_chipbench_correct.py``, which takes every
  rehearsal serving cell; here the same child's side with the rings cut
  under the window: ``correct`` comes out false;
- the new reader and the joined families on a made-up reduced trace and
  made-up counters, by hand; a program that lacks what they read gives
  nothing; no share over 100 % can come out of the counts.
"""
import json
import os
import sys
import time

import numpy
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import check, modules, work  # noqa: E402
from chipbench import counts_mimo as counts  # noqa: E402
from chipbench.run import metric_reader  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CFG = load("configs", "mimo-v2.5.json")
CELL = load("workloads", "mimo_v25_decode_mixed.json")
TINY = load("configs", "tiny-mimo.json")
TINY_SERVE = dict(load("workloads", "tiny_mimo_serve.json"), chips=1)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAME = "mimo_v25_decode_mixed"


@pytest.fixture(autouse=True)
def host_draw_restored():
    from veles_tpu import prng
    keep = prng.RandomGenerator.fill_normal
    yield
    prng.RandomGenerator.fill_normal = keep


# -- the contract -------------------------------------------------------------

@pytest.mark.parametrize("cfg", [CFG, TINY], ids=lambda c: c["name"])
def test_the_module_serves_only(cfg):
    mod = modules.reference_of(cfg, serving=True)
    assert mod.__name__ == "chipbench.reference_mimo"
    with pytest.raises(modules.ContractError) as lacks:
        modules.reference_of(cfg, training=True)
    assert "exports no train_reference" in str(lacks.value)


def test_the_tiny_files_are_no_cell():
    assert "tiny_mimo_serve" not in [w["name"]
                                     for w in MANIFEST["workloads"]]
    assert "tiny-mimo" not in [c["name"] for c in MANIFEST["configs"]]
    assert TINY_SERVE["config"] == "tiny-mimo"
    assert TINY_SERVE["who"].startswith("nobody")


# -- the configuration --------------------------------------------------------

def test_the_configuration_is_a_share_the_rules_admit():
    from test_chipbench_manifest import config_faults
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "mimo-v2.5")
    assert config_faults(entry, CFG) == []
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (7, 16, 19072)
    assert (CFG["chips_per_layer"], CFG["vocab_shards"]) == (16, 8)
    # every key of the published config under its own name, unchanged but
    # for the three that are reduced; the two pattern lists whole
    for key, value in CFG["published"].items():
        assert key in CFG, key
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert len(CFG["hybrid_layer_pattern"]) == len(
        CFG["moe_layer_freq"]) == CFG["published"]["num_hidden_layers"] == 48
    assert CFG["router_width"] == CFG["published"]["n_routed_experts"] == 256
    assert CFG["n_routed_experts"] * CFG["chips_per_layer"] == 256
    assert CFG["vocab_size"] * CFG["vocab_shards"] == 152576
    for key in ("deployment", "assumed", "departures", "precision"):
        assert CFG[key]


def test_the_catalog_s_row_is_repeated_number_for_number():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert CFG["source"] == row["source_url"]
    assert CFG["published"] == row["config"]


def test_the_layers_held_are_the_published_first_seven():
    kinds = counts.layer_kinds(CFG)
    assert kinds == [("full", "dense")] + [("window", "experts")] * 4 + [
        ("full", "experts"), ("window", "experts")]
    mod = modules.reference_of(CFG)
    assert mod.layer_kinds(CFG) == kinds
    layers = mod.layer_list(CFG)
    assert [ly["type"] for ly in layers] == (
        ["embedding"] + ["hybrid_block"] * 7 + ["lm_head"])
    full, window = layers[1], layers[2]
    assert (full["n_heads"], full["n_kv_heads"], full["head_dim"],
            full["v_head_dim"], full["window"], full["sink"],
            full["rope_base"], full["ffn"]) == (
                64, 4, 192, 128, 0, False, 1e7, "dense")
    assert (window["n_heads"], window["n_kv_heads"], window["head_dim"],
            window["v_head_dim"], window["window"], window["sink"],
            window["rope_base"], window["ffn"]) == (
                64, 8, 192, 128, 128, True, 1e4, "experts")
    assert round(192 * window["rotary_factor"]) == 64
    assert (window["n_experts"], len(window["experts_held"]),
            window["top_k"], window["router"], window["shared_expert"]) == (
                256, 16, 8, "sigmoid", False)
    assert window["value_scale"] == 0.707 and window["eps"] == 1e-5


def test_the_cell_file():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == NAME)
    assert entry["chips"] == 1 and entry["traffic"] == "decode_mixed"
    assert entry["config"] == CELL["config"] == "mimo-v2.5"
    assert CELL["kind"] == "serve"
    assert CELL["traffic"] == {
        "loop": "closed", "clients": 64, "pool": 128, "pool_seed": 7,
        "prompt_len": {"dist": "uniform", "min": 256, "max": 3840},
        "output_len": {"dist": "uniform", "min": 256, "max": 512},
        "sampled_share": 0.5, "temperature": 0.8}
    assert CELL["cli"][:10] == [
        "--mesh", "data=1", "--serve-generate", "0", "--serve-slots", "64",
        "--serve-buckets", "512,1024,2048,4096", "--serve-max-context",
        "4608"]
    # what the issue's line lacks: the weights' type, by the key the
    # program has for it
    assert CELL["cli"][10:] == ["root.common.engine.precision_type=bfloat16"]
    assert (CELL["check_requests"], CELL["check_pad"],
            CELL["trace_seconds"]) == (6, 4352, 6)
    assert CELL["control_precision"] == "float8_e4m3fn"
    assert set(CELL["limits"]) == {"served_logit_gap"}
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if NAME in m.get("workloads", ())]
    assert mine == [
        "decode_batch_mean", "decode_step_ms", "mfu.decode",
        "device_idle_pct.decode", "tick_ms.admit", "tick_ms.prefill",
        "tick_ms.prepare", "tick_ms.dispatch", "tick_ms.device",
        "tick_ms.emit", "tick_ms.unaccounted", "stream_write_ms",
        "scope_ms.page_writeback", "scope_ms.page_gather",
        "scope_ms.serve_experts", "scope_ms.serve_route",
        "scope_ms.window_attn", "scope_ms.full_attn",
        "expert_tokens_mean.decode", "expert_load_peak.decode",
        "serve_experts_roofline"]
    new = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [NAME]]
    assert len(new) == 7 and all(m["moves"] == "out_tokens_per_s"
                                 for m in new)
    assert NAME in next(m for m in MANIFEST["end_to_end"]
                        if m["name"] == "out_tokens_per_s")["workloads"]
    for key in {m["name"].partition(".")[2] for m in new
                if m["name"].startswith("scope_ms.")}:
        assert key in CELL["scopes"], key


def test_the_warm_up_builds_every_bucket_and_both_rungs():
    from chipbench import traffic
    from veles_tpu.serving.pages import pages_for, view_ladder, view_rung
    warm = traffic.warmup_requests(CELL["traffic"], [512, 1024, 2048, 4096],
                                   CFG["vocab_size"])
    lengths = sorted({len(w["prompt"]) for w in warm})
    assert lengths == [512, 1024, 2048, 3840]
    ladder = view_ladder(pages_for(4608, 16), 16, 512)
    assert ladder == (288, 144)
    rungs = {view_rung(ladder, n + 4, 16) for n in lengths}
    assert rungs == {288, 144}
    sizes = traffic.pool(CELL["traffic"], CELL["traffic"]["pool"])
    assert max(p + n for p, n, _ in sizes) <= CELL["check_pad"] <= 4608


# -- the counts, by hand ------------------------------------------------------

def test_counts_by_hand():
    d = 4096
    full = d * 64 * 192 + d * 4 * 192 + d * 4 * 128 + 64 * 128 * d
    window = d * 64 * 192 + d * 8 * 192 + d * 8 * 128 + 64 * 128 * d
    assert counts.attention_matrix_params(CFG, "full") == full == 89128960
    assert counts.attention_matrix_params(CFG, "window") == window == 94371840
    expert = 3 * d * 2048
    dense = 3 * d * 16384
    assert counts.expert_matrix_params(CFG) == expert == 25165824
    sparse = d * 256 + 256 + 16 * expert
    held = (2 * full + 5 * window + dense + 6 * sparse + 7 * 2 * d
            + 5 * 64 + 2 * 19072 * d + 19072)
    assert work.model_params(CFG) == held
    assert 3.42e9 < held < 3.44e9                  # the issue's 3,430 M
    routed = 8 * 16 / 256 * expert
    token = (2 * full + 5 * window + dense + 6 * (d * 256 + routed)
             + d * 19072)
    assert work.matmul_params(CFG) == token
    assert work.dims(CFG)["window"] is None
    assert counts.experts_held(CFG) == work.dims(CFG)["experts_held"] == 16
    # a query with 1,000 keys before it: the full layers see them all, the
    # window layers 128
    att = 2.0 * 64 * (192 + 128) * (2 * 1000 + 5 * 128)
    assert work.attention_flops_forward(CFG, 1, 1000) == att
    assert work.attention_flops_forward(CFG, 1, 50) == (
        2.0 * 64 * (192 + 128) * 7 * 50)
    assert work.token_flops(CFG, 900, 100) == pytest.approx(
        2.0 * token + att)
    # the cache, keys of 192 stored at 256 lanes: 3,072 bytes a position a
    # full layer (2,560 unpadded), and a ring a slot
    assert work.kv_bytes_per_token(CFG, 2) == 2 * 4 * (256 + 128) * 2 == 6144
    assert counts.ring_bytes_per_slot(CFG, 2) == 5 * 144 * 8 * 384 * 2
    assert 64 * 4608 * 6144 == pytest.approx(1.81e9, rel=0.01)
    assert 64 * counts.ring_bytes_per_slot(CFG, 2) == pytest.approx(
        0.283e9, rel=0.01)
    # the experts' products: 2 assignments an expert at 64 rows
    assert counts.expert_flops(CFG, 32) == 2.0 * expert * 32
    assert counts.expert_bytes(CFG, 32, 14) == (
        14 * expert * 2 + 2.0 * d * 2 * 32)


def test_tiny_counts_follow_the_same_rules():
    s = work.dims(TINY)
    assert (s["full_layers"], s["window_layers"], s["expert_layers"],
            s["dense_layers"]) == (2, 2, 3, 1)
    assert work.kv_bytes_per_token(TINY, 4) == 2 * 1 * (24 + 16) * 4
    assert counts.ring_bytes_per_slot(TINY, 4) == 2 * 32 * 2 * 40 * 4


# -- the reference ------------------------------------------------------------

@pytest.fixture(scope="module")
def mimo():
    return modules.reference_of(TINY, serving=True)


def continuation(mimo, seed, prompt, n):
    served = []
    for _ in range(n):
        _, best = mimo.served_gaps(TINY, seed, prompt + served, [0], pad=64)
        served.append(int(best[0]))
    return served


@pytest.mark.parametrize("pick,correct", [
    (None, True), ("float8_e4m3fn", False), ("no_sink", False),
    ("window_short", False)],
    ids=["reference", "control", "sink_left_out", "window_a_page_short"])
def test_served_control_and_faults_are_not_correct(mimo, pick, correct):
    """The reference's own greedy continuation reads 0; the tokens that
    float8 products, or the reference with a mechanism planted wrong, put
    first lie below the limit's reach."""
    rng = numpy.random.default_rng(9)
    worst = 0.0
    for _ in range(3):
        prompt = rng.integers(0, TINY["vocab_size"], 20).tolist()
        if pick is None:
            served = continuation(mimo, 9, prompt, 6)
            gaps, _ = mimo.served_gaps(TINY, 9, prompt, served, pad=64)
        else:
            served = rng.integers(0, TINY["vocab_size"], 30).tolist()
            gaps, _ = mimo.served_gaps(TINY, 9, prompt, served, pad=64,
                                       pick=pick)
        worst = max(worst, float(gaps.max()))
    assert check.compare_served([worst], TINY_SERVE["limits"])[0] == correct, \
        worst


def test_weights_are_the_seed_s_a_unit_at_a_time(mimo):
    a = mimo.make_weights(TINY, 2 ** 31 + 11)
    one = mimo.make_unit(TINY, 2 ** 31 + 11, "blk1")
    other = mimo.make_unit(TINY, 2 ** 31 + 12, "blk1")
    assert set(a) == {"embed", "blk0", "blk1", "blk2", "blk3", "head"}
    for leaf, w in one.items():
        assert (numpy.asarray(a["blk1"][leaf]) == numpy.asarray(w)).all()
    assert not (numpy.asarray(one["wq"]) == numpy.asarray(other["wq"])).all()
    assert float(numpy.abs(numpy.asarray(one["sink"])).max()) > 0
    assert float(numpy.abs(numpy.asarray(one["router_bias"])).max()) > 0
    assert "sink" not in a["blk0"] and "router" not in a["blk0"]
    big = mimo.unit_shapes(CFG, "blk1")
    assert big["e_gate"][0] == (16, 4096, 2048)
    assert big["wk"][0] == (4096, 8 * 192) and big["wv"][0] == (4096, 8 * 128)
    assert mimo.unit_shapes(CFG, "blk0")["wk"][0] == (4096, 4 * 192)
    assert mimo.unit_shapes(CFG, "head")["weights"][0] == (4096, 19072)


# -- the run with the rings cut under the window ------------------------------

def test_a_ring_under_the_window_is_not_correct(tmp_path, monkeypatch):
    """The child's side around a live ``GenerationAPI`` as
    ``test_chipbench_correct.test_serve_cell_end_to_end`` drives it, with
    every window layer's ring cut to half its window underneath."""
    from veles_tpu.backends import XLADevice
    from veles_tpu.nn.hybrid import HybridBlock
    from veles_tpu.restful_api import GenerationAPI
    from chipbench import model_file, serve_client, serve_side, traffic
    sound = HybridBlock.cache_geometry
    monkeypatch.setattr(
        HybridBlock, "cache_geometry", lambda self, d, page_size: dict(
            sound(self, d, page_size),
            ring=self.window // 2 if self.window else 0))
    wl, cfg = TINY_SERVE, TINY
    spec = {"workload": wl, "config": cfg, "seed": 31, "seconds": 1,
            "trace": 0, "trace_dir": None, "t_start": time.time(),
            "platform": "cpu", "study": False,
            "sample_path": str(tmp_path / "sample.json")}
    model_file.skip_host_draw()
    wf = serve_side.build_workflow(cfg, wl)
    wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
    side = serve_side.ServeSide(spec, wf, model_file.Probe())
    side.attach()
    flag = dict(zip(wl["cli"][::2], wl["cli"][1::2]))
    api = GenerationAPI(wf, port=0, max_slots=int(flag["--serve-slots"]),
                        buckets=[int(b) for b in
                                 flag["--serve-buckets"].split(",")],
                        max_context=int(flag["--serve-max-context"]),
                        name="chipbench_test_short_ring")
    api.initialize()
    try:
        side.on_signal()
        records = []
        for item in traffic.schedule(wl["traffic"], 31,
                                     cfg["vocab_size"])[:6]:
            rec = {"i": item["i"], "sampled": item["sampled"], "item": item}
            serve_client.stream_request(api.port, item, rec)
            assert rec["ok"], rec["error"]
            records.append(rec)
        side.on_signal()
    finally:
        api.stop()
    with open(spec["sample_path"], "w") as f:
        json.dump(serve_client.pick_sample(records, 31, 4), f)
    report = side.finish()
    assert report["correct"] is False, report["checks"]


# -- the readers --------------------------------------------------------------

def made_up(counters=True, scopes=True):
    """A traced serving run's report as the readers see it: 100 decode
    steps in the slice, 6 expert layers."""
    steps = 100
    rise = {"veles_serving_decode_dispatches_total": steps}
    if counters:
        rise.update({
            "veles_moe_assignments_total": 64.0 * 8 * 6 * steps,
            "veles_moe_assignments_held_total": 32.0 * 6 * steps,
            "veles_moe_experts_touched_total": 14.0 * 6 * steps,
            "veles_moe_peak_load_tokens_sum": 5.0 * 6 * steps,
            "veles_moe_peak_load_tokens_count": 6.0 * steps})
    rows = {"blk1/experts": [600, 0.9], "blk1/router": [600, 0.05],
            "blk1/dispatch": [600, 0.03], "blk1/window_attn": [500, 0.04],
            "blk0/full_attn": [200, 0.25], "page_gather": [400, 0.4],
            "blk0/ffn": [100, 0.2]}
    prefill = {"blk1/experts": [90, 0.5], "blk0/full_attn": [30, 0.3]}
    report = {"slice": {"window_s": 6.0, "from_s": 12.0, "to_s": 18.0,
                        "counters": rise},
              "requests": [], "trace": {
                  "window_s": 6.0, "busy_s": 5.0,
                  "modules": {"jit_step(1)": [steps, 2.0],
                              "jit_prefill(2)": [30, 1.5]},
                  "scopes": {"jit_step(1)": rows,
                             "jit_prefill(2)": prefill} if scopes else None}}
    return {"report": report, "cfg": CFG, "wl": CELL, "work": work,
            "peaks": PEAKS, "chips": 1}


def test_the_readers_by_hand():
    ctx = made_up()
    assert metric_reader("expert_tokens_mean.decode")(ctx) == pytest.approx(
        32.0 / 16)
    assert metric_reader("expert_load_peak.decode")(ctx) == pytest.approx(
        5.0 / 2.0)
    held, touched = 32.0 * 600, 14.0 * 600
    expert = 3 * 4096 * 2048
    flops = 2.0 * expert * held
    nbytes = touched * expert * 2 + 2.0 * 4096 * 2 * held
    assert nbytes / 819e9 > flops / 197e12         # 2 rows an expert: bytes
    # the step's own scope alone: prefill's experts are in neither side
    assert metric_reader("serve_experts_roofline")(ctx) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.9)
    for key, ms in (("serve_experts", 9.0), ("serve_route", 0.8),
                    ("window_attn", 0.4), ("full_attn", 2.5),
                    ("page_gather", 4.0)):
        name = "scope_ms." + key
        assert metric_reader(name)(dict(ctx, metric=name)) == pytest.approx(
            ms), name


@pytest.mark.parametrize("name", [
    "serve_experts_roofline", "expert_tokens_mean.decode",
    "expert_load_peak.decode", "scope_ms.serve_experts",
    "scope_ms.window_attn"])
def test_a_program_that_lacks_them_gives_nothing(name):
    """The parent's program has neither the counters nor the scopes, a
    dense configuration's counts no such function, a rehearsal no peaks:
    nothing is returned and nothing raised."""
    read = metric_reader(name)
    bare = made_up(counters=False, scopes=False)
    assert read(dict(bare, metric=name)) is None
    if name.startswith("scope_ms."):
        only_dense = made_up()
        only_dense["report"]["trace"]["scopes"] = {
            "jit_step(1)": {"blk0/ffn": [100, 0.2], "page_gather": [4, 0.4]}}
        assert read(dict(only_dense, metric=name)) is None
        return
    gone = made_up()
    gone["report"] = {"slice": None, "counters": {}}
    assert read(dict(gone, metric=name)) is None
    dense = dict(made_up(), cfg=load("configs", "tiny.json"), metric=name)
    assert read(dense) is None
    if name.endswith("_roofline"):
        assert read(dict(made_up(), peaks=None, metric=name)) is None
        assert read(dict(made_up(scopes=False), metric=name)) is None


def test_no_share_over_100_can_come_out_of_the_counts():
    """The least time is that of the counted assignments' own operations
    and of the touched experts' matrices read once: a program reads a
    touched expert's three matrices at least once and multiplies every
    held assignment's row through them, so no device time is under it."""
    for held, touched in ((32, 14), (512, 16), (1, 1)):
        flops = counts.expert_flops(CFG, held)
        nbytes = counts.expert_bytes(CFG, held, touched)
        assert flops == 2.0 * 3 * 4096 * 2048 * held
        assert nbytes >= touched * 3 * 4096 * 2048 * 2
        least = work.roofline_seconds(flops, nbytes, PEAKS)[0]
        assert least == max(flops / 197e12, nbytes / 819e9)
