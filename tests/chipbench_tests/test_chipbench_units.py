"""The benchmark's own arithmetic, on the CPU and quick: traffic, tails,
the trace reduction on a recorded trace, operation counts against hand
counts, the plain reference against the program's own host oracle, and the
last line's keys. Nothing here touches libtpu while a module is imported.
"""
import json
import math
import os
import sys

import numpy
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, reduce, traffic, work  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


MISTRAL = load("configs", "mistral-7b.json")
INTERNLM = load("configs", "internlm2-1.8b.json")
TINY = load("configs", "tiny.json")
SAT = load("workloads", "mistral7b_decode_sat.json")["traffic"]
TRAIN = load("workloads", "internlm2_train4k.json")
WIDE = dict(SAT, prompt_len={"dist": "uniform", "min": 32, "max": 1024})
SEEDS = [5, 2 ** 31 + 5, 3000000011]


# -- traffic -----------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_schedule(seed):
    a = traffic.schedule(SAT, seed, 32000)
    b = traffic.schedule(SAT, seed, 32000)
    c = traffic.schedule(SAT, seed + 1, 32000)
    assert a == b and a != c and len(a) == SAT["pool"]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_has_the_same_sizes(seed):
    def sizes(seed):
        return sorted((len(r["prompt"]), r["n_new"], r["sampled"])
                      for r in traffic.schedule(SAT, seed, 32000))
    assert sizes(1) == sizes(seed)


@pytest.mark.parametrize("mix", [
    dict(SAT, loop="open"),
    dict(SAT, output_len={"dist": "zipf", "min": 1, "max": 2})],
    ids=["loop", "distribution"])
def test_what_the_generator_does_not_know_is_an_error(mix):
    with pytest.raises(ValueError):
        traffic.schedule(mix, 1, 32000)


@pytest.mark.parametrize("dist,lo,hi", [
    (SAT["prompt_len"], 64, 256), (SAT["output_len"], 256, 512)],
    ids=["uniform_prompt", "uniform_out"])
def test_lengths_stay_in_their_limits(dist, lo, hi):
    xs = traffic.lengths(dist, 200)
    assert min(xs) >= lo and max(xs) <= hi and len(set(xs)) > 20


def test_sampled_share():
    pool = traffic.pool(SAT, 64)
    assert sum(1 for _, _, s in pool if s) == 32
    quarter = dict(SAT, sampled_share=0.25)
    assert sum(1 for _, _, s in traffic.pool(quarter, 200) if s) == 50


def test_warmup_covers_the_buckets_in_use_only():
    reqs = traffic.warmup_requests(SAT, [128, 256, 512, 1024], 32000)
    assert sorted({len(r["prompt"]) for r in reqs}) == [128, 256]
    assert {r["sampled"] for r in reqs} == {False, True}
    wide = traffic.warmup_requests(WIDE, [128, 256, 512, 1024], 32000)
    assert sorted({len(r["prompt"]) for r in wide}) == [128, 256, 512, 1024]


# -- tails and due times -----------------------------------------------------

@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                    (100, 5.0)])
def test_percentile(q, want):
    assert reduce.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)
    assert reduce.percentile([5, 1, 4, 2, 3], q) == pytest.approx(
        numpy.percentile([5, 1, 4, 2, 3], q))


def test_latency_is_taken_from_the_due_instant():
    reqs = [{"due": 1.0, "first": 1.5, "last": 2.5, "tokens": 11,
             "ok": True},
            {"due": 2.0, "first": 2.1, "last": 2.1, "tokens": 1, "ok": True}]
    assert reduce.latency_samples(reqs, "ttft") == pytest.approx([0.5, 0.1])
    assert reduce.latency_samples(reqs, "tpot") == pytest.approx([0.1])


def test_a_failed_request_is_missing():
    ok = {"due": 0.0, "first": 0.1, "last": 1.1, "tokens": 11, "ok": True}
    bad = {"due": 0.0, "first": None, "last": None, "tokens": 0,
           "ok": False}
    samples = reduce.latency_samples([ok] * 9 + [bad], "ttft")
    assert samples.count(None) == 1
    assert reduce.tail_ms(samples, 50) == pytest.approx(100.0)
    assert math.isinf(reduce.tail_ms(samples, 95))


# -- the trace reduction -----------------------------------------------------

@pytest.mark.parametrize("intervals,want", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 15)], 15),
    ([(0, 10), (20, 30)], 20), ([(0, 10), (2, 3), (10, 12)], 12)])
def test_interval_union(intervals, want):
    assert reduce.interval_union(intervals) == want


def test_op_label():
    hlo = ('%jvp__.10 = (f32[32,4096,128]{2,1,0}, f32[32,8,4096]{2,1,0}) '
           'custom-call(f32[32,4096,128]{2,1,0} %bitcast.1), '
           'custom_call_target="tpu_custom_call", operand_layout={}')
    assert reduce.op_label(hlo) == "jvp__.10 custom-call tpu_custom_call"
    assert reduce.op_label("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "fusion.3 fusion"
    assert reduce.op_label("plain name") == "plain name"
    assert reduce.program_name("jit__train_step_fn(123)") \
        == "jit__train_step_fn"


def test_no_device_plane_is_an_error():
    host_only = [{"name": "/host:CPU", "lines": [
        {"name": "XLA Ops", "events": [["x", 0, 10]]}]}]
    with pytest.raises(reduce.NoDeviceStreams):
        reduce.reduce_trace(host_only, 1.0)
    idle = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": []}]}]
    with pytest.raises(reduce.NoDeviceStreams):
        reduce.reduce_trace(idle, 1.0)


def test_reduce_a_hand_made_trace():
    us = 1000       # the reduction tells "before" from "inside" to 1 us
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 0, 400 * us],
                                           ["jit_step(1)", 1000 * us,
                                            400 * us]]},
        {"name": "XLA Ops", "events": [
            ["a", 0, 100 * us], ["b", 100 * us, 300 * us],
            ["a", 1000 * us, 100 * us], ["b", 1200 * us, 200 * us]]},
    ]}]
    t = reduce.reduce_trace(planes, 2e-3)
    assert t["busy_s"] == pytest.approx(700e-6)
    assert t["modules"] == {"jit_step": [2, pytest.approx(800e-6)]}
    assert t["ops"]["a"] == [2, pytest.approx(200e-6)]
    gaps = dict(t["idle_gaps"])
    assert gaps["before jit_step"] == pytest.approx(600e-6)
    assert gaps["inside jit_step"] == pytest.approx(100e-6)
    assert reduce.matching(t["modules"], ("jit_step",)) == (
        2, pytest.approx(800e-6))


@pytest.fixture(scope="module")
def recorded():
    return load("testdata", "trace_train_step.json")


def test_recorded_trace_busy_time(recorded):
    """One train step of internlm2_train4k as the chip's profiler wrote it
    (names cut): the step's program covers the ops, which leave no gap."""
    t = reduce.reduce_trace(recorded["planes"], recorded["window_s"])
    step = t["modules"]["jit__train_step_fn"]
    assert step[0] == 1 and t["devices"] == 1
    assert 0.98 * step[1] <= t["busy_s"] <= step[1] * 1.0001
    assert t["busy_s"] == pytest.approx(recorded["busy_s"], rel=1e-9)


def test_recorded_trace_kernels(recorded):
    """A layer has one forward flash call (q, k, v) and two backward ones
    (dq; dk and dv), all on the cell's queries."""
    t = reduce.reduce_trace(recorded["planes"], recorded["window_s"])
    depth = INTERNLM["num_hidden_layers"]
    queries = "[%d,%d,128]" % (TRAIN["minibatch"] * 16, TRAIN["seq_len"])
    flash = [k for k in t["kernels"].values()
             if k["operands"][0].endswith(queries)]
    assert sum(k["count"] for k in flash if len(k["operands"]) == 3) == depth
    assert sum(k["count"] for k in flash
               if len(k["operands"]) > 3) == 2 * depth
    share = sum(k["seconds"] for k in flash) / t["busy_s"]
    assert 0.45 < share < 0.55, "the flash calls are half of the step"
    assert reduce.top_ops(t, 3)[2][0] in t["kernels"]


FWD = ('%jvp__.6 = (f32[32,4096,128]{2,1,0:T(8,128)S(1)}, f32[32,8,4096]'
       '{2,1,0:T(8,128)S(1)}) custom-call(f32[32,4096,128]{2,1,0:T(8,128)} '
       '%bitcast.965, f32[16,4096,128]{2,1,0:T(8,128)} %bitcast.994, '
       'bf16[16,4096,128]{2,1,0:T(8,128)(2,1)} %bitcast.1050), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints='
       '{f32[32,4096,128]{2,1,0}, f32[16,4096,128]{2,1,0}}')
DQ = ('%transpose_jvp___.13 = f32[32,4096,128]{2,1,0:T(8,128)} custom-call('
      'f32[32,4096,128]{2,1,0} %a, f32[32,4096,128]{2,1,0} %b, '
      'f32[16,4096,128]{2,1,0} %c, f32[16,4096,128]{2,1,0} %d, '
      'f32[32,8,4096]{2,1,0} %e, f32[32,8,4096]{2,1,0} %f), '
      'custom_call_target="tpu_custom_call"')
DKV = DQ.replace(".13 = f32[32,4096,128]{2,1,0:T(8,128)}",
                 ".12 = (f32[16,4096,128]{2,1,0}, f32[16,4096,128]{2,1,0})")
OTHER = ('%norm.1 = f32[8192,2048]{1,0} custom-call(f32[8192,2048]{1,0} %x), '
         'custom_call_target="tpu_custom_call"')


def test_kernel_types():
    assert reduce.kernel_types(FWD) == {
        "results": ["f32[32,4096,128]", "f32[32,8,4096]"],
        "operands": ["f32[32,4096,128]", "f32[16,4096,128]",
                     "bf16[16,4096,128]"]}
    assert work.hlo_bytes(["f32[32,4096,128]", "bf16[16,4096,128]", "s32[]"]) \
        == 32 * 4096 * 128 * 4 + 16 * 4096 * 128 * 2 + 4


@pytest.mark.parametrize("extra", [[], [[OTHER, 400, 50]]],
                         ids=["flash_only", "another_pallas_call"])
def test_flash_roofline_reads_the_flash_calls_only(extra):
    """One layer at minibatch 2: forward 10 ms, dq and dk/dv 10 ms each.
    The share is the least time of 2 + 4 products over those 30 ms, and a
    Pallas call on other operands changes nothing."""
    ms = 1000000
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        [FWD, 0, 10 * ms], [DQ, 10 * ms, 10 * ms], [DKV, 20 * ms, 10 * ms]]
        + extra}]}]
    trace = reduce.reduce_trace(planes, 0.05)
    read = run_module().metric_reader("flash_roofline")
    peaks = work.load_peaks("TPU v5 lite")
    ctx = {"report": {"trace": trace}, "peaks": peaks, "work": work,
           "cfg": dict(INTERNLM, num_hidden_layers=1),
           "wl": dict(TRAIN, minibatch=2)}
    product = 2.0 * 32 * 4096 * 2048.5 * 128
    assert read(ctx) == pytest.approx(
        100.0 * 6 * product / 197e12 / 0.030, rel=1e-6)
    assert read(dict(ctx, wl=dict(TRAIN, minibatch=2, seq_len=2048))) is None
    assert read(dict(ctx, peaks=None)) is None


def test_serving_readers_are_of_the_slice():
    """Two requests; the slice [1, 2] holds the second's first token (its
    prefill) and three later tokens of the two: two decode steps."""
    cfg = dict(TINY, sliding_window=None)
    reqs = [{"prompt_len": 3, "stamps": [0.5, 0.9, 1.1, 1.6, 2.4]},
            {"prompt_len": 5, "stamps": [1.2, 1.7, 2.2]}]
    report = {"requests": reqs, "slice": {
        "from_s": 1.0, "to_s": 2.0, "window_s": 1.0,
        "counters": {"veles_serving_decode_dispatches_total": 2.0}}}
    peaks = work.load_peaks("TPU v5 lite")
    ctx = {"report": report, "cfg": cfg, "wl": {"kind": "serve"},
           "work": work, "peaks": peaks, "chips": 1}
    reader = run_module().metric_reader
    assert reader("decode_batch_mean")(ctx) == pytest.approx(3 / 2.0)
    want = (work.token_flops(cfg, 3, 2) + work.token_flops(cfg, 3, 3)
            + work.token_flops(cfg, 5, 0) + work.token_flops(cfg, 5, 1))
    assert reader("mfu.decode")(ctx) == pytest.approx(100.0 * want / 197e12)
    report["slice"] = None
    assert reader("mfu.decode")(ctx) is None
    assert reader("decode_batch_mean")(ctx) is None


def test_training_readers_are_of_the_slice():
    report = {"trace": {"busy_s": 0.9, "window_s": 1.0},
              "slice": {"window_s": 1.0, "steps": 3, "tokens": 3 * 4096}}
    ctx = {"report": report, "cfg": INTERNLM, "wl": TRAIN, "work": work,
           "peaks": work.load_peaks("TPU v5 lite"), "chips": 1}
    reader = run_module().metric_reader
    assert reader("train_step_ms")(ctx) == pytest.approx(300.0)
    assert reader("device_idle_pct.train")(ctx) == pytest.approx(10.0)
    assert reader("mfu.train")(ctx) == pytest.approx(
        100.0 * work.train_flops_per_token(INTERNLM, 4096) * 3 * 4096
        / 197e12)


def run_module():
    import importlib
    return importlib.import_module("chipbench.run")


# -- operations and bytes against hand counts --------------------------------

def test_layer_parameters():
    assert work.layer_params(MISTRAL) == (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096)
    assert round(work.layer_params(MISTRAL) / 1e6, 1) == 218.1
    assert round(work.layer_params(INTERNLM) / 1e6, 1) == 62.9


def test_cache_bytes_per_token():
    assert work.kv_bytes_per_token(MISTRAL, 4) == 64 * 1024
    assert work.kv_bytes_per_token(dict(MISTRAL, num_hidden_layers=1), 4) \
        == 8 * 1024


def test_model_sizes():
    cut = dict(INTERNLM, num_hidden_layers=8)
    assert work.model_params(cut) == (
        8 * 62918656 + 2 * 92544 * 2048 + 92544)
    assert round(work.model_params(cut) / 1e6) == 883
    assert round(work.matmul_params(cut) / 1e6) == 693
    assert round(work.weight_bytes(MISTRAL, 4) / 1e9, 1) == 8.0


def test_train_flops_per_token():
    cut = dict(INTERNLM, num_hidden_layers=8)
    flops = work.train_flops_per_token(cut, 4096)
    attn = 3 * 8 * 4 * 2048 * (4097 / 2.0)
    assert flops == pytest.approx(6 * work.matmul_params(cut) + attn)
    assert round(flops / 1e9, 1) == 4.6


@pytest.mark.parametrize("t,window,want", [
    (4, None, 2.5), (4, 4, 2.5), (4, 2, (1 + 2 + 2 + 2) / 4.0),
    (4096, 4096, 2048.5)])
def test_context_mean(t, window, want):
    assert work.context_mean(t, window) == pytest.approx(want)


def test_request_flops_by_hand():
    cfg = dict(TINY, sliding_window=None)
    mm = 2.0 * work.matmul_params(cfg)
    att = 4.0 * cfg["num_hidden_layers"] * 4 * 16
    want = (mm * 3 + att * 3 * 2.0) + (mm + att * 4) + (mm + att * 5)
    assert work.request_flops(cfg, 3, 3) == pytest.approx(want)
    assert work.token_flops(cfg, 3, 0) == pytest.approx(mm * 3 + att * 6.0)
    assert work.token_flops(cfg, 3, 2) == pytest.approx(mm + att * 5)


def test_flash_call_flops():
    f = work.flash_call_flops(2 * 16, 4096, 128, causal=True)
    assert f == pytest.approx(4 * 2 * 16 * 4096 * 2048.5 * 128)
    assert work.flash_call_flops(2 * 16, 4096, 128, backward=True) \
        == pytest.approx(2 * f)
    assert work.flash_call_flops(1, 8, 4, causal=False) == 4 * 8 * 8 * 4


def test_peaks():
    peaks = work.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9")
    with pytest.raises(KeyError):
        work.load_peaks("_source")
    secs, bound = work.roofline_seconds(197e12, 819e9 / 2, peaks)
    assert secs == pytest.approx(1.0) and bound == "compute"


# -- the comparison ----------------------------------------------------------

def test_norm_gap_takes_the_worst_leaf_against_the_median():
    ref = {"a": {"w": 10.0, "b": 1e-9}, "c": {"w": 12.0}}
    prog = {"a": {"w": 10.5, "b": 1e-3}, "c": {"w": 12.0}}
    gap, leaf = check.norm_gap(prog, ref)
    assert leaf == "a.w" and gap == pytest.approx(0.05)
    assert check.still_leaves(ref) == ["a.b"]
    with pytest.raises(ValueError):
        check.norm_gap({"a": {"w": 1.0}}, ref)


@pytest.mark.parametrize("fault,number", [
    ("none", None), ("unchanged", "delta_norm_gap"),
    ("doubled", "delta_norm_gap"), ("half_grad", "grad_norm_gap"),
    ("nan", "loss_gap")])
def test_compare_train(fault, number):
    ref = {"loss": [10.0, 9.9, 9.8], "grad1": {"u": {"w": 2.0, "b": 1.0}},
           "delta": {"u": {"w": 3e-3, "b": 1e-3}}}
    prog = json.loads(json.dumps(ref))
    if fault == "unchanged":
        prog["delta"]["u"] = {"w": 0.0, "b": 0.0}
    if fault == "doubled":
        prog["delta"]["u"]["w"] *= 2
    if fault == "half_grad":
        prog["grad1"]["u"]["w"] *= 0.7
    if fault == "nan":
        prog["loss"][1] = float("nan")
    limits = {"loss_gap": 1e-3, "grad_norm_gap": 0.01,
              "delta_norm_gap": 0.01}
    ok, numbers = check.compare_train(prog, ref, limits)
    assert ok == (fault == "none")
    for name, n in numbers.items():
        assert (n["value"] <= n["limit"]) == (name != number)
    assert len(check.report_lines(numbers)) == 3


def test_compare_served():
    ok, numbers = check.compare_served([0.0, 0.01, 0.0], {
        "served_logit_gap": 0.05})
    assert ok and numbers["served_logit_gap"]["tokens"] == 3
    assert not check.compare_served([0.0, 0.2], {"served_logit_gap": 0.05})[0]
    assert not check.compare_served([], {"served_logit_gap": 0.05})[0]
