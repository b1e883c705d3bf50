"""The served hybrid block (``nn/hybrid.py`` ``mixer="softmax"``) at the
``tiny-mimo`` size, float32, seeded weights: window layers with a learned
sink among full layers of other head counts, keys wider than values, a
dense SwiGLU and sigmoid-routed experts without a shared one.

- ``HybridBlock.apply`` over a whole sequence against the plain reference
  (``chipbench/reference_mimo.py``), and each of sink, value scale,
  selection bias, per-type rotary base and window shown to matter: the
  reference without it misses the tolerance;
- prefill then decode through ``ContinuousEngine``'s cache against the
  same reference, over contexts of several windows and past the ring's
  end;
- a slot's bytes in a window layer do not grow with its context, and
  pages are the full layers' alone;
- the shares of all chips add up to the uncut layer;
- the modes the block does not serve are refused with a line that names
  it, at the constructor, at ``accepts`` and over HTTP, and never handed
  to the window plane;
- the step's counters and scopes.

Tolerances. Program and reference are both float32 (the CPU multiplies
float32 exactly at either precision setting) and differ in the order of
their sums: 5.7e-6 against logits of 10.6 was read on the builder's run,
so ``TOL`` = 1e-4 of the largest logit is 20 times that; the mechanisms'
faults read 1.6 to 4.9, four orders above it.
"""
import contextlib
import json
import os
import sys
import urllib.error
import urllib.request

import numpy
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOL = 1e-4
SEED = 5


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


CFG = load("configs", "tiny-mimo.json")


@pytest.fixture(scope="module")
def ref():
    from chipbench import modules
    return modules.reference_of(CFG, serving=True)


@pytest.fixture(scope="module")
def wf(ref):
    """The stack as the benchmark's child builds it, with the seed's
    weights on the device."""
    from veles_tpu import prng
    from veles_tpu.backends import XLADevice
    from chipbench import model_file, serve_side
    keep = prng.RandomGenerator.fill_normal
    model_file.skip_host_draw()
    try:
        wf = serve_side.build_workflow(CFG, {"init_positions": 8})
        wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
    finally:
        prng.RandomGenerator.fill_normal = keep
    weights = ref.make_weights(CFG, SEED)
    for f in wf.forwards:
        arrays = f.param_arrays()
        assert set(arrays) == set(weights[f.name]), f.name
        for k, arr in arrays.items():
            assert tuple(arr.shape) == weights[f.name][k].shape, (f.name, k)
            arr.assign_devmem(weights[f.name][k])
    return wf


@pytest.fixture(scope="module")
def tokens(ref):
    return ref.make_tokens(SEED, 1, 60, CFG["vocab_size"])[0, :60]


@pytest.fixture(scope="module")
def program_logits(wf, tokens):
    import jax.numpy as jnp
    from veles_tpu.nn.sampling import params_of
    params = params_of(wf)
    x = jnp.asarray(tokens)[None]
    for f in wf.forwards:
        x = f.apply(params[f.name], x)
    return numpy.asarray(x[0])


def test_layers_are_what_the_configuration_says(wf):
    blocks = wf.forwards[1:-1]
    assert [(b.window, b.ffn, b.n_kv_heads) for b in blocks] == [
        (0, "dense", 1), (8, "experts", 2), (8, "experts", 2),
        (0, "experts", 1)]
    assert all(b.has_sink == bool(b.window) for b in blocks)
    assert blocks[1]._widths(CFG["hidden_size"]) == (24, 16, 8)
    assert blocks[1].router_kind == "sigmoid" and not blocks[1].shared_expert


def test_apply_against_the_reference(ref, tokens, program_logits):
    want = numpy.asarray(ref.logits_fn(CFG, SEED, tokens))
    assert numpy.abs(want).max() > 1.0, "logits too flat to tell"
    assert numpy.abs(program_logits - want).max() <= TOL * numpy.abs(
        want).max()


@pytest.mark.parametrize("fault", [
    "no_sink", "no_value_scale", "no_router_bias", "one_rope_base",
    "window_short"])
def test_each_mechanism_matters(ref, tokens, program_logits, fault):
    """The reference with one mechanism left out misses the tolerance by
    three orders or more: the program has it, and the test would see it
    go."""
    bad = numpy.asarray(ref.logits_fn(CFG, SEED, tokens, pick=fault))
    assert numpy.abs(program_logits - bad).max() > 1000 * TOL * numpy.abs(
        bad).max()


# -- prefill then decode through the engine's cache ---------------------------

@pytest.fixture(scope="module")
def engine(wf):
    from veles_tpu.serving import ContinuousEngine
    eng = ContinuousEngine(wf, max_slots=4, buckets=(16, 32),
                           max_context=80, name="hybrid_test").start()
    yield eng
    eng.stop()


def series():
    from veles_tpu.telemetry.counters import counters, histograms
    out = dict(counters.snapshot())
    peak = histograms.snapshot().get("veles_moe_peak_load_tokens")
    out["peak_count"] = peak["count"] if peak else 0
    return out


#: (prompt, answer): contexts of several windows (8), up to and past the
#: ring's 32 positions, a prompt that fills its bucket, one beyond a bucket
CASES = [(5, 40), (30, 45), (16, 60), (32, 48), (9, 20)]


@pytest.fixture(scope="module")
def served(engine):
    from veles_tpu.serving.engine import make_request
    rng = numpy.random.default_rng(1)
    reqs = [make_request(rng.integers(0, CFG["vocab_size"], n).tolist(), m)
            for n, m in CASES]
    before = series()
    outs = engine.serve(reqs)
    return reqs, outs, before, series()


@pytest.mark.parametrize("case", range(len(CASES)), ids=[
    "%d+%d" % c for c in CASES])
def test_prefill_then_decode_against_the_reference(ref, served, case):
    """Every served token is the one the reference puts first at its
    position given the tokens served before it, and lies nowhere below
    the reference's best logit: the prompt's K and V went into pages and
    rings, and each step read them back at the right positions."""
    reqs, outs = served[:2]
    prompt, out = reqs[case]["prompt"], outs[case]
    assert len(out) == CASES[case][1]
    gaps, first = ref.served_gaps(CFG, SEED, prompt, out, pad=96)
    assert list(first) == list(out)
    assert float(gaps.max()) <= TOL


def test_a_ring_shorter_than_the_window_is_seen(wf, ref, monkeypatch):
    """The same run with the ring cut under the window: the step then
    reads rows that were overwritten, and the served tokens fall below
    the reference's best."""
    from veles_tpu.nn.hybrid import HybridBlock
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request
    sound = HybridBlock.cache_geometry

    def short(self, d, page_size):
        g = sound(self, d, page_size)
        return dict(g, ring=4 if g["ring"] else 0)
    monkeypatch.setattr(HybridBlock, "cache_geometry", short)
    eng = ContinuousEngine(wf, max_slots=2, buckets=(16,), max_context=48,
                           name="short_ring").start()
    try:
        prompt = numpy.random.default_rng(2).integers(
            0, CFG["vocab_size"], 14).tolist()
        (out,) = eng.serve([make_request(prompt, 30)])
    finally:
        eng.stop()
    gaps, _ = ref.served_gaps(CFG, SEED, prompt, out, pad=96)
    assert float(gaps.max()) > 1000 * TOL


def test_window_layers_keep_rings_and_full_layers_pages(engine, served):
    """What a slot holds of a window layer is the ring, whatever its
    context; the page ledger backs the full layers alone."""
    from veles_tpu.serving.pages import pages_for
    stats = engine.stats()
    ring = (pages_for(CFG["sliding_window"], engine.page_size) + 1) \
        * engine.page_size
    assert stats["kv_ring_positions"] == ring == 32
    rows = engine.page_pool.device_rows
    want_ring = want_pages = 0
    for blk, (k, v) in zip(engine.stack["blocks"], engine._caches):
        kv, kd, vd = blk.n_kv_heads, 24, 16
        if blk.window:
            assert k.shape == (engine.max_slots, ring, kv, kd)
            assert v.shape == (engine.max_slots, ring, kv, vd)
            want_ring += engine.max_slots * ring * kv * (kd + vd) * 4
        else:
            assert k.shape == (rows, engine.page_size, kv, kd)
            assert v.shape == (rows, engine.page_size, kv, vd)
            want_pages += rows * engine.page_size * kv * (kd + vd) * 4
    assert stats["kv_ring_bytes"] == want_ring > 0
    assert stats["kv_pool_bytes"] == want_ring + want_pages
    # a request reserves pages for its own worst case, once: no count of
    # it knows how many window layers there are
    assert engine.scheduler.reject_reason(30, 45) is None
    assert engine.scheduler.reject_reason(30, 51) is not None   # > 80
    assert engine.page_pool.in_use() == 0, "every finished slot's pages back"


def test_the_step_counts_its_experts(engine, served):
    """Assignments of live rows only, a sample of the fullest expert's
    load a layer a step, and the experts touched; prefill counts
    nothing."""
    _, outs, before, after = served

    def rise(name):
        return after.get(name, 0) - before.get(name, 0)
    expert_layers = sum(1 for b in engine.stack["blocks"]
                        if b.ffn == "experts")
    decoded = sum(len(o) - 1 for o in outs)    # a prefill gives the first
    assert rise("veles_moe_assignments_total") == (
        decoded * CFG["num_experts_per_tok"] * expert_layers)
    held = rise("veles_moe_assignments_held_total")
    touched = rise("veles_moe_experts_touched_total")
    steps = rise("veles_serving_decode_dispatches_total")
    assert 0 < touched <= held < rise("veles_moe_assignments_total")
    assert touched <= steps * expert_layers * CFG["n_routed_experts"]
    assert rise("peak_count") == steps * expert_layers


def test_ahead_serves_what_the_serial_order_serves_with_its_counts(wf, ref):
    """The hybrid step keeps its tokens on the device and its experts'
    counts ride them: with step n+1 dispatched before step n is read the
    engine serves, greedy and sampled, what its twin held to the serial
    order serves and what the reference puts first; the counts are read
    one step later with the tokens and are the twin's, but for the one
    row-step dropped behind the row that ends on its ``eos_id``."""
    import ahead_drill
    from veles_tpu.serving.engine import make_request
    rng = numpy.random.default_rng(3)

    def prompt(n):
        return rng.integers(0, CFG["vocab_size"], n).tolist()
    ahead, serial = ahead_drill.twins(
        wf, "hybrid_ah", max_slots=3, buckets=(16, 32), max_context=80,
        page_size=16)
    ender, short = ahead_drill.ender(
        lambda req: ahead_drill.serve_by_ticks(serial, [req])[0],
        lambda i: prompt(9), 24, 0.0)
    reqs = [make_request(prompt(5), 30),
            ender,
            make_request(prompt(30), 20, temperature=0.8, seed=4),
            make_request(prompt(16), 12),
            make_request(prompt(9), 8, temperature=0.7, seed=5)]
    expert_layers = sum(1 for b in ahead.stack["blocks"]
                        if b.ffn == "experts")
    got, rises = {}, {}
    for engine in (ahead, serial):
        events = ahead_drill.record_order(engine)
        before = series()
        got[engine] = ahead_drill.serve_by_ticks(engine, reqs)
        after = series()
        rises[engine] = {k: after.get(k, 0) - before.get(k, 0)
                         for k in after}
        steps = sum(1 for e in events if e[0] == "dispatch")
        ran_ahead = ahead_drill.ahead_of(events)
        assert (ran_ahead >= 0.8 * steps) if engine is ahead \
            else ran_ahead == 0
        decoded = sum(len(o) - 1 for o in got[engine])
        dropped = 1 if engine is ahead else 0
        assert ahead_drill.row_steps(events, 1) == decoded + dropped
        assert rises[engine]["veles_moe_assignments_total"] == (
            (decoded + dropped) * CFG["num_experts_per_tok"]
            * expert_layers)
        assert rises[engine]["peak_count"] == steps * expert_layers
        assert engine._flying is None
        assert engine.page_pool.in_use() == 0
    assert got[ahead] == got[serial] and got[ahead][1] == short
    for case in (0, 1):
        gaps, first = ref.served_gaps(CFG, SEED, reqs[case]["prompt"],
                                      got[ahead][case], pad=96)
        assert list(first) == list(got[ahead][case])
        assert float(gaps.max()) <= TOL


def test_the_step_names_its_scopes(engine, served):
    """(``combine`` is a cast and a reshape, which the compiler folds into
    its neighbours: nothing of it is left to name.)"""
    text = engine._program("step", engine.view_ladder[0]).compiled(
        ).as_text()
    for scope in ("blk0/full_attn", "blk1/window_attn", "blk1/router",
                  "blk1/dispatch", "blk1/experts", "blk0/ffn",
                  "blk1/attn_qkv", "blk1/rope", "blk1/attn_out",
                  "blk1/norm1", "page_gather", "page_writeback"):
        assert scope in text, scope
    assert "blk1/attn/" not in text and "page_gather/blk1" not in text


def test_rows_wider_than_the_lanes_are_stored_padded():
    """Keys of 160 and values of 136 are kept at 256 lanes (noughts
    beyond their width); what is served is what ``apply`` over the whole
    sequence puts first, token for token."""
    import jax.numpy as jnp
    from veles_tpu import nn
    from veles_tpu.backends import XLADevice
    from veles_tpu.loader import FullBatchLoaderMSE
    from veles_tpu.memory import Array
    from veles_tpu.nn.hybrid import stored_width
    from veles_tpu.nn.sampling import params_of
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request
    assert [stored_width(w) for w in (24, 128, 136, 192, 256, 300)] == [
        24, 128, 256, 256, 256, 384]

    class NoData(FullBatchLoaderMSE):
        hide_from_registry = True

        def load_data(self):
            z = numpy.zeros((1, 8), numpy.int32)
            self.create_originals(z, None, targets=z)
            self.class_lengths = [0, 0, 1]

    block = dict(type="hybrid_block", mixer="softmax", n_heads=2,
                 n_kv_heads=1, head_dim=160, v_head_dim=136,
                 rotary_factor=0.4, ffn="dense", dense_hidden=48,
                 weights_stddev=0.2)
    std = nn.StandardWorkflow(
        name="wide_rows",
        layers=[dict(type="embedding", vocab_size=40, dim=32, stddev=0.5,
                     name="embed"),
                dict(block, name="blk0"),
                dict(block, name="blk1", window=8, sink=True),
                dict(type="lm_head", vocab_size=40, weights_stddev=0.3,
                     name="head")],
        loader_unit=NoData(None, minibatch_size=1, name="nodata"),
        loss_function="softmax_seq")
    wide = std.extract_forward_workflow()
    wide.forwards = list(std.forwards)
    wide.forwards[0].input = Array(numpy.zeros((1, 8), numpy.int32),
                                   name="tokens")
    wide.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
    eng = ContinuousEngine(wide, max_slots=2, buckets=(16,), max_context=64,
                           name="wide_rows").start()
    try:
        prompt = numpy.random.default_rng(4).integers(0, 40, 11).tolist()
        (out,) = eng.serve([make_request(prompt, 40)])
        assert [c[0].shape[-1] for c in eng._caches] == [256, 256]
        assert [c[1].shape[-1] for c in eng._caches] == [256, 256]
    finally:
        eng.stop()
    # causal: one pass over prompt + answer gives every position's choice
    params = params_of(wide)
    x = jnp.asarray(prompt + out)[None]
    for f in wide.forwards:
        x = f.apply(params[f.name], x)
    first = numpy.asarray(jnp.argmax(x[0], axis=-1))
    assert list(first[len(prompt) - 1:-1]) == out


# -- the shares add up -------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(ref):
    """Sigmoid routing over all 16 experts, no shared one: the parts that
    4 chips of 4 experts each compute add up to what one chip holding all
    16 computes."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.nn.experts import sparse_experts
    d, f, e, k = 64, 32, 16, 4
    keys = jax.random.split(jax.random.key(3), 6)
    p = {"router": 0.3 * jax.random.normal(keys[0], (d, e)),
         "router_bias": 0.3 * jax.random.normal(keys[1], (e,)),
         "e_gate": 0.2 * jax.random.normal(keys[2], (e, d, f)),
         "e_up": 0.2 * jax.random.normal(keys[3], (e, d, f)),
         "e_down": 0.2 * jax.random.normal(keys[4], (e, f, d))}
    x = jax.random.normal(keys[5], (2, 24, d))

    def scope(_):
        return contextlib.nullcontext()

    def part(held):
        local_of = numpy.full((e,), -1, numpy.int32)
        local_of[held] = numpy.arange(len(held))
        leaves = dict(p, **{n: p[n][jnp.asarray(held)]
                            for n in ("e_gate", "e_up", "e_down")})
        return sparse_experts(leaves, x, top_k=k, local_of=local_of,
                              n_held=len(held), precision=None, scope=scope,
                              block=16, router="sigmoid", shared=False)
    whole = part(list(range(e)))
    parts = sum(part(list(range(c * 4, c * 4 + 4))) for c in range(4))
    assert float(jnp.abs(whole).max()) > 0.1
    assert float(jnp.abs(parts - whole).max()) <= 1e-5 * float(
        jnp.abs(whole).max())


# -- what the block does not serve -------------------------------------------

@pytest.mark.parametrize("knobs,names", [
    (dict(quant_weights=True), "--quant-weights"),
    (dict(quant_kv=True), "--quant-kv"),
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(prefill_chunk=8), "chunked prefill"),
    (dict(tp=2), "--serve-tp"),
    (dict(artifact="/nowhere"), "serve-artifact"),
    (dict(draft="wf"), "a draft model"),
], ids=lambda k: next(iter(k)) if isinstance(k, dict) else None)
def test_the_constructor_refuses_what_the_block_does_not_serve(wf, knobs,
                                                                names):
    from veles_tpu.serving import ContinuousEngine
    if knobs.get("draft") == "wf":
        knobs = dict(draft=wf)
    with pytest.raises(ValueError) as refused:
        ContinuousEngine(wf, max_slots=2, buckets=(16,), max_context=48,
                         **knobs)
    assert "blk0 (HybridBlock)" in str(refused.value)
    assert names in str(refused.value)


@pytest.mark.parametrize("mode", ["speculative", "beam"])
def test_accepts_refuses_the_other_decode_modes(engine, mode):
    from veles_tpu.serving.engine import make_request
    reason = engine.accepts(make_request([1, 2, 3], 4, mode=mode))
    assert reason and "blk0 (HybridBlock)" in reason and mode in reason
    assert engine.window_fallback is False


def test_the_scan_sampler_refuses_the_block(wf):
    from veles_tpu.error import VelesError
    from veles_tpu.nn import sampling
    for call in (lambda: sampling.generate(wf, [1, 2, 3], 4),
                 lambda: sampling.prompt_logits(wf, [1, 2, 3])):
        with pytest.raises(VelesError) as refused:
            call()
        assert "blk0 is a hybrid_block" in str(refused.value)


def test_a_refused_mode_is_a_400_and_not_the_window_plane(wf):
    from veles_tpu.restful_api import GenerationAPI
    api = GenerationAPI(wf, port=0, max_slots=2, buckets=[16],
                        max_context=48, name="hybrid_refusal")
    api.initialize()
    try:
        def post(body):
            req = urllib.request.Request(
                "http://127.0.0.1:%d/generate" % api.port,
                json.dumps(body).encode(),
                {"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())
        code, answer = post({"prompt": [1, 2, 3], "n_new": 4,
                             "mode": "beam"})
        assert code == 400 and "blk0 (HybridBlock)" in answer["error"]
        code, answer = post({"prompt": [1, 2, 3], "n_new": 4})
        assert code == 200 and len(answer["tokens"]) == 4
        assert answer["engine"] == "continuous"
    finally:
        api.stop()


def test_a_mixer_without_rows_is_not_served():
    from veles_tpu.error import VelesError
    from veles_tpu.nn.hybrid import HybridBlock
    blk = HybridBlock(None, mixer="attention", name="blk9")
    with pytest.raises(VelesError) as refused:
        blk.cache_geometry(64, 16)
    assert "blk9" in str(refused.value) and "'attention'" in str(
        refused.value)
