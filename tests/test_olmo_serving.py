"""The served post-norm delta-rule hybrid (``nn/hybrid.py``
``mixer="delta_rule"`` beside ``mixer="softmax"`` full layers) at the
``tiny-olmo`` size, float32, seeded weights: gated delta-rule layers whose
``beta`` reaches 2, three to one full multi-head layer with norms on the
whole q and k projections and no rotary, norms after the sublayers, a final
norm before the head.

- ``apply`` over a sequence against the plain reference
  (``chipbench/reference_olmo.py``), and each mechanism shown to matter:
  the reference without it misses the tolerance;
- the served forms (``serve_prefill`` from a zero state over a
  bucket-padded prompt, then ``serve_step`` a token at a time on the
  slot's state, tail and K/V rows) against the reference's full forward,
  logits compared, at prompt lengths that are no multiple of the chunk or
  of a bucket;
- prefill then decode through ``ContinuousEngine`` against the same
  reference, two requests in turn on one slot, and the step one ahead of
  its reads;
- a row that is not live keeps its state and tail bit for bit;
- a slot's state bytes do not grow with its context, and the pages are the
  full layers' alone;
- the modes the stack does not serve are refused with a line that names
  the block;
- the step's counter and scopes, and ``state_bytes`` on ``/stats`` and
  ``/metrics``.

Tolerances. Program and reference are both float32 (the CPU multiplies
float32 exactly at either precision setting) and differ in the order of
their sums and in the delta rule's form (the program's chunks solve a
triangular system, the reference runs token by token): 6.3e-5 against
logits of 4.8 was read on the builder's run (1.3e-5 of the largest), so
``TOL`` = 1e-4 of the largest logit is 7.6 times that; the mechanisms'
faults read 1.57 to 16.3, four orders above it.
"""
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

with open(os.path.join(ROOT, "chipbench", "configs", "tiny-olmo.json")) as _f:
    CFG = json.load(_f)


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
    return ref.make_tokens(SEED, 1, 70, CFG["vocab_size"])[0, :70]


@pytest.fixture(scope="module")
def program_logits(wf, tokens):
    import jax.numpy as jnp
    from veles_tpu.nn.sampling import params_of
    params = params_of(wf)
    x = jnp.asarray(tokens)[None]
    for f in wf.forwards:
        x = f.apply(params[f.name], x)
    return numpy.asarray(x[0])


def blocks_of(wf):
    return [f for f in wf.forwards if type(f).__name__ == "HybridBlock"]


def test_layers_are_what_the_configuration_says(wf):
    blocks = blocks_of(wf)
    assert [b.mixer for b in blocks] == (["delta_rule"] * 3 + ["softmax"]) * 2
    assert all(b.norm_place == "post" and b.ffn == "dense" for b in blocks)
    full, linear = blocks[3], blocks[0]
    assert full.qk_norm and full._widths(CFG["hidden_size"]) == (16, 16, 0)
    assert (linear.n_key_heads, linear.key_dim, linear.value_dim,
            linear.beta_scale) == (2, 8, 16, 2.0)
    assert type(wf.forwards[-2]).__name__ == "FinalNorm"


def test_apply_against_the_reference(ref, tokens, program_logits):
    want = numpy.asarray(ref.logits_fn(CFG, SEED, tokens))
    assert numpy.abs(want).max() > 1.0, "logits too flat to tell"
    assert numpy.abs(program_logits - want).max() <= TOL * numpy.abs(
        want).max()


@pytest.mark.parametrize("fault", ["beta_single", "pre_norm",
                                   "per_head_qk_norm", "rotary",
                                   "no_final_norm"])
def test_each_mechanism_matters(ref, tokens, program_logits, fault):
    """The reference with one mechanism left out (``beta`` undoubled, the
    norms before the sublayers, q and k normalised a head at a time, a
    rotary, no final norm) misses the tolerance by three orders or more:
    the program has it, and the test would see it go."""
    bad = numpy.asarray(ref.logits_fn(CFG, SEED, tokens, pick=fault))
    assert numpy.abs(program_logits - bad).max() > 1000 * TOL * numpy.abs(
        bad).max()


# -- the served forms, logits against the full forward ------------------------

def served_logits(wf, prompt, answer, bucket, context):
    """Logits at the prompt's last position and after each token of
    ``answer``, through the blocks' own served forms: ``serve_prefill``
    over the prompt padded to ``bucket``, then ``serve_step`` a token at a
    time on one slot's delta-rule state and tail and its full layers' K/V
    rows (a view of ``context`` positions, in order)."""
    import jax.numpy as jnp
    from veles_tpu.nn.sampling import (_embed_ids, _embed_prompt,
                                       _head_logits, _prefill_blocks,
                                       params_of, split_stack)
    stack = split_stack(list(wf.forwards), hybrid=True)
    params = params_of(wf)
    stem, norm, head = stack["stem"], stack["norm"], stack["head"]
    t_p = len(prompt)
    ids = numpy.zeros((1, bucket), numpy.int32)
    ids[0, :t_p] = prompt
    x = _embed_prompt(stem, None, params, jnp.asarray(ids))
    x, caches = _prefill_blocks(stack["blocks"], params, x, bucket, stem.dim,
                                live=jnp.arange(bucket) < t_p)
    out = [_head_logits(head, params, x[0, t_p - 1], None, norm=norm)]
    held = []
    for blk, (a, b) in zip(stack["blocks"], caches):
        if blk.mixer == "delta_rule":
            held.append((a, b))
        else:
            pad = ((0, 0), (0, context - t_p), (0, 0), (0, 0))
            held.append((jnp.pad(a[:, :t_p], pad), jnp.pad(b[:, :t_p], pad)))
    live = jnp.ones((1,), bool)
    for i, tok in enumerate(answer):
        x = _embed_ids(stem, params, jnp.asarray([tok], jnp.int32))
        pos = jnp.asarray([t_p + i], jnp.int32)
        new = []
        for blk, (a, b) in zip(stack["blocks"], held):
            x, a, b, _, _ = blk.serve_step(params[blk.name], x, a, b, pos,
                                           live)
            new.append((a, b))
        held = new
        out.append(_head_logits(head, params, x[0], None, norm=norm))
    return numpy.asarray(jnp.stack(out))


@pytest.mark.parametrize("t_p,bucket", [(5, 16), (37, 64), (70, 128)],
                         ids=["5in16", "37in64", "70in128"])
def test_served_forms_against_the_full_forward(wf, ref, t_p, bucket):
    """A prompt of no whole chunk (64) or bucket, its padding leaving the
    state alone, and the tokens after it one step at a time: every
    position's logits are the reference's."""
    seq = ref.make_tokens(SEED + t_p, 1, t_p + 12,
                          CFG["vocab_size"])[0, :t_p + 12]
    got = served_logits(wf, seq[:t_p], seq[t_p:], bucket, t_p + 16)
    want = numpy.asarray(ref.logits_fn(CFG, SEED, seq))[t_p - 1:]
    assert got.shape == want.shape
    assert numpy.abs(got - want).max() <= TOL * numpy.abs(want).max()


def test_a_row_that_is_not_live_keeps_its_state_bit_for_bit(wf):
    import jax
    import jax.numpy as jnp
    from veles_tpu.nn.sampling import params_of
    blk = blocks_of(wf)[0]
    shape, tail_shape = (blk.cache_geometry(CFG["hidden_size"], 16)[k]
                         for k in ("state", "tail"))
    keys = jax.random.split(jax.random.key(7), 3)
    state = jax.random.normal(keys[0], (4,) + shape)
    tail = jax.random.normal(keys[1], (4,) + tail_shape)
    x = jax.random.normal(keys[2], (4, CFG["hidden_size"]))
    live = jnp.asarray([True, False, True, False])
    _, s2, t2, _, _ = blk.serve_step(params_of(wf)[blk.name], x, state, tail,
                                     jnp.zeros((4,), jnp.int32), live)
    s2, t2, state, tail = map(numpy.asarray, (s2, t2, state, tail))
    for row in (1, 3):
        assert s2[row].tobytes() == state[row].tobytes()
        assert t2[row].tobytes() == tail[row].tobytes()
    for row in (0, 2):
        assert not numpy.array_equal(s2[row], state[row])
        # the tail moves up by the one position the row took
        assert numpy.array_equal(t2[row][:-1], tail[row][1:])


# -- through the engine -------------------------------------------------------

@pytest.fixture(scope="module")
def engine(wf):
    from veles_tpu.serving import ContinuousEngine
    eng = ContinuousEngine(wf, max_slots=4, buckets=(16, 32),
                           max_context=80, name="olmo_test").start()
    yield eng
    eng.stop()


def series():
    from veles_tpu.telemetry.counters import counters
    return dict(counters.snapshot())


#: (prompt, answer): prompts of no whole bucket, one that fills its bucket,
#: answers that run the context to the end of the view
CASES = [(5, 40), (30, 45), (17, 60), (32, 48), (9, 20)]


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
    the reference's best logit: the prompt's state and K/V rows went into
    the slot, and each step read and advanced them."""
    reqs, outs = served[:2]
    prompt, out = reqs[case]["prompt"], outs[case]
    assert len(out) == CASES[case][1]
    gaps, first = ref.served_gaps(CFG, SEED, prompt, out, pad=96)
    assert list(first) == list(out)
    assert float(gaps.max()) <= TOL


def test_two_requests_in_turn_on_one_slot(wf, ref):
    """A slot's second request starts from nothing: its prefill overwrites
    the state and tail rows whole."""
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request
    rng = numpy.random.default_rng(6)
    first, second = (rng.integers(0, CFG["vocab_size"], n).tolist()
                     for n in (29, 7))
    eng = ContinuousEngine(wf, max_slots=1, buckets=(16, 32),
                           max_context=80, name="one_slot").start()
    try:
        (a,) = eng.serve([make_request(first, 40)])
        (b,) = eng.serve([make_request(second, 30)])
        assert eng.admitted == 2
    finally:
        eng.stop()
    for prompt, out in ((first, a), (second, b)):
        gaps, top = ref.served_gaps(CFG, SEED, prompt, out, pad=96)
        assert list(top) == list(out) and float(gaps.max()) <= TOL


def test_ahead_serves_what_the_serial_order_serves(wf, ref):
    """With step n+1 dispatched before step n is read, greedy and sampled,
    the engine serves what its twin held to the serial order serves, the
    row that ends on its ``eos_id`` among them (its dropped row-step moved
    a state that the slot's next prefill overwrites)."""
    import ahead_drill
    from veles_tpu.serving.engine import make_request
    rng = numpy.random.default_rng(3)

    def prompt(n):
        return rng.integers(0, CFG["vocab_size"], n).tolist()
    ahead, serial = ahead_drill.twins(
        wf, "olmo_ah", max_slots=3, buckets=(16, 32), max_context=80,
        page_size=16)
    ender, short = ahead_drill.ender(
        lambda req: ahead_drill.serve_by_ticks(serial, [req])[0],
        lambda i: prompt(9), 24, 0.0)
    reqs = [make_request(prompt(5), 30), ender,
            make_request(prompt(30), 20, temperature=0.8, seed=4),
            make_request(prompt(16), 12),
            make_request(prompt(9), 8, temperature=0.7, seed=5)]
    got = {}
    for engine in (ahead, serial):
        got[engine] = ahead_drill.serve_by_ticks(engine, reqs)
        assert engine._flying is None and engine.page_pool.in_use() == 0
    assert got[ahead] == got[serial] and got[ahead][1] == short
    gaps, first = ref.served_gaps(CFG, SEED, reqs[0]["prompt"],
                                  got[ahead][0], pad=96)
    assert list(first) == list(got[ahead][0]) and float(gaps.max()) <= TOL


def test_a_slot_holds_a_state_row_and_pages_are_the_full_layers(engine,
                                                                 served):
    """A delta-rule layer's pool is a row a slot, whatever its context:
    the state in float32 and the convolution's last three inputs; the
    page pool is the full layers' alone, and every finished slot's pages
    are back."""
    from chipbench import counts_olmo
    stats = engine.stats()
    rows = engine.page_pool.device_rows
    want_state = want_pages = 0
    for blk, (a, b) in zip(engine.stack["blocks"], engine._caches):
        if blk.mixer == "delta_rule":
            assert a.shape == (engine.max_slots, 2, 8, 16)
            assert a.dtype == numpy.float32
            assert b.shape == (engine.max_slots, 3, 2 * 2 * 8 + 2 * 16)
            want_state += a.nbytes + b.nbytes
        else:
            assert a.shape == b.shape == (rows, engine.page_size, 4, 16)
            want_pages += a.nbytes + b.nbytes
    assert stats["state_bytes"] == want_state == engine.max_slots * (
        counts_olmo.state_bytes_per_slot(CFG, tail_bytes=4))
    assert stats["kv_pool_bytes"] == want_state + want_pages
    assert stats["kv_ring_bytes"] == 0 and stats["kv_ring_positions"] == 0
    # what a position costs is the full layers' K and V alone
    assert want_pages == rows * engine.page_size * (
        counts_olmo.kv_bytes_per_token(CFG, 4))
    assert engine.scheduler.reject_reason(30, 50) is None
    assert engine.scheduler.reject_reason(30, 51) is not None   # > 80
    assert engine.page_pool.in_use() == 0


def test_the_step_counts_its_state_rows(engine, served):
    """Live rows x delta-rule layers of every decode dispatch, read with
    the tokens; prefill counts nothing."""
    _, outs, before, after = served
    linear = sum(1 for b in engine.stack["blocks"] if b.mixer == "delta_rule")
    decoded = sum(len(o) - 1 for o in outs)    # a prefill gives the first
    rise = after.get("veles_serving_state_rows_total", 0) - before.get(
        "veles_serving_state_rows_total", 0)
    assert linear == 6 and rise == decoded * linear


def test_the_programs_name_their_scopes(engine, served):
    step = engine._program("step", engine.view_ladder[0]).compiled(
        ).as_text()
    for scope in ("blk0/delta_rule", "blk0/conv", "blk0/delta_in",
                  "blk0/delta_out", "blk0/delta_gate", "blk3/full_attn",
                  "blk3/attn_qkv", "blk0/norm1", "blk0/ffn", "norm/",
                  "page_gather", "page_writeback"):
        assert scope in step, scope
    assert "blk3/rope" not in step
    prefill = engine._program("prefill", 16).compiled().as_text()
    assert "blk0/state_write" in prefill and "blk0/delta_rule" in prefill


# -- what the stack does not serve --------------------------------------------

@pytest.mark.parametrize("knobs,names", [
    (dict(quant_weights=True), "--quant-weights"),
    (dict(quant_kv=True), "--quant-kv"),
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(prefill_chunk=8), "chunked prefill"),
    (dict(tp=2), "--serve-tp"),
    (dict(artifact="/nowhere"), "serve-artifact"),
    (dict(draft="wf"), "a draft model"),
], ids=lambda k: next(iter(k)) if isinstance(k, dict) else None)
def test_the_constructor_refuses_what_the_stack_does_not_serve(wf, knobs,
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


def test_the_scan_sampler_refuses_the_stack(wf):
    from veles_tpu.error import VelesError
    from veles_tpu.nn import sampling
    with pytest.raises(VelesError) as refused:
        sampling.generate(wf, [1, 2, 3], 4)
    assert "blk0 is a hybrid_block" in str(refused.value)
    # the final norm alone is refused by name too
    forwards = [f for f in wf.forwards
                if type(f).__name__ != "HybridBlock"]
    with pytest.raises(VelesError) as refused:
        sampling.split_stack(forwards)
    assert "norm is a final_norm" in str(refused.value)


def test_a_refused_mode_is_a_400_and_state_bytes_are_reported(wf):
    from veles_tpu.restful_api import GenerationAPI
    api = GenerationAPI(wf, port=0, max_slots=2, buckets=[16],
                        max_context=48, name="olmo_refusal")
    api.initialize()
    base = "http://127.0.0.1:%d" % api.port
    try:
        def post(body):
            req = urllib.request.Request(
                base + "/generate", json.dumps(body).encode(),
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
        with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
            page = resp.read().decode()
        state = api._engine.stats()["state_bytes"]
        assert state > 0
        assert "veles_serving_state_bytes %d" % state in page or (
            "veles_serving_state_bytes %s" % float(state) in page)
    finally:
        api.stop()
