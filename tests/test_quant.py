"""Quantization subsystem (veles_tpu/quant/): int8 weights with
dequant-on-read serving, int8 KV-cache slot pool, and the offline
``veles-tpu quantize`` snapshot CLI.

The contracts under test: quant-OFF is bit-identical to a build
without the feature (and leaks zero quant counters), quantized greedy
serving is TOKEN-EXACT vs float on the bench model, the int8 pool
halves its HBM at the same ``max_slots``, a quantized snapshot resumes
anywhere a plain one does, and an injected ``quant.calibrate`` fault
degrades instead of wedging the serving plane."""
import os

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import prng
from veles_tpu.error import VelesError
from veles_tpu.ops.precision import (INT8_QMAX, dequantize_int8,
                                     dequantize_rows_int8,
                                     quantize_int8, quantize_rows_int8)
from veles_tpu.quant import (QUANT_COUNTERS, dequantize_params,
                             is_quantized_params, quantize_params,
                             quantize_state, dequantize_state)
from veles_tpu.serving import ContinuousEngine
from veles_tpu.serving.engine import make_request
from veles_tpu.telemetry.counters import counters

from conftest import import_model


@pytest.fixture(scope="module")
def trained():
    """The serving-engine test model (same geometry + seed as
    tests/test_serving_engine.py, where the float contracts live)."""
    lm = import_model("char_lm")
    prng.seed_all(971)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                           dim=32, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return lm, wf


def _prompt(lm, seed, length=10):
    return [int(t) for t in
            lm.make_corpus(numpy.random.RandomState(seed), length)]


def _requests(lm):
    """Mixed greedy/sampled load — the set the id-exactness bar is
    measured on (greedy rows must match float exactly; sampled rows
    must at least be deterministic, and DO match on this model)."""
    return [make_request(_prompt(lm, 40 + s, 5 + s % 6), 6,
                         temperature=0.8 if s % 2 else 0.0,
                         seed=40 + s)
            for s in range(5)]


# -- numeric core (ops/precision.py) -----------------------------------------

def test_per_channel_round_trip_error_bounded():
    w = numpy.random.RandomState(0).randn(64, 24).astype(numpy.float32)
    q, scale = quantize_int8(w, axis=-1)
    q, scale = numpy.asarray(q), numpy.asarray(scale)
    assert q.dtype == numpy.int8
    assert scale.shape == (1, 24)
    err = numpy.abs(numpy.asarray(dequantize_int8(q, scale)) - w)
    # symmetric rounding error is at most half an lsb per column
    assert (err <= scale / 2 + 1e-7).all()
    # per-channel beats per-tensor on spread columns
    w[:, 3] *= 100.0
    qt, st = quantize_int8(w, axis=None)
    assert numpy.asarray(st).shape == ()
    qc, sc = quantize_int8(w, axis=-1)
    err_t = numpy.abs(numpy.asarray(dequantize_int8(qt, st)) - w)[:, 0]
    err_c = numpy.abs(numpy.asarray(dequantize_int8(qc, sc)) - w)[:, 0]
    assert err_c.max() < err_t.max()


def test_zero_and_extreme_groups_are_safe():
    w = numpy.zeros((8, 40), numpy.float32)
    w[:, 1] = 3.0
    q, scale = quantize_int8(w, axis=-1)
    out = numpy.asarray(dequantize_int8(q, scale))
    assert (out[:, 0] == 0).all() and (out[:, 1] == 3.0).all()
    assert numpy.asarray(q).max() <= INT8_QMAX


def test_kv_row_quant_is_per_position_and_requant_stable():
    x = numpy.random.RandomState(1).randn(6, 4, 8).astype(numpy.float32)
    q, s = quantize_rows_int8(x)
    assert numpy.asarray(q).shape == x.shape
    assert numpy.asarray(s).shape == (6,)
    back = numpy.asarray(dequantize_rows_int8(q, s))
    assert numpy.abs(back - x).max() <= numpy.asarray(s).max() / 2 + 1e-7
    # re-quantizing a dequantized row with its own scale is bit-exact —
    # the no-error-accumulation property the decode step relies on
    q2, s2 = quantize_rows_int8(back)
    assert (numpy.asarray(q2) == numpy.asarray(q)).all()
    assert numpy.allclose(numpy.asarray(s2), numpy.asarray(s))


# -- parameter trees ----------------------------------------------------------

def test_quantize_params_eligibility_and_round_trip(trained):
    from veles_tpu.nn.sampling import params_of
    lm, wf = trained
    params = params_of(wf)
    qp, report = quantize_params(params)
    assert is_quantized_params(qp) and not is_quantized_params(params)
    assert report["params"] > 0
    assert report["bytes_after"] < report["bytes_before"] / 3
    for uname, uparams in qp.items():
        # embedding tables and 1-D tensors ride through untouched
        for pname, val in uparams.items():
            if pname == "table" or getattr(
                    params[uname][pname], "ndim", 0) != 2:
                assert not isinstance(val, dict), (uname, pname)
    dp = dequantize_params(qp)
    for uname, uparams in params.items():
        for pname, arr in uparams.items():
            a, b = numpy.asarray(arr), numpy.asarray(dp[uname][pname])
            assert a.shape == b.shape
            if not isinstance(qp[uname][pname], dict):
                assert (a == b).all()


def test_dequantized_weights_move_no_logit_past_a_quarter(trained):
    """What int8 weights cost in the model's own terms: the prompt's
    last-position logits through the dequantized twin lie within 0.25
    of the float tree's (broken scales move them by whole units)."""
    from veles_tpu.nn.sampling import params_of, prompt_logits
    lm, wf = trained
    dq = dequantize_params(quantize_params(params_of(wf))[0])
    deltas = [float(numpy.abs(
        numpy.asarray(prompt_logits(wf, r["prompt"]))
        - numpy.asarray(prompt_logits(wf, r["prompt"], params=dq))
    ).max()) for r in _requests(lm)[:2]]      # eager: seconds a prompt
    assert 0.0 < max(deltas) <= 0.25, deltas


def test_bad_granularity_rejected(trained):
    from veles_tpu.nn.sampling import params_of
    _lm, wf = trained
    with pytest.raises(VelesError, match="granularity"):
        quantize_params(params_of(wf), granularity="per_banana")


# -- serving engine: off = leak-free, on = token-exact ------------------------

def test_quant_off_engine_leaks_no_quant_counters(trained):
    lm, wf = trained
    before = counters.snapshot()
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8, 16),
                              max_context=48, name="qoff").start()
    try:
        from veles_tpu.nn import sampling
        req = make_request(_prompt(lm, 7), 5)
        assert engine.serve([req])[0] == sampling.generate(
            wf, req["prompt"], req["n_new"], temperature=0)
        assert engine.stats()["quant_weights"] == 0
        assert engine.stats()["artifact_mode"] == 0
    finally:
        engine.stop()
    delta = counters.delta(before, names=QUANT_COUNTERS)
    assert delta == {}, "quant counters leaked into a quant-off run"


@pytest.mark.parametrize("qw,qkv", [(True, False), (False, True),
                                    (True, True)])
def test_quantized_greedy_and_sampled_token_exact(trained, qw, qkv):
    """The headline quality bar: int8 serving (weights, KV cache, or
    both) answers the bench model's requests with the exact tokens the
    float plane produces — greedy rows by contract, sampled rows
    measured-and-locked on this model."""
    lm, wf = trained
    from veles_tpu.nn import sampling
    reqs = _requests(lm)
    ref = [sampling.generate(wf, r["prompt"], r["n_new"],
                             temperature=r["temperature"],
                             seed=r["seed"]) for r in reqs]
    engine = ContinuousEngine(wf, max_slots=3, buckets=(8, 16),
                              max_context=48, quant_weights=qw,
                              quant_kv=qkv,
                              name="q_%d%d" % (qw, qkv)).start()
    try:
        assert engine.serve(list(reqs)) == ref
        # concurrent == solo (per-slot PRNG independence survives
        # quantization — the noise derives from seeds, not weights)
        solo = [engine.serve([r])[0] for r in reqs]
        assert solo == ref
        assert engine.programs_built <= engine.programs_bound()
    finally:
        engine.stop()


@pytest.mark.parametrize("qw", [False, True], ids=["f32w", "int8w"])
@pytest.mark.parametrize("decode_block", [1, 4])
def test_int8_pool_ladder_serves_what_the_whole_view_serves(
        trained, decode_block, qw):
    """The int8 pool's decode step gathers and writes through the same
    table: at the shortest view that holds every active row it serves,
    token for token, greedy and sampled, what it serves at the whole
    view (tests/test_serving_engine.py holds the float pool to the
    same drill)."""
    import ladder_drill
    lm, wf = trained
    ladder, whole = ladder_drill.twins(
        wf, "q_lad%d%d" % (decode_block, qw), quant_kv=True,
        quant_weights=qw, decode_block=decode_block)
    assert ladder.view_ladder == (16, 8)
    seen = ladder_drill.record_rungs(ladder)
    reqs = ladder_drill.requests(lambda seed, n: _prompt(lm, seed, n))
    got = ladder_drill.serve_by_ticks(ladder, reqs)
    assert got == ladder_drill.serve_by_ticks(whole, reqs)
    assert [len(t) for t in got] == [r["n_new"] for r in reqs]
    ladder_drill.assert_shortest_rungs(ladder, seen)
    assert {pages for pages, _ in seen} == {8, 16}
    assert whole.stats()["view_share"] == 1.0
    assert ladder.programs_bound() == len(ladder.buckets) + 2
    assert ladder.page_pool.in_use() == whole.page_pool.in_use() == 0


@pytest.mark.parametrize("qw", [False, True], ids=["f32w", "int8w"])
@pytest.mark.parametrize("decode_block", [1, 4])
def test_int8_pool_ahead_serves_what_the_serial_order_serves(
        trained, decode_block, qw):
    """The int8 pool's step keeps its tokens on the device too: with
    step n+1 dispatched before step n is read it serves, token for
    token, greedy and sampled, what it serves in the serial order; a
    row that ends on an ``eos_id`` costs one dropped row-step and
    poisons nobody (tests/test_serving_engine.py holds the float pool
    to the same drill)."""
    import ahead_drill
    import ladder_drill
    lm, wf = trained
    ahead, serial = ahead_drill.twins(
        wf, "q_ah%d%d" % (decode_block, qw), quant_kv=True,
        quant_weights=qw, decode_block=decode_block)
    ender, short = ahead_drill.ender(
        lambda req: ahead_drill.serve_by_ticks(serial, [req])[0],
        lambda i: _prompt(lm, 600 + 10 * i, 6), 18, 0.8, seed=9)
    reqs = ladder_drill.requests(lambda seed, n: _prompt(lm, seed, n))
    reqs.insert(1, ender)
    events, events_s = (ahead_drill.record_order(e)
                        for e in (ahead, serial))
    got = ahead_drill.serve_by_ticks(ahead, reqs)
    assert got == ahead_drill.serve_by_ticks(serial, reqs)
    assert got[1] == short
    assert [len(t) for i, t in enumerate(got) if i != 1] == [
        r["n_new"] for i, r in enumerate(reqs) if i != 1]
    steps = sum(1 for e in events if e[0] == "dispatch")
    assert ahead_drill.ahead_of(events) >= 0.8 * steps
    assert ahead_drill.ahead_of(events_s) == 0
    assert (ahead_drill.row_steps(events, decode_block)
            - ahead_drill.row_steps(events_s, decode_block)) == decode_block
    assert ahead.programs_built <= ahead.programs_bound()
    assert ahead.page_pool.in_use() == serial.page_pool.in_use() == 0


def test_int8_pool_halves_hbm(trained):
    lm, wf = trained
    sizes = {}
    for qkv in (False, True):
        engine = ContinuousEngine(wf, max_slots=3, buckets=(8, 16),
                                  max_context=48, quant_kv=qkv,
                                  name="pool_%d" % qkv).start()
        try:
            engine.serve([make_request(_prompt(lm, 9), 3)])
            sizes[qkv] = engine.stats()["kv_pool_bytes"]
        finally:
            engine.stop()
    # int8 payload + f32 per-position scales vs f32 payload: < 0.5x
    assert sizes[True] < sizes[False] / 2


def test_quant_calibrate_fault_degrades_then_recovers(trained,
                                                     monkeypatch):
    """An injected calibration fault fails the serving tick; the
    queued request survives the failed tick and is answered correctly
    once the (times=1) fault is spent — degrade, don't wedge."""
    lm, wf = trained
    from veles_tpu.nn import sampling
    from veles_tpu.resilience.faults import FaultInjected
    from veles_tpu.resilience import faults
    monkeypatch.setenv("VELES_FAULTS", "quant.calibrate:raise:times=1")
    with pytest.raises(FaultInjected):
        from veles_tpu.nn.sampling import params_of
        quantize_params(params_of(wf))
    # the times=1 clause is spent; re-arm it for the engine phase (an
    # unchanged spec string never re-arms by itself)
    faults.plane.configure()
    before = counters.get("veles_faults_injected_total")
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8, 16),
                              max_context=48, quant_weights=True,
                              name="qfault").start()
    try:
        req = make_request(_prompt(lm, 11), 4)
        assert engine.serve([req], timeout=60)[0] == \
            sampling.generate(wf, req["prompt"], req["n_new"],
                              temperature=0)
    finally:
        engine.stop()
        monkeypatch.setenv("VELES_FAULTS", "")
    assert counters.get("veles_faults_injected_total") > before


# -- snapshot plane (veles-tpu quantize) --------------------------------------

def test_quantize_state_round_trip_bounds(trained):
    from veles_tpu.snapshotter import collect_state
    _lm, wf = trained
    state = collect_state(wf)
    qstate, report = quantize_state(state)
    assert report["params"] > 0
    assert qstate["__meta__"]["quant"]["params"] == report["params"]
    # input state is not mutated
    assert not any(isinstance(v, dict) and "__quant__" in v
                   for sd in state["__units__"].values()
                   if isinstance(sd, dict) for v in sd.values())
    ds = dequantize_state(qstate)
    for uname, sd in state["__units__"].items():
        for pname, arr in sd.items():
            if not isinstance(arr, numpy.ndarray):
                continue
            back = ds["__units__"][uname][pname]
            assert back.dtype == arr.dtype
            if isinstance(qstate["__units__"][uname][pname], dict):
                col_max = numpy.abs(arr).max(axis=0)
                assert numpy.abs(back - arr).max() <= \
                    col_max.max() / (2 * 127) + 1e-6
            else:
                assert (back == arr).all()


def test_quantize_cli_snapshot_resumes_and_serves(trained, tmp_path):
    """End to end: snapshot → ``veles-tpu quantize`` → resume → the
    resumed model's greedy decode equals the LIVE engine serving the
    original weights under ``quant_weights`` — both paths apply the
    same int8 scheme, so they must agree token for token."""
    from veles_tpu.__main__ import main as cli_main
    from veles_tpu.nn import sampling
    from veles_tpu.snapshotter import Snapshotter, resume
    lm, wf = trained
    snap = Snapshotter(wf, prefix="qt", directory=str(tmp_path),
                       compression="gz", async_mode=False)
    snap._runs = 1
    path = snap.export()
    assert cli_main(["quantize", path]) == 0
    qpath = path.replace(".pickle", ".int8.pickle")
    assert os.path.exists(qpath)
    assert os.path.getsize(qpath) < os.path.getsize(path)
    lm2 = import_model("char_lm")
    prng.seed_all(971)
    wf2 = lm2.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                             dim=32, n_train=256, n_valid=64)
    wf2.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    resume(wf2, qpath)
    prompt = _prompt(lm, 13)
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8, 16),
                              max_context=48, quant_weights=True,
                              name="qsnap").start()
    try:
        served = engine.serve([make_request(prompt, 6)])[0]
    finally:
        engine.stop()
    assert served == sampling.generate(wf2, prompt, 6, temperature=0)


def test_quantize_cli_rejects_unquantizable_path(tmp_path, capsys):
    from veles_tpu.__main__ import main as cli_main
    missing = str(tmp_path / "nope.pickle.gz")
    assert cli_main(["quantize", missing]) == 1
    assert "quantize failed" in capsys.readouterr().err
