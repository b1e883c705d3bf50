"""Telemetry subsystem (veles_tpu/telemetry/): deterministic
accounting — counters, spans, cost model, Chrome-trace export. The
regression locks here are the ones no clock can hold through host
noise: cached decode is ONE dispatch per lax.scan (the round-5
speculative finding was a dispatch-count story), and a subsystem that
is off counts nothing."""
import json
import threading
import urllib.request

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import (linalg, loadgen, overlap, quant, resilience,
                       serving, telemetry)
from veles_tpu.telemetry import Cost, CostModel, peak_bf16_flops
from veles_tpu.telemetry import chrome_trace, spans
from veles_tpu.telemetry.counters import counters
from veles_tpu.telemetry.cost import cost_of_fn

from conftest import import_model


# -- counters ----------------------------------------------------------------

def test_counter_registry_thread_safety():
    counters.reset()
    n_threads, n_incs = 8, 2000

    def worker():
        for _ in range(n_incs):
            counters.inc("t_threads_total")
            counters.inc("t_bytes_total", 3)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counters.get("t_threads_total") == n_threads * n_incs
    assert counters.get("t_bytes_total") == 3 * n_threads * n_incs


def test_counter_delta_and_prometheus_text():
    counters.reset()
    before = counters.snapshot()
    counters.inc("veles_dispatches_total", 4)
    delta = counters.delta(before)
    assert delta == {"veles_dispatches_total": 4}
    text = counters.prometheus_text()
    assert "# HELP veles_dispatches_total" in text
    assert "# TYPE veles_dispatches_total counter" in text
    assert "veles_dispatches_total 4" in text
    assert text.endswith("\n")


# -- spans -------------------------------------------------------------------

def test_span_nesting_counters_and_jsonl_roundtrip(tmp_path):
    spans.recorder.clear()
    counters.reset()
    with spans.span("outer", who="test"):
        with spans.span("inner"):
            counters.inc("veles_dispatches_total", 2)
    recs = spans.recorder.records()
    inner = next(r for r in recs if r["name"] == "inner")
    outer = next(r for r in recs if r["name"] == "outer")
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert inner["parent"] == outer["sid"]
    assert outer["parent"] is None
    # counter deltas ride the span (both levels see the incs)
    assert inner["counters"]["veles_dispatches_total"] == 2
    assert outer["counters"]["veles_dispatches_total"] == 2
    assert outer["who"] == "test"
    assert outer["dur"] >= inner["dur"] >= 0
    # JSONL round trip
    path = str(tmp_path / "spans.jsonl")
    assert spans.recorder.to_jsonl(path) == len(recs)
    loaded = spans.read_jsonl(path)
    assert [r["name"] for r in loaded] == [r["name"] for r in recs]
    roots = spans.tree(loaded)
    assert [r["name"] for r in roots] == ["outer"]
    assert [c["name"] for c in roots[0]["children"]] == ["inner"]


def test_span_decorator_and_exception_close():
    spans.recorder.clear()

    @spans.spanned("decorated")
    def boom():
        raise RuntimeError("x")

    with pytest.raises(RuntimeError):
        boom()
    rec = spans.recorder.records("decorated")[0]
    assert rec["error"] is True
    # nesting stack recovered: a fresh span lands at depth 0
    with spans.span("after"):
        pass
    assert spans.recorder.records("after")[0]["depth"] == 0


def test_spans_config_switch_disables_all_recording():
    """root.common.trace.spans = False must silence EVERY span site
    (the recorder gates centrally), not just Unit.run."""
    from veles_tpu.config import root
    spans.recorder.clear()
    prev = root.common.trace.get("spans", True)
    root.common.trace.spans = False
    try:
        with spans.span("direct"):
            pass
        wf = _chain_workflow()
        wf.initialize()
        wf.run()
        assert spans.recorder.records() == []
    finally:
        root.common.trace.spans = prev
    with spans.span("after_reenable"):
        pass
    assert [r["name"] for r in spans.recorder.records()] == \
        ["after_reenable"]


def test_span_sink_streams_jsonl(tmp_path):
    path = str(tmp_path / "stream.jsonl")
    spans.recorder.set_sink(path)
    try:
        with spans.span("streamed"):
            pass
    finally:
        spans.recorder.set_sink(None)
    loaded = spans.read_jsonl(path)
    assert [r["name"] for r in loaded] == ["streamed"]


# -- cost model --------------------------------------------------------------

def test_cost_model_mfu_on_known_matmul():
    import jax.numpy as jnp
    m, k, n = 128, 256, 64
    c = cost_of_fn(lambda a, b: a @ b,
                   jnp.ones((m, k), jnp.float32),
                   jnp.ones((k, n), jnp.float32))
    assert c.source == "xla"
    assert c.flops == 2.0 * m * k * n          # the textbook number
    assert c.bytes_accessed >= 4 * (m * k + k * n + m * n)
    cm = CostModel(peak_flops=1e12)
    cm.record("mm", c, executions=10)
    # 10 executions of 4.19 MFLOP in 42µs on a 1 TFLOP/s chip = 100 %
    seconds = 10 * c.flops / 1e12
    assert cm.mfu("mm", seconds) == pytest.approx(1.0)
    assert cm.mfu("mm", seconds * 2) == pytest.approx(0.5)
    rep = cm.report({"mm": seconds})
    assert rep["mm"]["mfu"] == pytest.approx(1.0)
    assert rep["mm"]["executions"] == 10
    assert rep["mm"]["flops"] == pytest.approx(10 * c.flops)


def test_cost_arithmetic_and_peak_lookup():
    a = Cost(100.0, 50.0, 7.0)
    b = Cost(10.0, 2.0, 9.0)
    s = a + b
    assert (s.flops, s.bytes_accessed, s.peak_memory) == (110.0, 52.0, 9.0)
    assert a.scaled(3).flops == 300.0
    assert a.scaled(3).peak_memory == 7.0      # per-execution, not summed
    assert a.arithmetic_intensity == 2.0
    assert peak_bf16_flops("TPU v5 lite") == 197e12
    assert peak_bf16_flops("TPU v5p") == 459e12
    from veles_tpu.telemetry.cost import UnknownDevice
    with pytest.raises(UnknownDevice):     # never another chip's peak
        peak_bf16_flops("weird")


def test_pallas_analytic_fallbacks():
    from veles_tpu.ops.flash_attention import analytic_cost as flash_cost
    from veles_tpu.ops.fused_fc import analytic_cost as fc_cost
    full = flash_cost(2, 1024, 8, 64)
    causal = flash_cost(2, 1024, 8, 64, causal=True)
    train = flash_cost(2, 1024, 8, 64, causal=True, train=True)
    assert full.flops == 4.0 * 2 * 8 * 1024 * 1024 * 64
    assert causal.flops == pytest.approx(full.flops / 2)
    assert train.flops == pytest.approx(causal.flops * 3.5)
    assert full.source == "analytic"
    fc = fc_cost([(784, 100), (100, 10)], mb=100, steps=600)
    mm = 784 * 100 + 100 * 10
    assert fc.flops >= 600 * 3 * 2 * 100 * mm
    assert fc.bytes_accessed > 600 * 100 * 784 * 4   # the batch stream
    assert fc.peak_memory > 0


def test_kernel_cost_collector():
    """Pallas kernels note analytic costs at trace time; program_cost
    collects them during its re-lower (the custom call is opaque to
    XLA's cost model). flash_attention's entry calls note_kernel_cost
    — here the collector contract is exercised directly since the
    kernel itself cannot lower in this environment."""
    from veles_tpu.telemetry.cost import (collecting_kernel_costs,
                                          note_kernel_cost)
    note_kernel_cost(Cost(1.0))          # no active collector: no-op
    with collecting_kernel_costs() as notes:
        note_kernel_cost(Cost(10.0, 5.0))
        note_kernel_cost(Cost(2.0, 1.0))
    assert [c.flops for c in notes] == [10.0, 2.0]
    with collecting_kernel_costs() as notes2:
        pass
    assert notes2 == []


# -- workflow integration ----------------------------------------------------

class _Rec(vt.Unit):
    hide_from_registry = True

    def run(self):
        counters.inc("veles_dispatches_total")


def _chain_workflow(n=3):
    wf = vt.Workflow(name="telemetry_wf")
    prev = wf.start_point
    for i in range(n):
        u = _Rec(wf, name="u%d" % i)
        u.link_from(prev)
        prev = u
    wf.end_point.link_from(prev)
    return wf


def test_unit_runs_record_spans():
    spans.recorder.clear()
    wf = _chain_workflow()
    wf.initialize()
    wf.run()
    unit_spans = spans.recorder.records("unit.run")
    names = {r["unit"] for r in unit_spans}
    assert {"u0", "u1", "u2"} <= names
    # each unit.run span nests under the workflow.run span
    run_span = spans.recorder.records("workflow.run")[-1]
    u0 = next(r for r in unit_spans if r["unit"] == "u0")
    assert u0["parent"] == run_span["sid"]
    assert u0["counters"]["veles_dispatches_total"] == 1
    assert run_span["steps"] >= 3


def test_trace_export_cli_from_real_workflow_run(tmp_path):
    """Acceptance gate: `veles-tpu trace export` on a real workflow
    run's span JSONL produces schema-valid Chrome trace_event JSON."""
    spans.recorder.clear()
    wf = _chain_workflow()
    wf.initialize()
    wf.run()
    jsonl = str(tmp_path / "run.jsonl")
    assert spans.recorder.to_jsonl(jsonl) > 0
    out = str(tmp_path / "trace.json")
    from veles_tpu.__main__ import main
    assert main(["trace", "export", jsonl, out]) == 0
    with open(out) as f:
        doc = json.load(f)
    assert chrome_trace.validate(doc) == []
    events = doc["traceEvents"]
    x_names = [e["name"] for e in events if e["ph"] == "X"]
    assert "unit.run" in x_names and "workflow.run" in x_names
    # counter tracks emitted for the dispatch counter
    assert any(e["ph"] == "C" and
               e["name"] == "veles_dispatches_total" for e in events)
    # span args survive into the trace
    unit_ev = next(e for e in events
                   if e["ph"] == "X" and e["name"] == "unit.run")
    assert "unit" in unit_ev["args"]


def test_trace_export_cli_rejects_empty_input(tmp_path):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    from veles_tpu.__main__ import main
    assert main(["trace", "export", str(empty),
                 str(tmp_path / "o.json")]) == 1


def test_trace_export_tolerates_truncated_lines(tmp_path, caplog):
    """A --trace-file killed mid-append ends in half a JSON record;
    `trace export` must skip the bad line with a counted warning and
    export the readable spans instead of raising on json.loads."""
    import logging
    spans.recorder.clear()
    with spans.span("kept_a"):
        pass
    with spans.span("kept_b"):
        pass
    jsonl = tmp_path / "torn.jsonl"
    assert spans.recorder.to_jsonl(str(jsonl)) == 2
    with open(jsonl, "a") as f:
        f.write('{"name": "torn", "ts": 123.0, "du')   # mid-write cut
    with caplog.at_level(logging.WARNING,
                         logger="veles_tpu.telemetry"):
        recs = spans.read_jsonl(str(jsonl))
    assert [r["name"] for r in recs] == ["kept_a", "kept_b"]
    assert any("skipped 1 malformed" in rec.message
               for rec in caplog.records)
    out = tmp_path / "trace.json"
    from veles_tpu.__main__ import main
    assert main(["trace", "export", str(jsonl), str(out)]) == 0
    with open(out) as f:
        doc = json.load(f)
    assert chrome_trace.validate(doc) == []
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names == ["kept_a", "kept_b"]


def test_chrome_trace_validator_catches_violations():
    assert chrome_trace.validate([]) != []
    assert chrome_trace.validate({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "Z", "ts": 0, "dur": 0}]}
    assert any("phase" in e for e in chrome_trace.validate(bad))
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": -1, "dur": 0}]}
    assert any("ts" in e for e in chrome_trace.validate(bad))
    good = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0,
                             "dur": 1.0, "pid": 1, "tid": 2,
                             "args": {}}]}
    assert chrome_trace.validate(good) == []


# -- decode dispatch accounting (round-5 regression lock) --------------------

@pytest.fixture(scope="module")
def tiny_lm():
    lm = import_model("char_lm")
    from veles_tpu import prng
    prng.seed_all(1234)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=1,
                           dim=16, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return lm, wf


def test_cached_decode_is_one_dispatch_per_scan(tiny_lm):
    """The cached sampler (prefill + lax.scan) is ONE device program:
    decoding N tokens must cost exactly one decode dispatch, not one
    per token — the dispatch-count discipline behind the round-5
    speculative finding, now framework-observable."""
    lm, wf = tiny_lm
    rng = numpy.random.RandomState(7)
    prompt = list(lm.make_corpus(rng, lm.SEQ_LEN // 2))
    for n_new in (8, 24):
        before = counters.snapshot()
        lm.generate(wf, prompt, n_new, temperature=0)
        delta = counters.delta(before)
        assert delta.get("veles_decode_dispatches_total") == 1, delta
        assert delta.get("veles_decode_tokens_total") == n_new
    # dispatches per token stays well under the 1.0 gate ceiling
    before = counters.snapshot()
    lm.generate(wf, prompt, 24, temperature=0)
    delta = counters.delta(before)
    dpt = (delta["veles_decode_dispatches_total"]
           / delta["veles_decode_tokens_total"])
    assert dpt <= 1.0 / 24 + 1e-9


def test_train_step_cost_report(tiny_lm):
    """The TrainStep's own program cost (the CostModel's source): real
    FLOPs from Compiled.cost_analysis at the recorded arg
    shapes."""
    _, wf = tiny_lm
    rep = wf.train_step.cost_report()
    assert rep is not None
    cost = rep["cost"]
    assert cost.flops > 0
    assert cost.bytes_accessed > 0
    assert cost.source == "xla"
    # MFU math composes: tiny model for 1 s on a full chip is ~0
    assert 0 <= cost.mfu(1.0, peak_flops=197e12) < 1e-3


# -- off means off -----------------------------------------------------------

@pytest.fixture(scope="module")
def feature_off_run():
    """One plain run from zeroed registries: a tiny LM trains for an
    epoch, then a ContinuousEngine with every feature option at its
    default serves four requests. Returns what the counters and the
    histograms' sample counts read after the training alone and after
    both."""
    from veles_tpu import prng
    from veles_tpu.serving.engine import ContinuousEngine, make_request
    from veles_tpu.telemetry.counters import histograms
    lm = import_model("char_lm")
    prng.seed_all(4321)
    counters.reset()
    histograms.reset()
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=1,
                           dim=16, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()

    def reading():
        return dict(counters.snapshot(),
                    **{name: histograms.count(name)
                       for name in histograms.snapshot()})

    trained = reading()
    engine = ContinuousEngine(wf, max_slots=4, buckets=(16,),
                              max_context=48, name="off_t").start()
    try:
        rng = numpy.random.RandomState(5)
        outs = engine.serve([
            make_request(list(lm.make_corpus(rng, 8 + i)), 6,
                         temperature=0.0 if i % 2 else 0.8, seed=i)
            for i in range(4)])
    finally:
        engine.stop()
    assert [len(o) for o in outs] == [6] * 4
    return {"trained": trained, "served": reading()}


#: every family whose subsystem the run leaves off reads zero after
#: the whole run; the serving plane's own, after the training alone
OFF_FAMILIES = {
    "LOSSLESS_COUNTERS": (serving.LOSSLESS_COUNTERS, "served"),
    "PREFIX_COUNTERS": (serving.PREFIX_COUNTERS, "served"),
    "TP_COUNTERS": (serving.TP_COUNTERS, "served"),
    "QOS_COUNTERS": (serving.QOS_COUNTERS, "served"),
    "O1_COUNTERS": (serving.O1_COUNTERS, "served"),
    "STREAM_COUNTERS": (serving.STREAM_COUNTERS, "served"),
    "ROUTER_COUNTERS": (serving.ROUTER_COUNTERS, "served"),
    "QUANT_COUNTERS": (quant.QUANT_COUNTERS, "served"),
    "OVERLAP_COUNTERS": (overlap.OVERLAP_COUNTERS, "served"),
    "LINALG_COUNTERS": (linalg.LINALG_COUNTERS, "served"),
    "LOADGEN_COUNTERS": (loadgen.LOADGEN_COUNTERS, "served"),
    "RESILIENCE_COUNTERS": (resilience.RESILIENCE_COUNTERS, "served"),
    "ELASTIC_COUNTERS": (resilience.ELASTIC_COUNTERS, "served"),
    "TENSORMON_COUNTERS": (telemetry.TENSORMON_COUNTERS, "served"),
    "WATCH_COUNTERS": (telemetry.WATCH_COUNTERS, "served"),
    "TRACE_COUNTERS": (telemetry.TRACE_COUNTERS, "served"),
    "SERVING_COUNTERS": (serving.SERVING_COUNTERS, "trained"),
    "SERVING_HISTOGRAMS": (serving.SERVING_HISTOGRAMS, "trained"),
}


@pytest.mark.parametrize("family", OFF_FAMILIES)
def test_feature_off_counters_stay_zero(feature_off_run, family):
    """A subsystem that the run leaves off counts nothing: the one
    promise every ``*_COUNTERS`` family makes, held on a live run."""
    names, when = OFF_FAMILIES[family]
    reading = feature_off_run[when]
    assert names
    assert {n: reading[n] for n in names if reading.get(n)} == {}
    # the run did run: the planes that are on counted
    served = feature_off_run["served"]
    assert feature_off_run["trained"]["veles_dispatches_total"] > 0
    assert served["veles_serving_tokens_total"] == 24
    assert served["veles_serving_e2e_seconds"] == 4


# -- /metrics endpoints ------------------------------------------------------

def test_web_status_metrics_endpoint():
    from veles_tpu.web_status import WebStatusServer
    counters.inc("veles_dispatches_total")
    server = WebStatusServer(port=0).start()
    try:
        url = "http://127.0.0.1:%d/metrics" % server.port
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "veles_dispatches_total" in body
        assert "veles_status_workflows 0" in body
    finally:
        server.stop()
