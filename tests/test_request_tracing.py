"""Request-plane SLO observability (ISSUE 11): per-request tracing,
latency histograms, fleet /metrics aggregation.

The contract under test: every serving request gets a process-unique
``request_id`` threaded through its Ticket lifecycle; TTFT / TPOT /
queue-wait / end-to-end land in fixed-bucket Prometheus histograms
(p50/p90/p99 derived from buckets, rendered as gauges by the shared
``metrics_text`` path); terminal accounting is EXACTLY once even when
two sweeps see the same ticket; tracing stays off the hot path
(decode-step dispatch counts bit-identical tracing on vs off); and
``veles-tpu metrics aggregate`` merges N live /metrics endpoints
(counters summed, histogram buckets summed, quantiles recomputed,
per-endpoint up/down stamped)."""
import http.server
import io
import json
import os
import threading
import time
from contextlib import redirect_stdout

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import prng
from veles_tpu.serving import (SERVING_HISTOGRAMS, ContinuousEngine,
                               Ticket)
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import (SlotScheduler, shed_expired,
                                         split_expired)
from veles_tpu.telemetry import fleet
from veles_tpu.telemetry.counters import (HISTOGRAMS,
                                          HistogramRegistry, counters,
                                          histogram_quantile,
                                          histograms, metrics_text,
                                          observe)
from veles_tpu.telemetry.recorder import flight
from veles_tpu.telemetry.spans import recorder as span_recorder

from conftest import import_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- histogram registry (no jax) ----------------------------------------------

def test_histogram_observe_and_quantiles():
    reg = HistogramRegistry()
    name = "veles_serving_ttft_seconds"
    for v in (0.003, 0.02, 0.02, 0.07, 0.2, 4.0):
        reg.observe(name, v)
    assert reg.count(name) == 6
    assert abs(reg.sum(name) - 4.313) < 1e-9
    snap = reg.snapshot()[name]
    assert snap["bounds"] == HISTOGRAMS[name]["buckets"]
    assert sum(snap["counts"]) == 6
    p50 = reg.quantile(name, 0.5)
    # rank 3 lands in the (0.01, 0.025] bucket (two samples there)
    assert 0.01 < p50 <= 0.025
    p99 = reg.quantile(name, 0.99)
    assert p99 >= p50
    # empty histogram answers None, not 0 (0 is a real latency)
    assert reg.quantile("veles_serving_tpot_seconds", 0.5) is None


def test_histogram_overflow_bucket_reports_last_bound():
    bounds = (0.1, 1.0)
    # every sample beyond the last bound: quantile cannot see past it
    assert histogram_quantile(bounds, (0, 0, 5), 0.5) == 1.0
    assert histogram_quantile(bounds, (0, 0, 0), 0.5) is None
    # exact boundary value belongs to its bucket (Prometheus `le`)
    reg = HistogramRegistry()
    reg.observe("veles_serving_tpot_seconds", 0.0005)
    assert reg.snapshot()["veles_serving_tpot_seconds"]["counts"][0] == 1


def test_histogram_prometheus_exposition_format():
    reg = HistogramRegistry()
    reg.observe("veles_serving_e2e_seconds", 0.3)
    reg.observe("veles_serving_e2e_seconds", 7.0)
    text = reg.prometheus_text()
    assert "# TYPE veles_serving_e2e_seconds histogram" in text
    assert 'veles_serving_e2e_seconds_bucket{le="0.5"} 1' in text
    assert 'veles_serving_e2e_seconds_bucket{le="+Inf"} 2' in text
    assert "veles_serving_e2e_seconds_count 2" in text
    assert "veles_serving_e2e_seconds_sum 7.3" in text
    # cumulative monotonicity across every rendered bucket
    cums = [int(line.rsplit(None, 1)[1]) for line in text.splitlines()
            if "_bucket{" in line]
    assert cums == sorted(cums)


def test_metrics_text_renders_quantile_gauges_and_histograms():
    histograms.reset()
    try:
        for v in (0.01, 0.02, 0.03, 0.4):
            observe("veles_serving_ttft_seconds", v)
        text = metrics_text()
        assert "# TYPE veles_serving_ttft_seconds histogram" in text
        assert "# TYPE veles_serving_ttft_seconds_p50 gauge" in text
        assert "veles_serving_ttft_seconds_p99" in text
        # no samples -> no rows at all (non-serving pages unchanged)
        assert "veles_serving_tpot_seconds" not in text
    finally:
        histograms.reset()


def test_metrics_text_collision_guard_drops_shadowing_gauge():
    histograms.reset()
    counters.inc("veles_dispatches_total", 0)
    observe("veles_serving_ttft_seconds", 0.02)
    before = counters.get("veles_metrics_name_collisions_total")
    try:
        text = metrics_text({
            "veles_dispatches_total": 123.0,          # shadows counter
            "veles_serving_ttft_seconds_p50": 9.9,    # shadows quantile
            "veles_fine_gauge": (7, "a fine gauge")})
        grown = counters.get("veles_metrics_name_collisions_total") \
            - before
        assert grown == 2
        assert "veles_fine_gauge 7" in text
        assert "# TYPE veles_dispatches_total gauge" not in text
        # the page never renders a duplicate metric name with two TYPEs
        names = {}
        for line in text.splitlines():
            if line.startswith("# TYPE "):
                _h, _t, name, kind = line.split()
                assert names.setdefault(name, kind) == kind, name
    finally:
        histograms.reset()


# -- exactly-once terminal accounting (no jax) --------------------------------

def test_ticket_terminal_is_exactly_once():
    histograms.reset()
    t = Ticket()
    assert t.fail("boom", code=503) is True
    assert t.fail("boom again", code=500) is False
    assert t.code == 503            # the first answer stands
    assert t.succeed({"tokens": [1]}) is False
    assert t.result is None
    histograms.reset()


def test_deadline_shed_accounted_exactly_once():
    """A ticket expired by shed_expired must record its queue-wait
    histogram sample, its expiry counters and its terminal flight
    event exactly once — also when the tick sweep AND the failure-path
    sweep both hand it to shed_expired."""
    histograms.reset()
    sched = SlotScheduler(1, (8,), 16)
    busy, old = Ticket(), Ticket(deadline=time.time() - 1)
    sched.push(make_request([1, 2], 4), busy)
    sched.take_admissions()
    sched.push(make_request([1, 2], 4), old)
    exp_before = counters.get("veles_serving_expired_total")
    shed_before = counters.get("veles_shed_requests_total")
    # the tick sweep sees it...
    _adm, expired = sched.take_admissions()
    assert expired == [old]
    shed_expired(expired)
    # ...and the failure-path sweep hands the SAME ticket over again
    shed_expired(expired)
    shed_expired(sched.expire_queued())
    assert counters.get("veles_serving_expired_total") \
        - exp_before == 1
    assert counters.get("veles_shed_requests_total") \
        - shed_before == 1
    # one sample at ``busy``'s admission (observed where the wait ends,
    # though that ticket has not ended), one at ``old``'s expiry
    assert histograms.count("veles_serving_queue_wait_seconds") == 2
    assert old.outcome == "expired"
    done = [r for r in flight.records(kind="request")
            if r.get("request_id") == old.request_id
            and r.get("phase") == "done"]
    assert len(done) == 1 and done[0]["outcome"] == "expired"
    histograms.reset()


def test_split_expired_unchanged_by_tracing_fields():
    live = Ticket(deadline=time.time() + 60)
    dead = Ticket(deadline=time.time() - 60)
    keep, gone = split_expired([({}, live), ({}, dead)])
    assert [t for _r, t in keep] == [live] and gone == [dead]


# -- fleet aggregation (no jax) -----------------------------------------------

_PAGE_A = """\
# HELP veles_serving_admitted_total x
# TYPE veles_serving_admitted_total counter
veles_serving_admitted_total 10
# HELP veles_serving_ttft_seconds ttft
# TYPE veles_serving_ttft_seconds histogram
veles_serving_ttft_seconds_bucket{le="0.1"} 8
veles_serving_ttft_seconds_bucket{le="1"} 10
veles_serving_ttft_seconds_bucket{le="+Inf"} 10
veles_serving_ttft_seconds_sum 0.9
veles_serving_ttft_seconds_count 10
# TYPE veles_serving_ttft_seconds_p50 gauge
veles_serving_ttft_seconds_p50 0.0625
# TYPE veles_serving_slots_busy gauge
veles_serving_slots_busy 3
"""

_PAGE_B = """\
# TYPE veles_serving_admitted_total counter
veles_serving_admitted_total 4
# TYPE veles_serving_ttft_seconds histogram
veles_serving_ttft_seconds_bucket{le="0.1"} 0
veles_serving_ttft_seconds_bucket{le="1"} 2
veles_serving_ttft_seconds_bucket{le="+Inf"} 4
veles_serving_ttft_seconds_sum 9.5
veles_serving_ttft_seconds_count 4
# TYPE veles_serving_slots_busy gauge
veles_serving_slots_busy 5
"""


def test_fleet_parse_and_merge_math():
    pa = fleet.parse_metrics_text(_PAGE_A)
    pb = fleet.parse_metrics_text(_PAGE_B)
    assert pa["counters"]["veles_serving_admitted_total"] == 10
    assert pa["histograms"]["veles_serving_ttft_seconds"]["count"] == 10
    # the endpoint-local quantile gauge parses as a gauge...
    assert "veles_serving_ttft_seconds_p50" in pa["gauges"]
    merged = fleet.merge([pa, pb])
    assert merged["counters"]["veles_serving_admitted_total"] == 14
    h = merged["histograms"]["veles_serving_ttft_seconds"]
    assert h["buckets"]["0.1"] == 8 and h["buckets"]["1"] == 12
    assert h["buckets"]["+Inf"] == 14 and h["count"] == 14
    assert abs(h["sum"] - 10.4) < 1e-9
    assert merged["gauges"]["veles_serving_slots_busy"] == 8
    # ...but is DROPPED from the merge: fleet quantiles are
    # recomputed from the merged buckets, never averaged
    assert "veles_serving_ttft_seconds_p50" not in merged["gauges"]
    qs = fleet.quantiles(h)
    # rank(0.5) = 7 of 14 sits inside the (0, 0.1] bucket (8 samples)
    assert 0.0 < qs[0.5] <= 0.1
    assert qs[0.99] > qs[0.5]


def test_fleet_merge_step_function_handles_unequal_grids():
    pa = fleet.parse_metrics_text(
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 2\n'
        "h_sum 1.0\nh_count 2\n")
    pb = fleet.parse_metrics_text(
        "# TYPE h histogram\n"
        'h_bucket{le="0.5"} 1\nh_bucket{le="1"} 1\n'
        'h_bucket{le="+Inf"} 3\nh_sum 4.0\nh_count 3\n')
    h = fleet.merge([pa, pb])["histograms"]["h"]
    # at le=0.5 endpoint A contributes its cumulative at <=0.5 (0)
    assert h["buckets"]["0.5"] == 1
    assert h["buckets"]["1"] == 3
    assert h["buckets"]["+Inf"] == 5


class _Static(http.server.BaseHTTPRequestHandler):
    page = ""

    def log_message(self, *a):
        pass

    def do_GET(self):
        if self.path != "/metrics":
            self.send_error(404)
            return
        body = self.page.encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _serve_page(page):
    handler = type("H", (_Static,), {"page": page})
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def test_metrics_aggregate_cli_merges_two_live_endpoints():
    """The acceptance e2e: `veles-tpu metrics aggregate` over two live
    HTTP endpoints — counters summed, buckets summed, quantiles
    recomputed, per-endpoint up/down stamped (a dead third endpoint
    reports up=0 without failing the aggregation)."""
    a, b = _serve_page(_PAGE_A), _serve_page(_PAGE_B)
    dead = "http://127.0.0.1:1/metrics"
    try:
        urls = ["http://127.0.0.1:%d/metrics" % a.server_address[1],
                "http://127.0.0.1:%d" % b.server_address[1], dead]
        from veles_tpu.__main__ import main
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["metrics", "aggregate"] + urls)
        assert rc == 0
        text = out.getvalue()
        assert "veles_serving_admitted_total 14" in text
        assert 'veles_serving_ttft_seconds_bucket{le="1"} 12' in text
        assert "veles_serving_ttft_seconds_count 14" in text
        assert "veles_serving_slots_busy 8" in text
        assert 'veles_fleet_endpoint_up{endpoint="%s"} 0' % dead \
            in text
        assert text.count("veles_fleet_endpoint_up{") == 3
        assert "veles_serving_ttft_seconds_p50" in text
        assert "veles_serving_ttft_seconds_p99" in text
        # --json form carries the structured aggregation
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["metrics", "aggregate", "--json"] + urls)
        assert rc == 0
        agg = json.loads(out.getvalue())
        assert [ep["up"] for ep in agg["endpoints"]] \
            == [True, True, False]
        # the whole fleet down = exit 2 (an alert, not a report)
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["metrics", "aggregate", dead])
        assert rc == 2
    finally:
        a.shutdown()
        b.shutdown()


# -- static registration pass (scripts/check_counters.py) ---------------------

def _load_checker():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "veles_check_counters_hist",
        os.path.join(REPO, "scripts", "check_counters.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_counters_verifies_histograms(tmp_path):
    mod = _load_checker()
    # the tree itself is clean — every observe()d veles_* histogram
    # carries help + bucket bounds
    assert mod.find_unregistered_histograms() == []
    regs = mod.registered_histograms()
    for name in SERVING_HISTOGRAMS:
        assert regs.get(name) is True, name
    # the detector detects: an unregistered observe is flagged
    (tmp_path / "veles_tpu").mkdir()
    (tmp_path / "veles_tpu" / "x.py").write_text(
        'observe("veles_bogus_seconds", 1.0)\n'
        'histograms.quantile("veles_bogus2_seconds", 0.5)\n')
    uses = mod.used_histograms(str(tmp_path))
    assert set(uses) == {"veles_bogus_seconds",
                         "veles_bogus2_seconds"}


# -- engine e2e: ids, histograms, spans, dispatch lock ------------------------

@pytest.fixture(scope="module")
def served():
    lm = import_model("char_lm")
    prng.seed_all(1311)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=1,
                           dim=32, n_train=128, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    engine = ContinuousEngine(wf, max_slots=3, buckets=(8, 16),
                              max_context=48, decode_block=4,
                              name="eng_trace").start()
    yield lm, wf, engine
    engine.stop()


def _reqs(lm, n=4):
    rng = numpy.random.RandomState(7)
    return [make_request(
        [int(t) for t in rng.randint(0, lm.VOCAB, 5 + i)], 4 + i,
        temperature=0.0 if i % 2 else 0.8, seed=50 + i)
        for i in range(n)]


def test_request_ids_histograms_spans_and_flight(served):
    lm, _wf, engine = served
    histograms.reset()
    span_recorder.clear()
    reqs = _reqs(lm)
    tickets = [Ticket() for _ in reqs]
    for req, ticket in zip(reqs, tickets):
        assert engine.submit(req, ticket)
    for ticket in tickets:
        assert ticket.event.wait(120), ticket.error
    rids = [t.result["request_id"] for t in tickets]
    assert len(set(rids)) == len(rids)
    assert all(t.request_id == rid
               for t, rid in zip(tickets, rids))
    # per-request SLO samples: one TTFT + one queue-wait + one e2e
    # per request; TPOT for every multi-token request
    assert histograms.count("veles_serving_ttft_seconds") == len(reqs)
    assert histograms.count("veles_serving_queue_wait_seconds") \
        == len(reqs)
    assert histograms.count("veles_serving_e2e_seconds") == len(reqs)
    assert histograms.count("veles_serving_tpot_seconds") == len(reqs)
    assert histograms.quantile("veles_serving_ttft_seconds", 0.5) > 0
    # lifecycle spans tagged with the id, exportable per request
    recs = span_recorder.records()
    for rid in rids:
        mine = [r for r in recs if r.get("request_id") == rid]
        names = {r["name"] for r in mine}
        assert {"request", "request.queue", "request.prefill",
                "request.decode"} <= names, names
        total = [r for r in mine if r["name"] == "request"][0]
        assert total["outcome"] == "retired"
        assert total["tokens"] == len(
            [t for t in tickets
             if t.request_id == rid][0].result["tokens"])
    # terminal flight events: one done event per request
    done = [r for r in flight.records(kind="request")
            if r.get("phase") == "done"
            and r.get("request_id") in rids]
    assert len(done) == len(rids)
    # the engine prefill span carries the id too
    prefills = [r for r in recs if r["name"] == "serving.prefill"]
    assert prefills and all("request_id" in r for r in prefills)
    histograms.reset()


def test_trace_export_filters_one_request(served, tmp_path):
    lm, _wf, engine = served
    span_recorder.clear()
    reqs = _reqs(lm, n=2)
    out = engine.serve(reqs)
    assert len(out) == 2
    recs = span_recorder.records("request")
    rid = recs[-1]["request_id"]
    jsonl = str(tmp_path / "run.jsonl")
    span_recorder.to_jsonl(jsonl)
    trace = str(tmp_path / "trace.json")
    from veles_tpu.__main__ import main
    assert main(["trace", "export", jsonl, trace,
                 "--request", rid]) == 0
    doc = json.load(open(trace))
    named = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert named, "no spans exported"
    assert all(ev["args"].get("request_id") == rid for ev in named)
    # an unknown id refuses instead of writing a blank page
    assert main(["trace", "export", jsonl, trace,
                 "--request", "req-0-0"]) == 1


def test_tracing_off_hot_path_dispatches_bit_identical(served):
    """The CI lock the satellite asks for: decode-step dispatch count
    (and tokens) with request tracing enabled is bit-identical to
    tracing off — tracing is host-side timestamps at step boundaries
    only, never device work."""
    lm, _wf, engine = served
    from veles_tpu.config import root
    reqs = _reqs(lm)
    engine.serve(list(reqs))          # warm every program

    def measure():
        before = {k: counters.get(k) for k in (
            "veles_serving_decode_dispatches_total",
            "veles_serving_prefill_dispatches_total",
            "veles_decode_dispatches_total",
            "veles_compiles_total")}
        # solo, sequential: admission timing cannot reshuffle chunk
        # boundaries between the two measured passes
        out = [engine.serve([r])[0] for r in reqs]
        return out, {k: counters.get(k) - v
                     for k, v in before.items()}

    prev = root.common.trace.get("requests", True)
    try:
        root.common.trace.requests = True
        out_on, d_on = measure()
        root.common.trace.requests = False
        out_off, d_off = measure()
    finally:
        root.common.trace.requests = prev
    assert out_on == out_off
    assert d_on == d_off, (d_on, d_off)
    assert d_on["veles_compiles_total"] == 0


def test_tracing_disabled_emits_no_request_spans(served):
    lm, _wf, engine = served
    from veles_tpu.config import root
    histograms.reset()
    prev = root.common.trace.get("requests", True)
    span_recorder.clear()
    try:
        root.common.trace.requests = False
        engine.serve(_reqs(lm, n=2))
        assert not span_recorder.records("request")
        # the SLO histograms record regardless — p99 TTFT must be
        # answerable on a fleet running with tracing off
        assert histograms.count("veles_serving_ttft_seconds") == 2
    finally:
        root.common.trace.requests = prev
        histograms.reset()


def test_live_metrics_page_exposes_request_slos(served):
    """Both HTTP surfaces render through metrics_text — one rendered
    page after real serving carries the histogram series and the
    quantile gauges."""
    lm, _wf, engine = served
    histograms.reset()
    try:
        engine.serve(_reqs(lm, n=2))
        text = metrics_text()
        assert "# TYPE veles_serving_ttft_seconds histogram" in text
        assert 'veles_serving_ttft_seconds_bucket{le="+Inf"} 2' \
            in text
        assert "veles_serving_ttft_seconds_p99" in text
        assert "veles_serving_queue_wait_seconds_p50" in text
        # and the fleet parser round-trips the live page
        parsed = fleet.parse_metrics_text(text)
        assert parsed["histograms"][
            "veles_serving_ttft_seconds"]["count"] == 2
    finally:
        histograms.reset()
