"""Fleet watchtower (veles_tpu/telemetry/timeseries.py + alerts.py):
the in-process metrics time-series, the SLO burn-rate alert engine,
and the watch/alerts surfaces.

The load-bearing locks:
- the watchtower OFF (the default) is BIT-IDENTICAL to a build
  without the feature: no sampler thread, no store, no engine, empty
  ``veles_alert_firing`` exposition, a single ``enabled: false``
  header line from the history pull, and NOT ONE ``veles_watch_*`` /
  ``veles_alert_*`` counter moves (the tensormon scan-lock
  discipline);
- the SeriesStore ring is seq-cursored exactly like the span ring:
  bounded capacity ``retention/period + 1``, eviction keeps the
  newest, a cursor older than the tail silently skips evicted
  records, and a torn JSONL pull salvages per line;
- windowed derivations are restart-safe (negative counter deltas
  clamp to growth-from-zero) and DIVERGE from the
  cumulative-since-start ``_p99`` gauges by design — an hour of good
  traffic must not bury a brownout;
- burn-rate and threshold rules ride a fire_for/resolve_for
  hysteresis machine whose streaks HOLD on no-data evaluations; a
  critical rule's firing edge marks the process unready (the router
  probe loop routes around it) and its resolve edge readmits;
- rule construction is FAIL-CLOSED: unknown series / type / op /
  source / severity / field raise at parse, never at 3am;
- ``veles-tpu watch`` / ``veles-tpu alerts`` drive a real 2-replica
  fleet through the same ``/metrics/history`` + ``/alerts`` pages a
  remote operator would scrape.

Budget discipline: everything above the live-fleet test is jax-free
(fake clocks, hand-fed stores); the live test uses one tiny char_lm
workflow shared by both replicas.
"""
import io
import json
import time
import urllib.error
import urllib.request
from contextlib import redirect_stdout

import numpy
import pytest

import veles_tpu as vt
from veles_tpu.config import root
from veles_tpu.loadgen.harness import aggregate, verdict
from veles_tpu.resilience import health
from veles_tpu.telemetry import WATCH_COUNTERS, alerts, timeseries
from veles_tpu.telemetry import fleet
from veles_tpu.telemetry.counters import counters, histogram_quantile
from veles_tpu.telemetry.recorder import flight
from veles_tpu.telemetry.timeseries import (SeriesStore, parse_history,
                                            pull_payload)

from conftest import import_model

TTFT = "veles_serving_ttft_seconds"


@pytest.fixture(autouse=True)
def _reset_watchtower():
    """Every test starts with the watchtower down and the shipped
    knob defaults, and leaves no sampler thread / health residue for
    the rest of the suite."""
    timeseries.stop_watch()
    flight.clear()
    yield
    timeseries.stop_watch()
    node = root.common.telemetry.watch
    node.enabled = False
    node.period = 1.0
    node.retention = 300.0
    node.rules = None
    node.slo_ttft_ms = 500.0
    node.slo_e2e_ms = 5000.0
    node.objective = 0.99
    node.fast_window = 30.0
    node.slow_window = 120.0
    node.burn_factor = 6.0
    node.queue_depth_limit = 64
    node.shed_rate_limit = 5.0
    for rule in ("brownout_shedding",):
        health.forget("alert.watch.%s" % rule)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


def _hist(bounds, counts):
    """Registry-snapshot histogram form: counts = per-bucket +
    overflow (len(bounds) + 1)."""
    assert len(counts) == len(bounds) + 1
    return {"bounds": list(bounds), "counts": list(counts),
            "sum": float(sum(counts)), "count": float(sum(counts))}


def _feed(store_, clock, counter_values=None, hists=None, gauges=None,
          dt=1.0):
    clock.tick(dt)
    return store_.ingest(dict(counter_values or {}),
                         dict(hists or {}), dict(gauges or {}))


# -- ring math (no jax, fake clock) -------------------------------------------

def test_ring_capacity_eviction_and_cursor_pull():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=5.0, clock=fk,
                     count_samples=False)
    # capacity = retention/period + 1 so a full window has both ends
    for i in range(10):
        _feed(st, fk, {"c": float(i)})
    recs = st.samples()
    assert len(recs) == 6
    assert [r["seq"] for r in recs] == [5, 6, 7, 8, 9, 10]
    # a cursor older than the ring's tail silently skips the evicted
    pulled, cur = st.records_since(0)
    assert [r["seq"] for r in pulled] == [5, 6, 7, 8, 9, 10]
    assert cur == st.cursor() == 10
    # incremental pull: only what was appended after the cursor
    pulled, cur2 = st.records_since(cur)
    assert pulled == [] and cur2 == 10
    _feed(st, fk, {"c": 10.0})
    pulled, cur3 = st.records_since(cur)
    assert len(pulled) == 1 and pulled[0]["seq"] == 11 and cur3 == 11
    # non-sample events ride the same ring, in order
    st.note_event("watch.alert", rule="r", state="firing")
    pulled, _ = st.records_since(cur3)
    assert pulled[0]["kind"] == "watch.alert"
    assert pulled[0]["seq"] == 12


def test_delta_rate_window_selection_and_restart_clamp():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)
    assert st.delta("c") is None and st.rate("c") is None
    for i in range(6):                       # ts 1001..1006, c = 10*i
        _feed(st, fk, {"c": 10.0 * i})
    # window=None → the latest adjacent pair
    assert st.delta("c") == 10.0
    assert st.rate("c") == pytest.approx(10.0)
    # window picks the newest sample at least `window` older
    assert st.delta("c", window=2.5) == 30.0
    assert st.rate("c", window=2.5) == pytest.approx(10.0)
    # a window outrunning retention spans the whole ring
    assert st.delta("c", window=1e9) == 50.0
    # a restarted remote process: negative delta clamps to the newest
    # absolute value — growth from zero, not negative traffic
    _feed(st, fk, {"c": 3.0})
    assert st.delta("c") == 3.0
    assert st.rate("c") == pytest.approx(3.0)


def test_windowed_quantile_diverges_from_cumulative():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)
    bounds = [0.1, 1.0]
    # an hour of fast traffic ...
    _feed(st, fk, hists={TTFT: _hist(bounds, [1000, 0, 0])})
    # ... then a brownout: 50 slow requests land in (0.1, 1.0]
    _feed(st, fk, hists={TTFT: _hist(bounds, [1000, 50, 0])})
    windowed = st.quantile(TTFT, 0.5)
    cumulative = histogram_quantile(tuple(bounds), (1000, 50, 0), 0.5)
    assert windowed is not None and windowed > 0.1
    assert cumulative is not None and cumulative <= 0.1
    # error_fraction errs toward alerting: an SLO target between
    # bounds counts the whole straddling bucket as bad
    assert st.error_fraction(TTFT, 0.5) == pytest.approx(1.0)
    assert st.error_fraction(TTFT, 1.0) == pytest.approx(0.0)
    # no growth in the window → no verdict (None, not 0.0)
    _feed(st, fk, hists={TTFT: _hist(bounds, [1000, 50, 0])})
    assert st.quantile(TTFT, 0.5) is None
    assert st.error_fraction(TTFT, 0.5) is None


def test_hist_delta_bounds_mismatch_falls_back_to_absolute():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)
    _feed(st, fk, hists={TTFT: _hist([0.1, 1.0], [10, 0, 0])})
    # remote restart re-registered with different buckets
    _feed(st, fk, hists={TTFT: _hist([0.5], [7, 2])})
    h = st.hist_delta(TTFT)
    assert h["bounds"] == [0.5] and h["counts"] == [7, 2]
    # a histogram absent from the older sample deltas as absolute
    _feed(st, fk, hists={TTFT: _hist([0.5], [8, 2]),
                         "veles_serving_e2e_seconds":
                         _hist([1.0], [4, 1])})
    h = st.hist_delta("veles_serving_e2e_seconds")
    assert h["counts"] == [4, 1] and h["count"] == 5


def test_gauge_providers_feed_sample_and_broken_provider_skipped():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)
    timeseries.add_gauge_provider(
        "wt_test", lambda: {"wt_g": (3.0, "help text"),
                            "wt_bad": "not-a-number"})
    timeseries.add_gauge_provider(
        "wt_boom", lambda: (_ for _ in ()).throw(RuntimeError("x")))
    try:
        rec = st.sample()
    finally:
        timeseries.remove_gauge_provider("wt_test")
        timeseries.remove_gauge_provider("wt_boom")
    assert rec["gauges"]["wt_g"] == 3.0
    assert "wt_bad" not in rec["gauges"]
    assert st.gauge("wt_g") == 3.0


def test_parse_history_salvages_torn_lines():
    header = {"kind": "watch.header", "enabled": True, "cursor": 2}
    rec = {"kind": "watch.sample", "seq": 2, "ts": 1.0,
           "counters": {}, "hist": {}, "gauges": {}}
    text = (json.dumps(header) + "\n" + json.dumps(rec)
            + "\n" + '{"kind": "watch.sam')      # torn mid-record
    got_header, got_records = parse_history(text)
    assert got_header["cursor"] == 2
    assert [r["seq"] for r in got_records] == [2]


# -- the alert rule engine (no jax, fake clock) -------------------------------

def test_burn_rate_hysteresis_fires_holds_and_resolves():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)
    rule = alerts.BurnRateRule(
        "ttft_burn", TTFT, slo_seconds=0.1, objective=0.9,
        fast_window=1.0, slow_window=1.0, factor=2.0,
        fire_for=2, resolve_for=2)
    eng = alerts.AlertEngine(st, [rule], clock=fk,
                             health_name="wt_unit",
                             dump_on_critical=False)
    ev0 = counters.get("veles_alert_evals_total")
    tr0 = counters.get("veles_alert_transitions_total")
    bounds = [0.1]
    # no samples yet → observe None, streaks hold, state ok
    assert eng.evaluate() == [] and rule.state == "ok"
    _feed(st, fk, hists={TTFT: _hist(bounds, [100, 0])})
    # breach #1 (all 50 new requests blow the SLO): streak 1, no fire
    _feed(st, fk, hists={TTFT: _hist(bounds, [100, 50])})
    assert eng.evaluate() == [] and rule.state == "ok"
    # a no-growth window → observe None → the streak HOLDS at 1
    _feed(st, fk, hists={TTFT: _hist(bounds, [100, 50])})
    assert eng.evaluate() == [] and rule.state == "ok"
    # breach #2 → firing (fire_for=2 satisfied across the hold)
    _feed(st, fk, hists={TTFT: _hist(bounds, [100, 90])})
    trans = eng.evaluate()
    assert [(t["rule"], t["state"]) for t in trans] \
        == [("ttft_burn", "firing")]
    assert rule.state == "firing" and rule.since == fk.t
    assert rule.status()["type"] == "burn_rate"
    # the firing edge rides the ring next to its samples
    edges = st.records("watch.alert")
    assert [(e["rule"], e["state"]) for e in edges] \
        == [("ttft_burn", "firing")]
    # exposition flips the labeled gauge
    assert 'veles_alert_firing{rule="ttft_burn"} 1' \
        in eng.render_firing()
    # heal: two clean windows → resolved (resolve_for=2)
    _feed(st, fk, hists={TTFT: _hist(bounds, [200, 90])})
    assert eng.evaluate() == [] and rule.state == "firing"
    _feed(st, fk, hists={TTFT: _hist(bounds, [300, 90])})
    trans = eng.evaluate()
    assert [(t["rule"], t["state"]) for t in trans] \
        == [("ttft_burn", "resolved")]
    assert 'veles_alert_firing{rule="ttft_burn"} 0' \
        in eng.render_firing()
    assert counters.get("veles_alert_evals_total") - ev0 == 6
    assert counters.get("veles_alert_transitions_total") - tr0 == 2
    if flight.enabled():
        noted = [(e["rule"], e["state"])
                 for e in flight.records("alert")]
        assert ("ttft_burn", "firing") in noted
        assert ("ttft_burn", "resolved") in noted


def test_critical_rule_marks_process_unready_and_readmits():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)
    rule = alerts.ThresholdRule(
        "brown", "veles_qos_brownout_level", threshold=2.0, op=">=",
        source="gauge", severity="critical", fire_for=1,
        resolve_for=1)
    eng = alerts.AlertEngine(st, [rule], clock=fk,
                             health_name="wt_crit",
                             dump_on_critical=False)
    cu0 = counters.get("veles_alert_critical_unready_total")
    try:
        _feed(st, fk, gauges={"veles_qos_brownout_level": 3.0})
        trans = eng.evaluate()
        assert [(t["rule"], t["state"]) for t in trans] \
            == [("brown", "firing")]
        assert health.readiness().get("alert.wt_crit.brown") is False
        assert counters.get("veles_alert_critical_unready_total") \
            - cu0 == 1
        _feed(st, fk, gauges={"veles_qos_brownout_level": 0.0})
        trans = eng.evaluate()
        assert [(t["rule"], t["state"]) for t in trans] \
            == [("brown", "resolved")]
        assert health.readiness().get("alert.wt_crit.brown") is True
    finally:
        health.forget("alert.wt_crit.brown")


def test_threshold_rule_rate_source_and_one_bad_rule_isolated():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)

    class Boom(alerts.Rule):
        def observe(self, store):
            raise RuntimeError("bad rule")

        def describe(self):
            return {}

    shed = alerts.ThresholdRule(
        "shed_fast", "veles_shed_requests_total", threshold=5.0,
        op=">", source="rate", window=10.0, fire_for=1,
        resolve_for=1)
    eng = alerts.AlertEngine(st, [Boom("boom"), shed], clock=fk,
                             dump_on_critical=False)
    _feed(st, fk, {"veles_shed_requests_total": 0.0})
    _feed(st, fk, {"veles_shed_requests_total": 20.0})
    # the raising rule must not take the sweep down
    trans = eng.evaluate()
    assert [(t["rule"], t["state"]) for t in trans] \
        == [("shed_fast", "firing")]
    assert shed.value == pytest.approx(20.0)


def test_rule_validation_fails_closed():
    with pytest.raises(ValueError, match="unregistered series"):
        alerts.ThresholdRule("x", "nope_total", 1.0)
    with pytest.raises(ValueError, match="unregistered series"):
        # a counter is not a gauge: source picks the registry
        alerts.ThresholdRule("x", "veles_shed_requests_total", 1.0,
                             source="gauge")
    with pytest.raises(ValueError, match="unknown op"):
        alerts.ThresholdRule("x", "veles_serving_queue_depth", 1.0,
                             op="!=")
    with pytest.raises(ValueError, match="unknown source"):
        alerts.ThresholdRule("x", "veles_serving_queue_depth", 1.0,
                             source="avg")
    with pytest.raises(ValueError, match="unknown severity"):
        alerts.ThresholdRule("x", "veles_serving_queue_depth", 1.0,
                             severity="page")
    with pytest.raises(ValueError, match="objective"):
        alerts.BurnRateRule("x", TTFT, 0.5, objective=1.5)
    with pytest.raises(ValueError, match="slow_window"):
        alerts.BurnRateRule("x", TTFT, 0.5, fast_window=60.0,
                            slow_window=30.0)
    with pytest.raises(ValueError, match="unknown type"):
        alerts.parse_rule({"name": "x", "type": "anomaly"})
    with pytest.raises(ValueError):                 # unexpected field
        alerts.parse_rule({"name": "x", "type": "threshold",
                           "series": "veles_serving_queue_depth",
                           "threshold": 1.0, "frobnicate": True})
    with pytest.raises(ValueError, match="duplicate"):
        alerts.AlertEngine(None, [
            alerts.ThresholdRule("a", "veles_serving_queue_depth", 1),
            alerts.ThresholdRule("a", "veles_serving_queue_depth", 2),
        ])


def test_default_rules_and_operator_overrides_from_config():
    names = {r.name for r in alerts.default_rules()}
    assert names == {"slo_ttft_burn", "slo_e2e_burn",
                     "queue_depth_high", "shed_rate_high",
                     "brownout_shedding"}
    by_name = {r.name: r for r in alerts.default_rules()}
    assert by_name["brownout_shedding"].severity == "critical"
    # the knob block retargets the shipped rules without redefining
    node = root.common.telemetry.watch
    node.slo_ttft_ms = 250.0
    node.fast_window = 2.0
    node.slow_window = 6.0
    node.burn_factor = 2.0
    by_name = {r.name: r for r in alerts.default_rules()}
    assert by_name["slo_ttft_burn"].slo_seconds \
        == pytest.approx(0.25)
    assert by_name["slo_ttft_burn"].fast_window == 2.0
    assert by_name["slo_ttft_burn"].factor == 2.0
    # operator rules append, and a duplicate name REPLACES the default
    node.rules = [
        {"name": "gpu_queue", "type": "threshold",
         "series": "veles_serving_queue_depth", "threshold": 5.0},
        {"name": "queue_depth_high", "type": "threshold",
         "series": "veles_serving_queue_depth", "threshold": 7.0},
    ]
    by_name = {r.name: r for r in alerts.rules_from_config()}
    assert by_name["gpu_queue"].threshold == 5.0
    assert by_name["queue_depth_high"].threshold == 7.0
    assert len(by_name) == 6
    # a malformed operator rule refuses to start the engine
    node.rules = [{"name": "bad", "type": "threshold",
                   "series": "not_registered", "threshold": 1.0}]
    with pytest.raises(ValueError, match="unregistered series"):
        alerts.rules_from_config()


# -- off is OFF (the bit-identical contract) ----------------------------------

def test_watch_off_is_bit_identical_off():
    before = {name: counters.get(name) for name in WATCH_COUNTERS}
    assert timeseries.enabled() is False
    assert timeseries.maybe_start() is None
    assert timeseries.store() is None
    assert timeseries.alert_engine() is None
    assert alerts.render_firing() == ""
    assert timeseries.alerts_payload() == {"enabled": False,
                                           "rules": []}
    body = pull_payload(0)
    lines = body.strip().splitlines()
    assert len(lines) == 1
    header = json.loads(lines[0])
    assert header["enabled"] is False
    assert header["cursor"] == 0 and header["records"] == 0
    # not one watch counter moved through any of the reads above
    after = {name: counters.get(name) for name in WATCH_COUNTERS}
    assert after == before


def test_maybe_start_samples_and_stop_watch_tears_down():
    node = root.common.telemetry.watch
    node.enabled = True
    node.period = 0.02
    node.retention = 10.0
    s0 = counters.get("veles_watch_samples_total")
    st = timeseries.maybe_start()
    assert st is not None
    assert timeseries.maybe_start() is st          # idempotent
    assert timeseries.alert_engine() is not None
    deadline = time.time() + 10
    while len(st.samples()) < 2 and time.time() < deadline:
        time.sleep(0.02)
    assert len(st.samples()) >= 2
    assert counters.get("veles_watch_samples_total") > s0
    # a live pull: header + records, counted
    p0 = counters.get("veles_watch_pulls_total")
    header, records = parse_history(pull_payload(0))
    assert header["enabled"] is True and header["cursor"] > 0
    assert header["records"] == len(records) >= 2
    assert isinstance(header["alerts"], list) and header["alerts"]
    assert counters.get("veles_watch_pulls_total") == p0 + 1
    payload = timeseries.alerts_payload()
    assert payload["enabled"] is True
    assert {r["rule"] for r in payload["rules"]} \
        >= {"slo_ttft_burn", "brownout_shedding"}
    timeseries.stop_watch()
    assert timeseries.store() is None
    assert timeseries.alert_engine() is None
    frozen = counters.get("veles_watch_samples_total")
    time.sleep(0.1)
    assert counters.get("veles_watch_samples_total") == frozen


# -- the client-side fleet helpers (veles-tpu watch internals) ----------------

def _fake_agg(retired, ttft_cum, up=(True, True)):
    """One fleet.aggregate()-shaped result: merged exposition-form
    registries + per-endpoint up flags."""
    count = float(sum(ttft_cum.values()))
    return {
        "merged": {
            "counters": {"veles_serving_retired_total": retired,
                         "veles_serving_tokens_total": retired * 4.0},
            "histograms": {TTFT: {
                "buckets": dict(ttft_cum, **{"+Inf": count}),
                "count": count, "sum": count * 0.05}},
            "gauges": {"veles_serving_slots": 4.0,
                       "veles_serving_slots_busy": 1.0,
                       "veles_serving_queue_depth": 0.0},
        },
        "endpoints": [{"up": u} for u in up],
    }


def test_hist_to_snapshot_uncumulates_exposition_buckets():
    snap = fleet.hist_to_snapshot(
        {"buckets": {"0.1": 5.0, "1.0": 8.0, "+Inf": 10.0},
         "count": 10.0, "sum": 3.5})
    assert snap["bounds"] == [0.1, 1.0]
    assert snap["counts"] == [5.0, 3.0, 2.0]       # + overflow bucket
    assert snap["count"] == 10.0 and snap["sum"] == 3.5
    qs = fleet.quantiles({"buckets": {"0.1": 5.0, "1.0": 8.0,
                                      "+Inf": 10.0},
                          "count": 10.0, "sum": 3.5}, qs=(0.5,))
    assert qs[0.5] is not None and qs[0.5] <= 1.0


def test_ingest_aggregate_and_interval_report_windowed_rates():
    fk = FakeClock()
    st = SeriesStore(period=1.0, retention=60.0, clock=fk,
                     count_samples=False)
    rep = fleet.interval_report(st)
    assert rep["qps"] is None and rep["up"] is None
    fleet.ingest_aggregate(st, _fake_agg(100.0, {"0.1": 50.0}),
                           ts=fk.tick())
    fleet.ingest_aggregate(st, _fake_agg(130.0, {"0.1": 70.0},
                                         up=(True, False)),
                           ts=fk.tick(2.0))
    rep = fleet.interval_report(st)
    assert rep["endpoints"] == 2.0 and rep["up"] == 1.0
    assert rep["qps"] == pytest.approx(15.0)       # 30 retired / 2 s
    assert rep["tok_s"] == pytest.approx(60.0)
    assert rep["ttft_p50"] is not None
    assert rep["slots"] == 4.0 and rep["slots_busy"] == 1.0
    line = fleet.format_interval(rep)
    assert "up 1/2" in line and "qps 15" in line


def test_loadgen_verdict_fails_on_alert_abort():
    report = {"wall_seconds": 1.0, "offered": 5, "dispatched": 3,
              "answered": 3, "records": [],
              "aggregates": aggregate([], 1.0)}
    # park the TTFT bound: aggregate() folds in the PROCESS-global
    # server histogram, which earlier suites legitimately filled
    assert verdict(report, slo_ttft_ms=1e9)["pass"] is True
    report["aborted_on_alert"] = {"rules": ["slo_ttft_burn"],
                                  "after_requests": 3}
    v = verdict(report, slo_ttft_ms=1e9)
    assert v["pass"] is False
    check = {c["name"]: c for c in v["checks"]}["aborted_on_alert"]
    assert check["ok"] is False
    assert check["observed"] == "slo_ttft_burn"


# -- the live fleet: watch/alerts CLIs over real replicas ---------------------

@pytest.fixture(scope="module")
def lm_wf():
    lm = import_model("char_lm")
    from veles_tpu import prng
    prng.seed_all(2025)
    wf = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                           dim=32, n_train=64, n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    return lm, wf


def _get_text(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def test_watch_and_alerts_clis_over_live_fleet(lm_wf):
    """A 2-replica fleet behind the router with the watchtower ON:
    the sampler comes up with the first HTTP surface, the history
    cursor pull round-trips over HTTP, /alerts lists the shipped
    rules, /metrics carries the veles_alert_firing rows, and the
    watch / alerts CLIs read it all like a remote operator."""
    from veles_tpu.__main__ import main
    from veles_tpu.serving.router import FleetRouter
    lm, wf = lm_wf
    node = root.common.telemetry.watch
    node.enabled = True
    node.period = 0.05
    node.retention = 60.0
    # park the latency SLOs out of range: compile-heavy first
    # requests on a CI host would legitimately burn the shipped
    # 500 ms budget, and this test wants a QUIET fleet (the firing
    # path is locked by the engine tests above and the storm drill
    # below)
    node.slo_ttft_ms = 600000.0
    node.slo_e2e_ms = 600000.0
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8,),
                             max_context=24,
                             name="watchtest_%d" % i)
            for i in range(2)]
    router = None
    try:
        for api in apis:
            api.initialize()
        st = timeseries.store()
        assert st is not None        # the first surface started it
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=3, retry_budget=2,
            attempt_timeout=60.0, request_timeout=120.0,
            name="watchtest.router").start()
        base = "http://127.0.0.1:%d" % router.port
        rng = numpy.random.RandomState(41)
        for i in range(3):
            prompt = [int(t) for t in rng.randint(0, lm.VOCAB, 5)]
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"prompt": prompt,
                                 "n_new": 3}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                assert r.status == 200
                assert json.loads(r.read())["tokens"]
        deadline = time.time() + 10
        while len(st.samples()) < 3 and time.time() < deadline:
            time.sleep(0.05)
        assert len(st.samples()) >= 3
        # the HTTP cursor pull, full then incremental
        header, records = parse_history(
            _get_text(base + "/metrics/history?since=0"))
        assert header["enabled"] is True and header["cursor"] > 0
        assert any(r["kind"] == "watch.sample" for r in records)
        cursor = header["cursor"]
        h2, recs2 = parse_history(
            _get_text(base + "/metrics/history?since=%d" % cursor))
        assert all(r["seq"] > cursor for r in recs2)
        assert h2["cursor"] >= cursor
        # /alerts lists the shipped rule set; nothing firing at idle
        payload = json.loads(_get_text(base + "/alerts"))
        assert payload["enabled"] is True
        assert {r["rule"] for r in payload["rules"]} \
            >= {"slo_ttft_burn", "queue_depth_high",
                "brownout_shedding"}
        assert payload["firing"] == []
        # the firing gauge rows ride every live /metrics page
        text = _get_text(base + "/metrics")
        assert 'veles_alert_firing{rule="slo_ttft_burn"} 0' in text
        # the dispatch-count lock: the sampler only READS registries
        # — watching an idle fleet must not move the dispatch plane
        d0 = counters.get("veles_dispatches_total")
        n0 = len(st.samples())
        deadline = time.time() + 10
        while len(st.samples()) < n0 + 3 and time.time() < deadline:
            time.sleep(0.05)
        assert len(st.samples()) >= n0 + 3
        assert counters.get("veles_dispatches_total") == d0
        # veles-tpu watch --once: one frame, exit 0
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["watch", base, "--once", "--no-clear",
                       "--period", "0.2", "--window", "10"])
        frame = out.getvalue()
        assert rc == 0
        assert "veles-tpu watch" in frame and "alerts:" in frame
        assert "1/1 endpoint(s) up" in frame
        # --json frames are machine-readable and carry the alerts
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["watch", base, "--once", "--json",
                       "--period", "0.2"])
        assert rc == 0
        rep = json.loads(out.getvalue().strip().splitlines()[-1])
        assert rep["alerts"]["enabled"] is True
        assert "qps" in rep and "ttft_p99" in rep
        # metrics aggregate --watch: one interval line per scrape
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["metrics", "aggregate", base, "--watch",
                       "0.2", "--iterations", "2"])
        assert rc == 0
        lines = [ln for ln in out.getvalue().splitlines()
                 if ln.strip()]
        assert len(lines) == 2
        assert "up 1/1" in lines[-1] and "qps" in lines[-1]
        # veles-tpu alerts: 0 with nothing firing, 2 with nobody home
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["alerts", base])
        assert rc == 0
        assert "rule(s), 0 firing" in out.getvalue()
        assert main(["alerts", "127.0.0.1:9", "--timeout", "1"]) == 2
    finally:
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()
        timeseries.stop_watch()


def test_storm_fires_then_heals_slo_ttft_burn_over_live_fleet(lm_wf):
    """The watchtower drill. A ``serve.decode_step:delay`` storm rides
    an open-loop burst, so TTFT blows its SLO: the burn-rate rule
    fires while load is offered and the harness's abort-on-alert poller
    stops the burst; the storm heals and clean traffic resolves the
    alert through the rule's hysteresis. Both transitions show wherever
    an operator looks: the ``/metrics/history`` pull (after a sample
    that holds a TTFT over the SLO), the flight recorder, and a
    ``watch --once`` frame taken while the alert fires."""
    from veles_tpu.__main__ import main
    from veles_tpu.loadgen import ChaosStorm, LoadGen, Workload
    from veles_tpu.serving.router import FleetRouter
    lm, wf = lm_wf
    slo_ms = 250.0
    node = root.common.telemetry.watch
    node.enabled = True
    node.period = 0.25
    node.retention = 120.0
    node.fast_window = 2.0
    node.slow_window = 6.0
    node.burn_factor = 2.0
    node.objective = 0.95
    node.slo_ttft_ms = slo_ms
    # the other rules parked out of range: exactly the TTFT pair
    node.slo_e2e_ms = 600000.0
    node.queue_depth_limit = 100000.0
    node.shed_rate_limit = 100000.0

    def workload(n, rate, seed):
        return Workload(n_requests=n, rate=rate, shape="steady",
                        min_prompt=4, max_prompt=8, n_new=4,
                        vocab=lm.VOCAB, batch_fraction=0.0,
                        stream_fraction=0.0, sample_fraction=0.0,
                        shared_fraction=0.0, seed=seed)

    def transitions(state):
        return [e for e in timeseries.store().records("watch.alert")
                if e.get("rule") == "slo_ttft_burn"
                and e.get("state") == state]

    aborts0 = counters.get("veles_loadgen_alert_aborts_total")
    samples0 = counters.get("veles_watch_samples_total")
    pulls0 = counters.get("veles_watch_pulls_total")
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8,),
                             max_context=24, name="stormtest_%d" % i)
            for i in range(2)]
    router = None
    try:
        for api in apis:
            api.initialize()
        assert timeseries.store() is not None
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=3, retry_budget=2,
            attempt_timeout=60.0, request_timeout=120.0,
            name="stormtest.router").start()
        base = "http://127.0.0.1:%d" % router.port
        storm = ChaosStorm("serve.decode_step", "delay",
                           window=(0, 1000000))
        report = LoadGen(base, workload(80, 8.0, seed=5),
                         storms=[storm], timeout=120.0,
                         abort_on_alert=True, alert_poll=0.2,
                         name="stormtest.storm").run()
        assert report.get("aborted_on_alert"), \
            "no rule fired while load was offered"
        assert counters.get("veles_loadgen_alert_aborts_total") \
            - aborts0 == 1
        deadline = time.time() + 30
        while not transitions("firing") and time.time() < deadline:
            time.sleep(0.1)
        fired = transitions("firing")[0]
        out = io.StringIO()
        with redirect_stdout(out):
            rc = main(["watch", base, "--once", "--no-clear",
                       "--period", "0.5", "--window", "5"])
        assert rc == 0
        assert "slo_ttft_burn" in out.getvalue()
        assert "FIRING" in out.getvalue()
        # the storm is gone with the burst (StormPlan restored the
        # fault plane): clean traffic walks the rule back
        for round_ in range(8):
            LoadGen(base, workload(40, 12.0, seed=6 + round_),
                    timeout=120.0,
                    name="stormtest.heal_%d" % round_).run()
            if any(e["ts"] > fired["ts"]
                   for e in transitions("resolved")):
                break
        assert any(e["ts"] > fired["ts"]
                   for e in transitions("resolved"))
        header, records = parse_history(
            _get_text(base + "/metrics/history?since=0"))
        assert header["enabled"] is True
        states = [r.get("state") for r in records
                  if r.get("kind") == "watch.alert"
                  and r.get("rule") == "slo_ttft_burn"]
        assert "firing" in states and "resolved" in states
        # the pull tells the story in order: a sample whose TTFT
        # histogram grew a count over the SLO lies before the firing
        bad_before, prev_bad = False, None
        for rec in records:
            h = (rec.get("hist") or {}).get(TTFT)
            if rec.get("kind") != "watch.sample" or not h:
                continue
            bad = int(h.get("count", 0)) - sum(
                c for b, c in zip(h["bounds"], h["counts"])
                if float(b) * 1000.0 <= slo_ms)
            if prev_bad is not None and bad > prev_bad \
                    and rec.get("ts", 0) <= fired["ts"]:
                bad_before = True
                break
            prev_bad = bad
        assert bad_before
        seen = {(r.get("rule"), r.get("state"))
                for r in flight.records() if r.get("kind") == "alert"}
        assert ("slo_ttft_burn", "firing") in seen
        assert ("slo_ttft_burn", "resolved") in seen
        assert counters.get("veles_watch_samples_total") > samples0
        assert counters.get("veles_watch_pulls_total") > pulls0
    finally:
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()
        timeseries.stop_watch()
