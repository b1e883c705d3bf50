"""The load/chaos harness (ISSUE 17): seeded workload synthesis,
timed chaos storms over the fault plane, and the SLO verdict.

Everything here is deterministic and fleet-free: the workload is a
seeded program (same knobs + seed -> same arrivals, same bodies), a
storm is a ``window=T0:T1`` fault clause armed via ``VELES_FAULTS``
and ALWAYS restored, and the verdict folds explicit aggregates into
explicit pass/fail checks. One live :class:`LoadGen` run targets
a dead port — a refused connection is data (the errors lane), and it
exercises the whole open-loop dispatch/join machinery in
milliseconds. The last test is the fleet under a burst: two QoS
replicas behind a QoS router, every offered request answered once.
"""
import os
import time

import pytest

from veles_tpu.error import VelesError
from veles_tpu.loadgen import (ChaosStorm, LoadGen, StormPlan,
                               Workload, aggregate, parse_storm,
                               percentile, verdict)
from veles_tpu.resilience.faults import plane
from veles_tpu.telemetry.counters import counters


# -- workload synthesis: seeded, bounded, labeled ----------------------------

def test_workload_is_deterministic_per_seed():
    knobs = dict(n_requests=40, rate=50.0, shape="diurnal",
                 min_prompt=4, max_prompt=32, sample_fraction=0.5,
                 stream_fraction=0.5, seed=7)
    a, b = Workload(**knobs), Workload(**knobs)
    assert a.arrivals() == b.arrivals()
    assert a.requests() == b.requests()
    c = Workload(**{**knobs, "seed": 8})
    assert c.requests() != a.requests()


def test_workload_shape_changes_arrivals_not_bodies():
    base = dict(n_requests=30, rate=50.0, seed=3)
    steady = Workload(shape="steady", **base)
    burst = Workload(shape="burst", **base)
    assert steady.requests() == burst.requests()
    assert steady.arrivals() != burst.arrivals()


def test_workload_prompt_lengths_and_labels_bounded():
    wl = Workload(n_requests=200, min_prompt=4, max_prompt=16,
                  batch_fraction=0.5, sample_fraction=0.5,
                  deadline_ms=250.0, vocab=64, seed=5)
    arrivals, bodies = wl.arrivals(), wl.requests()
    assert len(arrivals) == len(bodies) == 200
    assert arrivals == sorted(arrivals) and arrivals[0] > 0.0
    seen = {"interactive": 0, "batch": 0}
    for body in bodies:
        assert 4 <= len(body["prompt"]) <= 16
        assert all(0 < t < 64 for t in body["prompt"])
        assert body["mode"] in ("greedy", "sample")
        if body["mode"] == "sample":
            assert body["temperature"] > 0 and body["seed"] >= 1
        seen[body["priority"]] += 1
        # deadline_ms rides ONLY the protected class
        if body["priority"] == "interactive":
            assert body["deadline_ms"] == 250.0
        else:
            assert "deadline_ms" not in body
    assert seen["interactive"] and seen["batch"]


def test_workload_shared_prefixes():
    wl = Workload(n_requests=50, min_prompt=8, max_prompt=24,
                  shared_fraction=1.0, prefix_len=6, n_prefixes=2,
                  seed=9)
    bodies = wl.requests()
    openings = {tuple(b["prompt"][:6]) for b in bodies}
    assert len(openings) == 2        # every prompt opens with one of
    # the n_prefixes fixed system prompts


def test_workload_rejects_bad_knobs():
    with pytest.raises(ValueError):
        Workload(shape="tsunami")
    with pytest.raises(ValueError):
        Workload(rate=0.0)
    with pytest.raises(ValueError):
        Workload(min_prompt=8, max_prompt=4)


# -- storms: window-clause round trip + arm/restore --------------------------

def test_parse_storm_round_trip():
    storm = parse_storm("dispatch:raise:window=3:7,p=0.5")
    assert (storm.point, storm.action) == ("dispatch", "raise")
    assert storm.window == (3, 7) and storm.p == 0.5
    assert parse_storm(storm.spec()).spec() == storm.spec()


@pytest.mark.parametrize("text", [
    "dispatch:raise",                      # no window: not a storm
    "dispatch:raise:window=1:3;download:raise:window=1:3",   # two
    "nosuchpoint:raise:window=1:3",        # unknown injection point
])
def test_parse_storm_rejects(text):
    with pytest.raises((ValueError, VelesError)):
        parse_storm(text)


def test_chaos_storm_validates_eagerly():
    with pytest.raises(VelesError):
        ChaosStorm("nosuchpoint")


def test_storm_plan_arms_and_restores_env():
    far = ChaosStorm("dispatch", window=(10 ** 6, 10 ** 6 + 1))
    prior_env = os.environ.get("VELES_FAULTS")
    try:
        os.environ.pop("VELES_FAULTS", None)
        plane.configure()
        before = counters.get("veles_loadgen_storms_total")
        with StormPlan([far]):
            assert os.environ["VELES_FAULTS"] == far.spec()
            assert far.spec() in plane.current_spec()
        assert "VELES_FAULTS" not in os.environ
        assert counters.get("veles_loadgen_storms_total") \
            - before == 1
        # a pre-existing spec is COMBINED for the run, then restored
        os.environ["VELES_FAULTS"] = \
            "download:raise:window=10000000:10000001"
        plane.configure()
        with StormPlan([far]):
            armed = os.environ["VELES_FAULTS"]
            assert armed.startswith("download:") \
                and armed.endswith(far.spec())
        assert os.environ["VELES_FAULTS"].startswith("download:")
    finally:
        if prior_env is None:
            os.environ.pop("VELES_FAULTS", None)
        else:
            os.environ["VELES_FAULTS"] = prior_env
        plane.configure()


def test_storm_plan_empty_is_a_noop():
    prior = os.environ.get("VELES_FAULTS")
    with StormPlan([]):
        assert os.environ.get("VELES_FAULTS") == prior


# -- aggregates + verdict: pure folds ----------------------------------------

def test_percentile_nearest_rank():
    assert percentile([], 0.99) is None
    assert percentile([7.0], 0.5) == 7.0
    vals = list(range(1, 101))
    assert percentile(vals, 0.0) == 1
    assert percentile(vals, 0.5) == 51   # nearest rank on 100 points
    assert percentile(vals, 1.0) == 100


def _rec(priority="interactive", status=200, error=None, shed=False,
         ttft_s=None, e2e_s=0.1, tokens=8, stream=False):
    return {"priority": priority, "status": status, "error": error,
            "shed": shed, "ttft_s": ttft_s, "e2e_s": e2e_s,
            "tokens": tokens, "stream": stream}


def test_aggregate_classifies_ok_shed_error():
    records = [
        _rec(ttft_s=0.02),
        _rec(ttft_s=0.04),
        _rec(status=503, error="shed", shed=True, tokens=0),
        _rec(status=None, error="URLError: refused", tokens=0),
        _rec(priority="batch", tokens=16),
        _rec(priority="batch", status=503, error="shed", shed=True,
             tokens=0),
    ]
    agg = aggregate(records, wall=2.0)
    inter, batch = agg["interactive"], agg["batch"]
    assert (inter["offered"], inter["ok"], inter["shed"],
            inter["errors"]) == (4, 2, 1, 1)
    assert (batch["offered"], batch["ok"], batch["shed"],
            batch["errors"]) == (2, 1, 1, 0)
    # tokens (and goodput) count ONLY answered-200 work
    assert inter["tokens"] == 16 and batch["tokens"] == 16
    assert agg["goodput_tokens_per_s"] == pytest.approx(16.0)
    assert inter["ttft_p50_ms"] == pytest.approx(20.0)
    assert inter["ttft_p99_ms"] == pytest.approx(40.0)


def _report(server_ttft=None, client_ttft=None, offered=10, shed=0,
            errors=0, goodput=100.0):
    inter = {"offered": offered, "ok": offered - shed - errors,
             "shed": shed, "errors": errors, "tokens": 0,
             "ttft_p50_ms": client_ttft, "ttft_p99_ms": client_ttft,
             "e2e_p50_ms": 1.0, "e2e_p99_ms": 1.0}
    return {"aggregates": {
        "interactive": inter,
        "batch": dict(inter, offered=0, ok=0),
        "goodput_tokens_per_s": goodput,
        "server_ttft_p99_ms": server_ttft,
        "server_queue_wait_p99_ms": None,
    }}


def test_verdict_prefers_server_ttft_and_bounds():
    # server histogram wins over the (worse) client observation
    v = verdict(_report(server_ttft=100.0, client_ttft=5000.0),
                slo_ttft_ms=1000.0)
    assert v["pass"] is True
    # no server signal: judged on the client-side number
    v = verdict(_report(server_ttft=None, client_ttft=5000.0),
                slo_ttft_ms=1000.0)
    assert v["pass"] is False
    names = {c["name"]: c for c in v["checks"]}
    assert names["interactive_ttft_p99_ms"]["ok"] is False


def test_verdict_interactive_loss_and_goodput_bounds():
    v = verdict(_report(offered=20, shed=1, errors=1),
                max_interactive_loss=0.05)
    names = {c["name"]: c for c in v["checks"]}
    assert names["interactive_loss_fraction"]["observed"] == 0.1
    assert v["pass"] is False
    assert verdict(_report(offered=20, shed=1),
                   max_interactive_loss=0.05)["pass"] is True
    v = verdict(_report(goodput=3.0), min_goodput_tokens_per_s=5.0)
    names = {c["name"]: c for c in v["checks"]}
    assert names["goodput_tokens_per_s"]["ok"] is False


# -- the driver itself: open loop against a dead port ------------------------

def test_loadgen_records_a_dead_fleet_as_errors():
    """A refused connection is DATA: every offered request answers as
    an error (not a shed), the report stays whole, and the counters
    move — the machinery the live drill below builds on."""
    wl = Workload(n_requests=4, rate=1000.0, min_prompt=4,
                  max_prompt=4, n_new=1, seed=2)
    gen = LoadGen("http://127.0.0.1:9", wl, timeout=5.0)
    off0 = counters.get("veles_loadgen_requests_total")
    err0 = counters.get("veles_loadgen_errors_total")
    report = gen.run()
    assert report["offered"] == report["answered"] == 4
    agg = report["aggregates"]
    total = (agg["interactive"]["errors"] + agg["batch"]["errors"])
    assert total == 4
    assert agg["interactive"]["shed"] == agg["batch"]["shed"] == 0
    assert counters.get("veles_loadgen_requests_total") - off0 == 4
    assert counters.get("veles_loadgen_errors_total") - err0 == 4
    assert verdict(report, max_interactive_loss=0.0)["pass"] is False


# -- the fleet under a burst of twice what its slots sustain -----------------

def test_fleet_under_burst_answers_every_request_exactly_once():
    """Two QoS replicas (2 slots each) behind a QoS router take 24
    mixed interactive/batch requests offered at once. The interactive
    class comes through without a shed or an error, the QoS plane
    visibly works, every offered request has one terminal on the
    client's side and the server's, and both replicas' page and queue
    ledgers read zero after the drain."""
    import veles_tpu as vt
    from conftest import import_model
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.serving.router import FleetRouter
    from veles_tpu.telemetry.counters import histograms
    lm = import_model("char_lm")
    prng.seed_all(8282)
    wf = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                           dim=32, n_train=64, n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    pressure_names = ("veles_qos_throttled_total",
                      "veles_qos_preemptions_total",
                      "veles_qos_batch_deferrals_total")
    root.common.serving.qos = True
    root.common.router.qos = True
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8, 16),
                             max_context=32, name="burst_%d" % i)
            for i in range(2)]
    router = None
    try:
        for api in apis:
            api.initialize()
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=3, retry_budget=2,
            attempt_timeout=60.0, request_timeout=90.0,
            name="burst.router").start()
        workload = Workload(n_requests=24, rate=400.0, shape="burst",
                            min_prompt=4, max_prompt=8, n_new=4,
                            vocab=lm.VOCAB, batch_fraction=0.5,
                            stream_fraction=0.0, sample_fraction=0.0,
                            shared_fraction=0.25, seed=11)
        e2e0 = histograms.count("veles_serving_e2e_seconds")
        pressure0 = sum(counters.get(n) for n in pressure_names)
        report = LoadGen("http://127.0.0.1:%d" % router.port, workload,
                         timeout=120.0, name="burst.loadgen").run()
        agg = report["aggregates"]
        assert report["answered"] == report["offered"] == 24
        assert sum(agg[c][k] for c in ("interactive", "batch")
                   for k in ("ok", "shed", "errors")) == 24
        assert agg["interactive"]["shed"] == 0
        assert agg["interactive"]["errors"] == 0
        # park the TTFT bound: it is a time, and aggregate() folds in
        # the process-global server histogram
        assert verdict(report, slo_ttft_ms=1e9,
                       max_interactive_loss=0.0)["pass"] is True
        assert sum(counters.get(n) for n in pressure_names) > pressure0
        ok_total = agg["interactive"]["ok"] + agg["batch"]["ok"]
        assert histograms.count("veles_serving_e2e_seconds") - e2e0 \
            == ok_total
        schedulers = [api._engine.scheduler for api in apis]
        deadline = time.time() + 15
        while time.time() < deadline and any(
                s.busy_count() or s.queue_depth() for s in schedulers):
            time.sleep(0.05)
        for api in apis:
            assert api._engine.page_pool.in_use() == 0
            assert api._engine.scheduler.queue_depth() == 0
    finally:
        root.common.serving.qos = False
        root.common.router.qos = False
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()
