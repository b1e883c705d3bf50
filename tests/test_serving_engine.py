"""Continuous-batching serving engine (veles_tpu/serving/): persistent
slot-pool KV cache, bucketed prefill, iteration-level scheduling.

The contract under test: a request's tokens are a pure function of the
request (id-exact vs its solo decode, greedy AND sampled — per-slot
PRNG streams), short requests retire the moment they finish instead of
riding out long co-tenants, the jit cache is bounded by
``len(buckets) + 1`` programs, and tickets older than their deadline
are answered 503 + Retry-After instead of rotting in the queue."""
import json
import queue
import re
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import prng
from veles_tpu.serving import ContinuousEngine, parse_buckets
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import SlotScheduler, Ticket
from veles_tpu.telemetry.counters import counters

from conftest import import_model


def _post(url, payload, timeout=60.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


@pytest.fixture(scope="module")
def served():
    lm = import_model("char_lm")
    prng.seed_all(971)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                           dim=32, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    engine = ContinuousEngine(wf, max_slots=3, buckets=(8, 16),
                              max_context=48, name="eng_t").start()
    yield lm, wf, engine
    engine.stop()


def _prompt(lm, seed, length=12):
    return [int(t) for t in
            lm.make_corpus(numpy.random.RandomState(seed), length)]


# -- scheduler geometry (no jax) ---------------------------------------------

def test_bucket_selection_and_rejection():
    sched = SlotScheduler(2, (8, 16), 32)
    assert sched.bucket_for(3) == 8
    assert sched.bucket_for(8) == 8
    assert sched.bucket_for(9) == 16
    assert sched.bucket_for(17) is None
    assert sched.reject_reason(5, 10) is None
    assert "bucket" in sched.reject_reason(20, 4)
    assert "max_context" in sched.reject_reason(16, 30)
    with pytest.raises(ValueError):
        SlotScheduler(2, (8, 64), 32)     # bucket beyond max_context


def test_parse_buckets_forms():
    assert parse_buckets("16, 8,8") == (8, 16)
    assert parse_buckets([32, 16]) == (16, 32)
    from veles_tpu.error import VelesError
    with pytest.raises(VelesError):
        parse_buckets("")


def test_expired_ticket_purged_even_when_pool_full():
    sched = SlotScheduler(1, (8,), 16)
    t_busy, t_old = Ticket(), Ticket(deadline=time.time() - 1)
    sched.push(make_request([1, 2], 4), t_busy)
    admitted, expired = sched.take_admissions()
    assert len(admitted) == 1 and not expired
    sched.push(make_request([1, 2], 4), t_old)
    # pool is full — the expired HEAD must still be answered
    admitted, expired = sched.take_admissions()
    assert not admitted and expired == [t_old]
    # ... and so must an expired ticket BEHIND a live head
    t_live = Ticket(deadline=time.time() + 60)
    t_mid = Ticket(deadline=time.time() - 1)
    sched.push(make_request([1, 2], 4), t_live)
    sched.push(make_request([1, 2], 4), t_mid)
    admitted, expired = sched.take_admissions()
    assert not admitted and expired == [t_mid]
    assert sched.queue_depth() == 1               # t_live kept, FIFO


def test_poisoned_head_answered_400_not_crash_loop():
    """A queued request that fits no bucket (a checked=True submit
    bypassing accepts(), or a raw push) must be popped and answered
    400 — not crash take_admissions pre-pop tick after tick while the
    whole pool starves behind it."""
    sched = SlotScheduler(2, (8,), 16)
    bad, good = Ticket(), Ticket()
    sched.push(make_request([1] * 20, 2), bad)
    sched.push(make_request([1, 2], 2), good)
    admitted, expired = sched.take_admissions()
    assert bad.event.is_set() and bad.code == 400
    assert "bucket" in bad.error
    assert len(admitted) == 1          # the pool kept serving
    assert not expired


def test_retire_is_idempotent():
    # a shutdown abort racing a wedged worker's late _finish retires
    # the same slot twice — the free list must not hold an index twice
    sched = SlotScheduler(2, (8,), 16)
    sched.push(make_request([1, 2], 4), Ticket())
    (slot,), _ = sched.take_admissions()
    sched.retire(slot)
    sched.retire(slot)
    assert sorted(sched._free) == [0, 1]


# -- engine: lifecycle + id-exactness ----------------------------------------

def test_slot_lifecycle_admit_bucket_retire_reuse(served):
    """admit → prefill-bucket selection → retirement → slot reuse by a
    later request: 6 mixed-length requests through a 3-slot pool."""
    lm, wf, engine = served
    before = counters.snapshot()
    admitted0, retired0 = engine.admitted, engine.retired
    reqs = [make_request(_prompt(lm, s, length=ln), n, seed=s)
            for s, ln, n in ((1, 6, 8), (2, 12, 5), (3, 9, 10),
                             (4, 16, 6), (5, 5, 7), (6, 11, 9))]
    out = engine.serve(list(reqs))
    for req, toks in zip(reqs, out):
        assert len(toks) == req["n_new"]
        assert all(0 <= t < lm.VOCAB for t in toks)
    # every request owned a slot at some point; the pool has 3 rows,
    # so slots were REUSED (6 admissions through 3 slots)
    assert engine.admitted - admitted0 == 6
    assert engine.retired - retired0 == 6
    assert engine.scheduler.busy_count() == 0
    delta = counters.delta(before)
    assert delta["veles_serving_admitted_total"] == 6
    assert delta["veles_serving_retired_total"] == 6
    assert delta["veles_serving_tokens_total"] == \
        sum(r["n_new"] for r in reqs)
    assert delta["veles_serving_prefill_dispatches_total"] == 6
    assert delta["veles_serving_decode_dispatches_total"] >= 1


def test_concurrent_rows_id_exact_vs_solo_greedy_and_sampled(served):
    """The continuous-batching determinism bar: every row — greedy AND
    stochastic — equals its solo decode exactly, whatever strangers
    share the pool (per-slot PRNG streams derive noise purely from the
    request's seed)."""
    lm, wf, engine = served
    reqs = [make_request(_prompt(lm, 10 + i, length=5 + i), 6 + i % 3,
                         temperature=0.8 if i % 2 else 0.0,
                         seed=50 + i)
            for i in range(6)]
    solo = [engine.serve([r])[0] for r in reqs]
    conc = engine.serve(list(reqs))
    assert conc == solo
    # and the greedy/sampled rows also match the legacy scan decoder
    # (same _block_prefill/_block_step math, same per-row streams)
    from veles_tpu.nn import sampling
    for r, toks in zip(reqs, solo):
        assert toks == sampling.generate(
            wf, r["prompt"], r["n_new"],
            temperature=r["temperature"], seed=r["seed"])


def test_jit_program_cache_bounded_by_buckets(served):
    """After everything this module served, the engine holds at most
    ``programs_bound()`` jitted programs (the bucketed prefills + the
    fixed-shape decode step at each rung of its view ladder) — never
    one per distinct prompt length."""
    lm, wf, engine = served
    assert engine.programs_built <= engine.programs_bound()
    # and the dispatch counter rides _count_decode_dispatches, so the
    # decode plane stays visible to the round-5 regression lock
    before = counters.get("veles_decode_dispatches_total")
    engine.serve([make_request(_prompt(lm, 30, 7), 4)])
    assert counters.get("veles_decode_dispatches_total") > before
    assert engine.programs_built <= engine.programs_bound()
    # this engine's three pages a slot do not halve: one rung
    assert engine.programs_bound() == len(engine.buckets) + 1


def test_early_eos_retirement_frees_slot_for_queue(served):
    """A row emitting eos_id retires immediately — its tokens stop at
    the stop token and its slot is reused while longer co-tenants keep
    decoding."""
    lm, wf, engine = served
    p = _prompt(lm, 40, length=10)
    full = engine.serve([make_request(p, 12)])[0]
    eos = full[4]
    first = full.index(eos)
    retired0 = engine.admitted
    # 4 requests into 3 slots: the eos row must retire early and hand
    # its slot to the queued 4th request
    reqs = [make_request(p, 12, eos_id=eos),
            make_request(_prompt(lm, 41, 9), 12),
            make_request(_prompt(lm, 42, 13), 12),
            make_request(_prompt(lm, 43, 7), 12)]
    out = engine.serve(reqs)
    assert out[0] == full[:first + 1]
    assert out[0][-1] == eos
    assert len(out[0]) < 12                # retired before its n_new
    for toks in out[1:]:
        assert len(toks) == 12
    assert engine.admitted - retired0 == 4


def test_queued_past_deadline_answered_503(served):
    lm, wf, engine = served
    before = counters.get("veles_serving_expired_total")
    ticket = Ticket(deadline=time.time() - 0.5)
    assert engine.submit(make_request(_prompt(lm, 50, 6), 4), ticket)
    assert ticket.event.wait(30)
    assert ticket.error is not None and ticket.code == 503
    assert ticket.retry_after
    assert counters.get("veles_serving_expired_total") == before + 1


def test_injected_decode_fault_sheds_then_recovers(served, monkeypatch):
    lm, wf, engine = served
    from veles_tpu.error import VelesError
    monkeypatch.setenv("VELES_FAULTS", "serve.decode_step:raise:times=1")
    req = make_request(_prompt(lm, 60, 6), 6)
    with pytest.raises(VelesError, match="injected"):
        engine.serve([req])
    monkeypatch.setenv("VELES_FAULTS", "")
    # the pool stayed consistent: the very next request serves fine
    from veles_tpu.nn import sampling
    assert engine.serve([req])[0] == sampling.generate(
        wf, req["prompt"], req["n_new"], temperature=0)


def test_non_lm_workflow_degrades_to_window_worker():
    wf = vt.Workflow(None, name="w")
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           name="deg_g")
    api.initialize()
    try:
        assert api._engine is None         # graceful fallback, no raise
    finally:
        api.stop()


def test_bad_knob_geometry_raises_not_degrades(served):
    # an operator who ASKED for continuous batching must not silently
    # get the window worker because of a knob mistake
    lm, wf, _engine = served
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           buckets=(8, 128), max_context=48,
                           name="bad_g")
    with pytest.raises(ValueError):
        api.initialize()


# -- GenerationAPI over HTTP --------------------------------------------------

@pytest.fixture(scope="module")
def api_served(served):
    lm, wf, _engine = served
    api = vt.GenerationAPI(wf, port=0, engine="continuous", max_slots=3,
                           buckets=(8, 16), max_context=48,
                           name="capi")
    api.initialize()
    url = "http://127.0.0.1:%d/generate" % api.port
    yield lm, wf, api, url
    api.stop()


def test_http_greedy_and_sample_ride_the_engine(served, api_served):
    lm, wf, api, url = api_served
    from veles_tpu.nn import sampling
    p = _prompt(lm, 70, 9)
    code, out, _ = _post(url, {"prompt": p, "n_new": 8})
    assert code == 200, out
    assert out["engine"] == "continuous"
    assert out["tokens"] == sampling.generate(wf, p, 8, temperature=0)
    code, out, _ = _post(url, {"prompt": p, "n_new": 6,
                               "mode": "sample", "temperature": 0.7,
                               "seed": 11})
    assert code == 200 and out["engine"] == "continuous"
    assert out["tokens"] == sampling.generate(wf, p, 6,
                                              temperature=0.7, seed=11)


def test_http_oversized_request_falls_back_to_window(served,
                                                     api_served):
    """A prompt longer than the largest bucket (or a context overflow)
    still gets served — through the legacy shape-keyed worker."""
    lm, wf, api, url = api_served
    from veles_tpu.nn import sampling
    long_p = (_prompt(lm, 71, 12) * 2)[:20]     # > largest bucket 16
    code, out, _ = _post(url, {"prompt": long_p, "n_new": 5})
    assert code == 200, out
    assert "engine" not in out                  # window worker answered
    assert out["tokens"] == sampling.generate(wf, long_p, 5,
                                              temperature=0)


def test_http_expired_in_queue_gets_503_retry_after(served,
                                                    api_served):
    """request_timeout holds while QUEUED: with a zero timeout the
    ticket's deadline passes before any decode, and the scheduler
    answers 503 + Retry-After (not a silent 504)."""
    lm, wf, api, url = api_served
    prev = api.request_timeout
    api.request_timeout = 0.0
    try:
        code, out, headers = _post(url, {"prompt": _prompt(lm, 72, 6),
                                         "n_new": 4})
    finally:
        api.request_timeout = prev
    assert code == 503, out
    assert "expired" in out["error"]
    assert int(headers.get("Retry-After")) >= 1


def test_http_metrics_and_stats_expose_occupancy(served, api_served):
    lm, wf, api, url = api_served
    code, _, _ = _post(url, {"prompt": _prompt(lm, 73, 6), "n_new": 4})
    assert code == 200
    with urllib.request.urlopen(url + "/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert stats["engine"] == "continuous"
    assert stats["continuous"]["slots"] == 3
    assert stats["continuous"]["retired"] >= 1
    assert stats["continuous"]["programs"] <= 3
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % api.port, timeout=30) as r:
        text = r.read().decode()
    assert "veles_serving_slots 3" in text
    # same gauge names as web_status, just unsuffixed (one engine here)
    assert "veles_serving_queue_depth" in text
    assert "veles_serving_admitted_total" in text


def test_web_status_metrics_render_engine_gauges(served):
    lm, wf, engine = served
    from veles_tpu.web_status import WebStatusServer
    server = WebStatusServer(port=0)
    server._service.start_serving()
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % server.port,
                timeout=30) as r:
            text = r.read().decode()
        assert "veles_serving_slots_busy_eng_t" in text
        assert "veles_serving_queue_depth_eng_t" in text
        # paged-pool occupancy gauges (serving/pages.py) — the rows an
        # operator sizes pages/page_size with
        assert "veles_serving_pages_total_eng_t" in text
        assert "veles_serving_pages_in_use_eng_t" in text
        assert "veles_serving_page_fragmentation_eng_t" in text
    finally:
        server.stop()


# -- the paged pool ------------------------------------------------------------

def test_paged_admission_beats_dense_at_same_hbm():
    """THE paged-pool win, at the ledger: 16 pages x 8 positions is the
    HBM a dense pool spends on 128/32 = 4 slots of max_context=32. The
    paged scheduler admits on each request's OWN footprint, so the same
    HBM holds 8 concurrent short requests — strictly more than dense
    ever could."""
    from veles_tpu.serving.pages import PagePool
    pool = PagePool(16, 8)
    sched = SlotScheduler(8, (8,), 32, page_pool=pool)
    for s in range(8):
        sched.push(make_request([1, 2, 3, 4], 4, seed=s), Ticket())
    admitted, expired = sched.take_admissions()
    assert not expired
    assert len(admitted) == 8          # dense tops out at 4
    # each row reserved its own worst case (8 positions = 1 page)
    assert pool.in_use() == 8
    for slot in admitted:
        sched.retire(slot)
    assert pool.in_use() == 0
    assert pool.free_count() == 16


def test_admission_waits_for_pages_then_proceeds():
    """Real exhaustion at admission keeps FIFO order and waits for
    retirements (no shed): pages freed by a retiring row admit the
    waiting head on the next boundary."""
    from veles_tpu.serving.pages import PagePool
    pool = PagePool(2, 8)
    sched = SlotScheduler(4, (8,), 16, page_pool=pool)
    t1, t2 = Ticket(), Ticket()
    sched.push(make_request([1] * 6, 8), t1)     # worst 14 -> 2 pages
    sched.push(make_request([1] * 6, 8), t2)
    admitted, _ = sched.take_admissions()
    assert len(admitted) == 1                    # pool can hold one
    again, _ = sched.take_admissions()
    assert not again                             # starved, not shed
    assert not t2.event.is_set()
    sched.retire(admitted[0])
    admitted, _ = sched.take_admissions()
    assert len(admitted) == 1                    # head admitted now
    assert pool.in_use() == 2


def test_page_alloc_fault_sheds_503_and_ledger_stays_consistent(
        served, monkeypatch):
    """Chaos for satellite `serve.page_alloc`: an injected allocation
    fault sheds the admitting request 503 + Retry-After; the page
    ledger balances back to empty and the very next request decodes
    id-exact — recovery, not an outage."""
    lm, wf, engine = served
    shed0 = counters.get("veles_shed_requests_total")
    monkeypatch.setenv("VELES_FAULTS", "serve.page_alloc:raise:times=1")
    req = make_request(_prompt(lm, 80, 6), 6)
    ticket = Ticket()
    assert engine.submit(req, ticket)
    assert ticket.event.wait(60)
    assert ticket.error is not None and ticket.code == 503
    assert ticket.retry_after
    assert counters.get("veles_shed_requests_total") == shed0 + 1
    monkeypatch.setenv("VELES_FAULTS", "")
    from veles_tpu.nn import sampling
    assert engine.serve([req])[0] == sampling.generate(
        wf, req["prompt"], req["n_new"], temperature=0)
    # the ledger balanced: nothing leaked across the shed + recovery
    assert engine.page_pool.in_use() == 0
    assert engine.page_pool.free_count() == engine.pages


def test_unknown_mode_rejected_400_not_leaked(served):
    """accepts() must fail CLOSED on a mode string no tick path
    advances — admitting it would strand the ticket to timeout and
    leak the slot + its reserved pages forever."""
    lm, wf, engine = served
    ticket = Ticket()
    assert engine.submit(
        make_request(_prompt(lm, 140, 5), 4, mode="gredy"), ticket)
    assert ticket.event.wait(30)
    assert ticket.code == 400
    assert "mode" in ticket.error
    assert engine.page_pool.in_use() == 0


def test_page_reuse_after_retire_not_poisoned(served):
    """Pages freed by retired rows are immediately re-issued to new
    admissions; a page-constrained pool forces heavy reuse across
    waves, and every wave must stay id-exact — a stale row bleeding
    through a reused page would show up here."""
    lm, wf, _ = served
    engine = ContinuousEngine(wf, max_slots=3, buckets=(8,),
                              max_context=32, page_size=8, pages=6,
                              name="eng_tight").start()
    try:
        reqs_a = [make_request(_prompt(lm, 90 + i, 5), 6,
                               temperature=0.6 if i == 1 else 0.0,
                               seed=90 + i) for i in range(3)]
        reqs_b = [make_request(_prompt(lm, 95 + i, 6), 7, seed=95 + i)
                  for i in range(3)]
        ref_a = [engine.serve([r])[0] for r in reqs_a]
        for _wave in range(3):
            engine.serve(list(reqs_b))           # dirty every page
            assert engine.serve(list(reqs_a)) == ref_a
        assert engine.page_pool.in_use() == 0
        assert engine.page_pool.free_count() == engine.pages
    finally:
        engine.stop()


def test_quant_cache_invalidation_recalibrates(served):
    """Satellite regression: the int8 twin is cached on device-view
    leaf IDENTITY, so an in-place device mutation (same jax.Array,
    new bytes) would serve stale scales forever — an explicit
    :meth:`invalidate_quant_cache` must force recalibration at the
    next param refresh, while unchanged weights keep reusing the
    cached twin."""
    lm, wf, _ = served
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8,),
                              max_context=32, quant_weights=True,
                              name="eng_q")
    cal = lambda: counters.get("veles_quant_calibrations_total")  # noqa: E731
    c0 = cal()
    p1 = engine._prepare_params()
    assert cal() == c0 + 1
    p2 = engine._prepare_params()                # identity-cached
    assert cal() == c0 + 1
    assert p2 is p1
    engine.invalidate_quant_cache()
    p3 = engine._prepare_params()
    assert cal() == c0 + 2                       # recalibrated
    assert p3 is not p1
    # and through the serving path: idle-boundary refresh reuses the
    # twin until invalidated
    engine.start()
    try:
        req = make_request(_prompt(lm, 85, 6), 4)
        engine.serve([req])
        served_cal = cal()
        engine.serve([req])
        assert cal() == served_cal               # cache held
        engine.invalidate_quant_cache()
        engine.serve([req])
        assert cal() == served_cal + 1           # refresh recalibrated
    finally:
        engine.stop()


# -- speculative + beam on the pool -------------------------------------------

@pytest.fixture(scope="module")
def pooled(served):
    """Target + draft + an engine serving ALL four decode modes on one
    paged pool: 5 slots so greedy + sample + spec + one beam-width-2
    group can co-tenant a single step boundary."""
    lm, wf, _ = served
    prng.seed_all(437)
    draft = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=1,
                              dim=16, n_train=256, n_valid=64)
    draft.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    draft.run()
    engine = ContinuousEngine(wf, max_slots=5, buckets=(8, 16),
                              max_context=48, page_size=8,
                              spec_gamma=3, beam_width=2,
                              draft=draft, name="eng_pool").start()
    yield lm, wf, draft, engine
    engine.stop()


def test_speculative_id_exact_on_pool_vs_solo(pooled):
    """Pooled speculation (on-device draft/verify rounds over the page
    tables) emits the same tokens as the host-loop
    ``generate_speculative`` — greedy AND stochastic — and the greedy
    rows equal plain greedy decode (the speculation invariant)."""
    from veles_tpu.nn import sampling
    from veles_tpu.nn.speculative import generate_speculative
    lm, wf, draft, engine = pooled
    for temp, seed in ((0.0, 0), (0.7, 21)):
        p = _prompt(lm, 100 + seed, 7)
        req = make_request(p, 9, temperature=temp, seed=seed,
                           mode="speculative", gamma=3)
        toks = engine.serve([req])[0]
        solo, _stats = generate_speculative(
            wf, draft, p, 9, gamma=3, temperature=temp, seed=seed)
        assert toks == solo, "temp=%s" % temp
        if temp == 0.0:
            assert toks == sampling.generate(wf, p, 9, temperature=0)


def test_beam_id_exact_on_pool_vs_solo(pooled):
    """A pooled beam request (hypothesis rows on the page tables, the
    group top-k step, page-granular cache reorder) returns exactly
    ``beam_generate``'s best tokens and hypothesis scores."""
    from veles_tpu.nn.beam import beam_generate
    lm, wf, draft, engine = pooled
    from veles_tpu.serving.scheduler import Ticket as STicket
    for seed in (110, 111):
        p = _prompt(lm, seed, 6)
        req = make_request(p, 8, mode="beam", beam=2)
        ticket = STicket()
        assert engine.submit(req, ticket)
        assert ticket.event.wait(120)
        assert ticket.error is None, ticket.error
        solo, stats = beam_generate(wf, p, 8, beam=2)
        assert ticket.result["tokens"] == [int(t) for t in solo]
        assert numpy.allclose(ticket.result["scores"],
                              stats["scores"], atol=1e-4)


def test_mixed_mode_cotenancy_all_id_exact(pooled):
    """The full-stack co-tenancy bar: greedy + sampled + speculative +
    beam rows sharing ONE step boundary, every answer id-exact vs its
    own solo baseline — no mode perturbs another's tokens."""
    from veles_tpu.nn import sampling
    from veles_tpu.nn.beam import beam_generate
    from veles_tpu.nn.speculative import generate_speculative
    from veles_tpu.serving.scheduler import Ticket as STicket
    lm, wf, draft, engine = pooled
    pg = _prompt(lm, 120, 6)
    ps = _prompt(lm, 121, 9)
    pv = _prompt(lm, 122, 7)
    pb = _prompt(lm, 123, 5)
    reqs = [make_request(pg, 8),
            make_request(ps, 7, temperature=0.8, seed=7, mode="sample"),
            make_request(pv, 8, mode="speculative", gamma=3),
            make_request(pb, 6, mode="beam", beam=2)]
    tickets = [STicket() for _ in reqs]
    for r, t in zip(reqs, tickets):
        assert engine.submit(r, t)
    for t in tickets:
        assert t.event.wait(180)
        assert t.error is None, t.error
    # co-tenancy really happened: the beam pair plus at least two of
    # the single-row modes shared a step boundary (== 5 when all four
    # admissions land on the same tick, >= 4 when the first admission
    # races one boundary ahead)
    assert engine.peak_slots >= 4
    assert tickets[0].result["tokens"] == sampling.generate(
        wf, pg, 8, temperature=0)
    assert tickets[1].result["tokens"] == sampling.generate(
        wf, ps, 7, temperature=0.8, seed=7)
    spec_solo, _ = generate_speculative(wf, draft, pv, 8, gamma=3)
    assert tickets[2].result["tokens"] == spec_solo
    beam_solo, _ = beam_generate(wf, pb, 6, beam=2)
    assert tickets[3].result["tokens"] == [int(t) for t in beam_solo]
    # mode stats survive the pool: speculation reports its rounds
    assert tickets[2].result["rounds"] >= 1
    assert 0.0 <= tickets[2].result["acceptance"] <= 1.0


def test_scheduler_reserves_engine_gamma_for_gammaless_requests():
    """A speculative request that omits ``gamma`` must be page-
    reserved for the ENGINE's round width, not a literal default —
    under-reservation would resurrect mid-decode exhaustion for the
    exact rows the reservation policy promises it cannot happen to."""
    from veles_tpu.serving.pages import PagePool
    pool = PagePool(8, 8)
    sched = SlotScheduler(2, (8,), 40, page_pool=pool, spec_gamma=8)
    req = {"prompt": [1] * 6, "n_new": 10, "mode": "speculative"}
    sched.push(req, Ticket())
    (slot,), _ = sched.take_admissions()
    # worst = 6 + 10 + 8 + 1 = 25 positions -> 4 pages (a gamma=4
    # default would reserve only 3)
    assert len(slot.pages) == 4


def test_beam_n_new_1_finishes_at_admission(pooled):
    """An n_new=1 beam group is answered by its first hypothesis's
    prefill expansion; the dead sibling rows must not dispatch
    prefills of their own or leave pages behind."""
    from veles_tpu.nn.beam import beam_generate
    lm, wf, draft, engine = pooled
    p = _prompt(lm, 130, 6)
    before = counters.get("veles_serving_prefill_dispatches_total")
    toks = engine.serve([make_request(p, 1, mode="beam", beam=2)])[0]
    solo, _ = beam_generate(wf, p, 1, beam=2)
    assert toks == [int(t) for t in solo]
    assert counters.get("veles_serving_prefill_dispatches_total") \
        == before + 1
    assert engine.page_pool.in_use() == 0


def test_program_count_bounded_with_spec_and_beam(pooled):
    """After serving every decode mode, the jit cache holds at most
    ``programs_bound()`` programs — base prefills + decode step, draft
    prefills + the spec round, the beam step; a CONSTANT, never a
    function of traffic."""
    lm, wf, draft, engine = pooled
    assert engine.programs_built <= engine.programs_bound()
    # the base greedy/sample plane alone stays within the bucketed
    # prefills and the step's rungs
    base = [k for k in engine._progs
            if k[0] in ("prefill", "step")]
    assert len(base) <= len(engine.buckets) + len(engine.view_ladder)


# -- the float decode step's pool writes ----------------------------------------

#: slot layout for the two tests below: (mask, shared, pages, pos).
#: Page 5 is a prefix page three slots adopted; slot 2 starts INSIDE
#: its shared pages (the engine copies such a page first, the step
#: must not rely on it); slot 0 runs off the view's end at
#: decode_block 4; slots 3-5 are masked out (co-tenant spec/beam rows)
_STEP_SLOTS = [(1, 0, [1, 2, 3, 4], 26),
               (1, 1, [5, 6, 7, 0], 9),
               (1, 2, [5, 8, 9, 0], 14),
               (0, 0, [10, 11, 0, 0], 3),
               (0, 1, [5, 12, 0, 0], 9),
               (0, 0, [13, 14, 15, 16], 30)]


def _step_fixture(wf, decode_block):
    """An unstarted float engine's decode-step program, its parameters
    and a pool of noise (so an unwanted write shows wherever it lands)
    under the ``_STEP_SLOTS`` layout."""
    import jax.numpy as jnp
    engine = ContinuousEngine(wf, max_slots=len(_STEP_SLOTS),
                              buckets=(8,), max_context=32, page_size=8,
                              pages=24, decode_block=decode_block,
                              name="eng_rows%d" % decode_block)
    params = engine._prepare_params()
    engine._ensure_pool(params)
    rng = numpy.random.RandomState(31)
    caches = tuple(tuple(jnp.asarray(rng.standard_normal(a.shape)
                                     .astype(a.dtype)) for a in pool)
                   for pool in engine._caches)
    mask, shared, pages, pos = zip(*_STEP_SLOTS)
    state = dict(
        tok=numpy.arange(3, 3 + len(pos), dtype=numpy.int32),
        pos=numpy.array(pos, numpy.int32),
        temp=numpy.array([0.0, 0.8] * (len(pos) // 2), numpy.float32),
        mask=numpy.array(mask, numpy.int32),
        tables=numpy.array(pages, numpy.int32),
        shared=numpy.array(shared, numpy.int32),
        keys=jnp.asarray(rng.randint(0, 2 ** 31, (len(pos), 2))
                         .astype(numpy.uint32)))
    return engine, params, caches, state


def _whole_view_step(engine):
    """The oracle: gather every slot's whole view, ``_block_step``,
    write every view back whole — the formulation the row write-back
    replaced, kept here as the plain statement of what the pool must
    hold after a step."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.nn.sampling import (_block_step, _embed_ids,
                                       _head_logits, _split_rows)
    from veles_tpu.ops import matmul_precision
    from veles_tpu.serving.engine import _TEMP_EPS
    stem, head = engine.stack["stem"], engine.stack["head"]
    blocks, pos_emb = engine.stack["blocks"], engine.stack["pos_emb"]
    prec = matmul_precision()

    def step(params, tok, pos, temp, mask, tables, shared, keys, caches):
        def view(pool):
            return jnp.take(pool, tables, axis=0).reshape(
                (tables.shape[0], -1) + pool.shape[2:])
        views = [(view(kp), view(vp)) for kp, vp in caches]
        toks = []
        for _ in range(engine.decode_block):
            x = _embed_ids(stem, params, tok)
            if pos_emb is not None:
                x = x + jnp.take(params[pos_emb.name]["table"], pos,
                                 axis=0, mode="clip")
            for i, blk in enumerate(blocks):
                def row(x_row, ck, cv, pos_row, blk=blk):
                    y, ck, cv = _block_step(
                        blk, params[blk.name], x_row[None, None, :],
                        ck[None], cv[None], pos_row)
                    return y[0, 0], ck[0], cv[0]
                x, ck, cv = jax.vmap(row)(x, *views[i], pos)
                views[i] = (ck, cv)
            logits = _head_logits(head, params, x, prec)
            keys2, subs = _split_rows(keys)
            keys = jnp.where(mask[:, None] > 0, keys2, keys)
            samp = jax.vmap(jax.random.categorical)(
                subs, logits / jnp.maximum(temp, _TEMP_EPS)[:, None])
            nxt = jnp.where(temp > 0, samp.astype(jnp.int32),
                            jnp.argmax(logits, axis=-1).astype(jnp.int32))
            tok = jnp.where(mask > 0, nxt, tok)
            pos = pos + (mask > 0)
            toks.append(tok)
        keep = (mask[:, None] > 0) & (
            jnp.arange(tables.shape[1])[None, :] >= shared[:, None])
        wtab = jnp.where(keep, tables, 0).reshape(-1)
        out = tuple(
            tuple(pool.at[wtab].set(v.reshape((-1,) + pool.shape[1:]))
                  for pool, v in zip(pools, vws))
            for pools, vws in zip(caches, views))
        return jnp.stack(toks), keys, out
    return jax.jit(step)


@pytest.mark.parametrize("decode_block,ticks", [(1, 5), (4, 2), (2, 4)])
def test_float_step_pool_equals_whole_view_oracle(served, decode_block,
                                                  ticks):
    """The float decode step writes ONE row a slot and iteration; the
    pool it leaves must be, page for page and bit for bit, the pool the
    whole-view write-back left — over several ticks (each tick gathers
    what the last one stored), with half the batch masked out, slots
    holding shared pages, a row landing inside a shared page and a row
    past the view's end. The sink page (0) is the one page whose
    content is nobody's. From the second tick on the step takes its
    tokens from what it gave in the call before (the host's row says
    -1), as :meth:`_decode` runs it; the oracle is handed them by the
    host."""
    import jax.numpy as jnp
    _, wf, _ = served
    engine, params, caches, st = _step_fixture(wf, decode_block)
    before = [[numpy.asarray(a) for a in pool] for pool in caches]
    new, old = engine._build_decode(), _whole_view_step(engine)
    got = want = caches
    tok_g, tok_w = st["tok"], st["tok"]
    last_g = jnp.zeros((decode_block, len(_STEP_SLOTS)), jnp.int32)
    keys_g = keys_w = st["keys"]
    pos = st["pos"].copy()
    rest = (st["temp"], st["mask"], st["tables"], st["shared"])
    for _tick in range(ticks):
        toks_w, keys_w, want = old(params, tok_w, pos, *rest, keys_w,
                                   want)
        # THE step donates its keys and pool: hand it copies
        toks_g, keys_g, got = new(
            params, tok_g, pos, *rest, last_g, keys_g + 0,
            tuple(tuple(a + 0 for a in pool) for pool in got))
        numpy.testing.assert_array_equal(numpy.asarray(toks_g),
                                         numpy.asarray(toks_w))
        numpy.testing.assert_array_equal(numpy.asarray(keys_g),
                                         numpy.asarray(keys_w))
        tok_w = numpy.asarray(toks_w)[-1]
        last_g, tok_g = toks_g, numpy.full_like(tok_w, -1)
        pos = pos + decode_block * (st["mask"] > 0)
    frozen = sorted({p for m, _, pages, _ in _STEP_SLOTS if not m
                     for p in pages if p} | {5} | set(range(17, 25)))
    wrote = 0
    for pool_g, pool_w, pool_b in zip(got, want, before):
        for a_g, a_w, a_b in zip(pool_g, pool_w, pool_b):
            a_g = numpy.asarray(a_g)
            numpy.testing.assert_array_equal(a_g[1:],
                                             numpy.asarray(a_w)[1:])
            numpy.testing.assert_array_equal(a_g[frozen], a_b[frozen])
            wrote += int((a_g[1:] != a_b[1:]).any(axis=(2, 3)).sum())
    # and the rows did land: every in-view position a masked-in slot
    # wrote outside its shared pages, per block and tensor
    live = sum(1 for m, sh, _, p0 in _STEP_SLOTS if m
               for p in range(p0, p0 + decode_block * ticks)
               if sh * 8 <= p < 32)
    assert wrote == live * len(before) * 2


@pytest.mark.parametrize("decode_block", [1, 4])
def test_float_step_scatters_rows_not_views(served, decode_block):
    """No scatter of the lowered decode step updates more than
    ``decode_block x S x kv x hd`` elements (the rows one chunk made,
    per block and tensor) — the whole-view write-back was 32 x that on
    this engine and 2,048 x on the decode cell — and the row scatter
    sits under the ``page_writeback`` scope, which
    ``scope_ms.page_writeback`` reads."""
    import jax
    _, wf, _ = served
    engine, params, caches, st = _step_fixture(wf, decode_block)
    step = engine._build_decode()
    args = (params, st["tok"], st["pos"], st["temp"], st["mask"],
            st["tables"], st["shared"],
            numpy.zeros((decode_block, len(_STEP_SLOTS)), numpy.int32),
            st["keys"], caches)
    kp = caches[0][0]
    limit = decode_block * len(_STEP_SLOTS) * kp.shape[2] * kp.shape[3]

    def scatters(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("scatter"):
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scatters(sub)

    found = list(scatters(jax.make_jaxpr(step)(*args).jaxpr))
    sizes = [int(numpy.prod(e.invars[2].aval.shape)) for e in found]
    assert sizes and max(sizes) <= limit, (sizes, limit)
    # one row scatter per block and tensor into the pool itself
    into_pool = [e for e in found
                 if e.invars[0].aval.shape == kp.shape]
    assert len(into_pool) == 2 * len(caches)
    lowered = step.lower(*args)
    assert "page_writeback/scatter" in lowered.as_text(debug_info=True)
    assert re.search(r'op_name="[^"]*page_writeback[^"]*"',
                     lowered.compile().as_text())


# -- a step's tokens reach the streams under the next dispatch ------------------

class _Blocked:
    """A program's first result that keeps ``numpy.asarray`` waiting
    until the test lets go: while it waits, the dispatch is in flight
    and the tick thread is where it would sleep for the device."""

    def __init__(self, value, in_flight):
        self.value, self.release = value, threading.Event()
        self.in_flight = in_flight

    def __array__(self, dtype=None, copy=None):
        self.in_flight.put(self)
        assert self.release.wait(60)
        return numpy.asarray(self.value)


def _drain(ticket):
    """What a handler would have read by now: (tokens, terminal?)."""
    tokens, ended = [], False
    while True:
        try:
            item = ticket._stream_q.get_nowait()
        except queue.Empty:
            return tokens, ended
        if item is None:
            ended = True
        else:
            assert not ended, "tokens behind the terminal"
            tokens.extend(item)


@pytest.mark.parametrize("plane", ["float32", "int8", "speculative"])
def test_step_tokens_reach_streams_under_the_next_dispatch(pooled, plane):
    """Step n's tokens are not in the streams' queues when step n+1 is
    dispatched, and are there while it is in flight: the handlers write
    while the tick thread waits for the device."""
    lm, wf, draft, pool_engine = pooled
    spec = plane == "speculative"
    engine = ContinuousEngine(
        wf, max_slots=2, buckets=(8, 16), max_context=48, page_size=8,
        quant_kv=plane == "int8", spec_gamma=3,
        draft=draft if spec else None, name="eng_push_" + plane)
    mode = "speculative" if spec else "greedy"
    reqs = [make_request(_prompt(lm, 300 + i, 6 + i), 16, mode=mode,
                         gamma=3) for i in range(2)]
    from veles_tpu.nn import sampling
    expected = (pool_engine.serve([dict(r) for r in reqs]) if spec else
                [sampling.generate(wf, r["prompt"], 16, temperature=0)
                 for r in reqs])
    tickets = [Ticket(stream=True, mode=mode) for _ in reqs]
    for req, ticket in zip(reqs, tickets):
        assert engine.submit(req, ticket)
    in_flight, at_dispatch = queue.Queue(), []
    kind = "spec" if spec else "step"
    build = engine._program

    def program(which, bucket=None):
        real = build(which, bucket)
        if which != kind:
            return real

        def blocked(*args):
            at_dispatch.append([t._stream_q.qsize() for t in tickets])
            # the step's tokens go back into it as they were returned
            out = real(*(a.value if isinstance(a, _Blocked) else a
                         for a in args))
            return (_Blocked(out[0], in_flight),) + tuple(out[1:])
        return blocked
    engine._program = program
    engine.start()
    try:
        for n in range(1, 5):
            gate = in_flight.get(timeout=120)
            now = [t._stream_q.qsize() for t in tickets]
            # prefill's first token at once; then one event a step,
            # each handed over under the dispatch after its own
            assert at_dispatch[-1] == [max(1, n - 1)] * 2, (n, at_dispatch)
            assert now == [n] * 2, (n, now)
            assert engine.token_pushes == 2 * (n - 1)
            assert engine.token_pushes_overlapped == 2 * (n - 1)
            gate.release.set()
        while not all(t.event.is_set() for t in tickets):
            try:
                in_flight.get(timeout=0.05).release.set()
            except queue.Empty:
                pass
    finally:
        engine.stop()
    for ticket, answer in zip(tickets, expected):
        assert ticket.error is None
        got, ended = _drain(ticket)
        assert ended and got == ticket.result["tokens"] == answer


def _mid_decode(engine, ticket, at_least=3):
    """Tick the never-started engine until ``ticket``'s row has made
    ``at_least`` tokens and its last step's are still kept."""
    for _ in range(200):
        engine._tick()
        active = engine.scheduler.active()
        if active and len(active[0].tokens) >= at_least and engine._held:
            return active[0]
    raise AssertionError("the row did not get going")


@pytest.mark.parametrize("ending", ["idle", "abort", "handoff",
                                    "decode_fault", "stop"])
def test_nothing_is_held_at_an_ending(served, monkeypatch, ending):
    """Wherever no dispatch follows or a terminal is set, the step in
    flight is read and what was kept is pushed first: the stream ends
    with all its tokens, or with an error payload whose ``resume``
    holds exactly what it streamed, the step in flight's token too."""
    lm, wf, _ = served
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8, 16),
                              max_context=48, name="eng_end_" + ending)
    ticket = Ticket(stream=True)
    assert engine.submit(make_request(_prompt(lm, 320, 7), 12), ticket)
    slot = _mid_decode(engine, ticket)
    made = list(slot.tokens)
    before, ended = _drain(ticket)
    assert not ended and before == made[:-1]     # the last step's: kept
    if ending == "idle":
        for _ in range(40):
            engine._tick()
        assert ticket.error is None
        made = ticket.result["tokens"]
        assert len(made) == 12
    elif ending == "abort":
        engine._abort_active("internal serving error", code=500)
    elif ending == "handoff":
        done = threading.Event()
        engine._handoff = ("draining", done, {"count": 0})
        engine._tick()
        assert done.is_set()
    elif ending == "decode_fault":
        monkeypatch.setenv("VELES_FAULTS",
                           "serve.decode_step:raise:times=1")
        engine._tick()
        monkeypatch.setenv("VELES_FAULTS", "")
    else:
        engine.stop()
    assert ticket.event.is_set() and engine._held == []
    assert engine._flying is None
    if ending != "idle":
        # one step was in flight beyond the one whose tokens were kept
        assert slot.tokens[:len(made)] == made
        assert len(slot.tokens) == len(made) + 1
        made = list(slot.tokens)
    after, ended = _drain(ticket)
    assert ended and before + after == made
    if ending != "idle":
        assert ticket.error_payload()["resume"] == {
            "tokens": made, "tokens_done": len(made)}
    engine.stop()


def test_preempted_row_still_gets_its_last_steps_tokens(served):
    """QoS preempts a batch row with a step in flight: that step is
    read, and its tokens and the kept ones reach the stream before the
    ticket goes back to the queue; the whole stream is the
    uninterrupted answer, each token once."""
    from veles_tpu.config import root
    from veles_tpu.nn import sampling
    lm, wf, _ = served
    root.common.serving.qos = True
    try:
        engine = ContinuousEngine(wf, max_slots=1, buckets=(8, 24),
                                  max_context=48, name="eng_push_qos")
        req = make_request(_prompt(lm, 330, 6), 12, temperature=0.9,
                           seed=5, mode="sample")
        req["priority"] = "batch"
        t_b, t_i = Ticket(stream=True, mode="sample"), Ticket()
        assert engine.submit(req, t_b)
        slot = _mid_decode(engine, t_b)
        made = list(slot.tokens)
        got, _ = _drain(t_b)
        assert got == made[:-1]
        urgent = make_request(_prompt(lm, 331, 5), 3)
        urgent["priority"] = "interactive"
        assert engine.submit(urgent, t_i)
        engine._tick()
        assert engine.preemptions == 1 and not t_b.event.is_set()
        got += _drain(t_b)[0]
        assert len(slot.tokens) == len(made) + 1    # the step in flight
        assert slot.tokens[:len(made)] == made
        assert got == slot.tokens == t_b.progress
        for _ in range(200):
            if t_b.event.is_set() and t_i.event.is_set():
                break
            engine._tick()
        assert t_b.error is None and t_i.error is None
        tail, ended = _drain(t_b)
        # the resumed attempt streams its own first token onward
        assert ended and got + tail == t_b.result["tokens"]
        assert t_b.result["tokens"] == sampling.generate(
            wf, req["prompt"], 12, temperature=0.9, seed=5)
        engine.stop()
    finally:
        root.common.serving.qos = False


def _sse(url, payload, timeout=120.0):
    """One streamed request's events, in wire order."""
    req = urllib.request.Request(
        url, data=json.dumps(dict(payload, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return [json.loads(line[5:]) for line in r
                if line.startswith(b"data:")]


def test_push_overlap_share_on_a_live_run(served, api_served):
    """Six streams of 24 tokens: nearly every event of decode-step
    tokens is queued while a dispatch is in flight (a finishing row's
    is not), the two counters are on /metrics and their ratio on
    /stats."""
    lm, wf, api, url = api_served
    before = counters.snapshot()
    answers = {}

    def ask(i):
        answers[i] = _sse(url, {"prompt": _prompt(lm, 340 + i, 6 + i),
                                "n_new": 24})
    threads = [threading.Thread(target=ask, args=(i,), daemon=True)
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    for events in answers.values():
        assert events[-1]["done"] and len(events[-1]["tokens"]) == 24
        assert [t for e in events[:-1] for t in e["tokens"]] \
            == events[-1]["tokens"]
    delta = counters.delta(before)
    pushes = delta["veles_serving_token_pushes_total"]
    overlapped = delta["veles_serving_token_pushes_overlapped_total"]
    assert pushes == 6 * 23                 # the 24th is the prefill's
    assert overlapped >= 0.9 * pushes, (overlapped, pushes)
    with urllib.request.urlopen(url + "/stats", timeout=30) as r:
        share = json.loads(r.read())["continuous"]["push_overlap_share"]
    assert 0.9 <= share <= 1.0
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % api.port, timeout=30) as r:
        text = r.read().decode()
    for name in ("veles_serving_token_pushes_total",
                 "veles_serving_token_pushes_overlapped_total"):
        assert re.search(r"^%s \d+" % name, text, re.M), name


# -- the decode step's view ladder -----------------------------------------------

@pytest.mark.parametrize("pages_per_slot,page_size,min_bucket,ladder", [
    (128, 16, 128, (128, 64)),            # the decode cell
    (16, 4, 8, (16, 8)),
    (64, 16, 512, (64, 32)),              # the half is the bucket
    (6, 8, 8, (6, 3)),
    (3, 16, 8, (3,)),                     # no whole pages: a ladder of one
    (4, 16, 64, (4,)),                    # 32 positions < a bucket of 64
    (64, 16, 1024, (64,)),
    (1, 16, 8, (1,)),
])
def test_view_ladder_geometry(pages_per_slot, page_size, min_bucket,
                              ladder):
    from veles_tpu.serving.pages import view_ladder
    assert view_ladder(pages_per_slot, page_size, min_bucket) == ladder


@pytest.mark.parametrize("knobs,ladder", [
    (dict(buckets=(8, 16), max_context=64, page_size=4), (16, 8)),
    (dict(buckets=(8, 16), max_context=48), (3,)),
    (dict(buckets=(16, 32), max_context=64, page_size=8), (8, 4)),
    (dict(buckets=(32,), max_context=48, page_size=8), (6,)),
])
def test_engine_ladder_and_programs_bound(served, knobs, ladder):
    """The ladder follows from ``max_context``, ``page_size`` and
    ``buckets``; ``programs_bound()`` holds a step program a rung and
    is a constant of the engine."""
    _, wf, _ = served
    engine = ContinuousEngine(wf, max_slots=2, name="eng_geom", **knobs)
    assert engine.view_ladder == ladder
    assert engine.programs_bound() == len(engine.buckets) + len(ladder)
    assert engine.stats()["view_share"] == 1.0     # nothing dispatched


@pytest.mark.parametrize("seed", range(4))
def test_view_rung_is_the_shortest_that_holds_every_row(seed):
    """Over random geometries and random active rows: the rung chosen
    is never shorter than any row's need, and no shorter rung of the
    ladder would do."""
    from veles_tpu.serving.pages import pages_for, view_ladder, view_rung
    rng = numpy.random.RandomState(seed)
    for _ in range(300):
        size = int(rng.choice([1, 4, 8, 16]))
        context = int(rng.randint(size, 64 * size + 1))
        per_slot = pages_for(context, size)
        ladder = view_ladder(per_slot, size,
                             int(rng.randint(1, context + 1)))
        assert ladder[0] == per_slot and len(ladder) <= 2
        block = int(rng.choice([d for d in (1, 2, 4) if size % d == 0]))
        needs = [min(int(rng.randint(1, context + 1)),
                     int(rng.randint(0, context)) + block)
                 for _ in range(rng.randint(1, 9))]
        rung = view_rung(ladder, max(needs), size)
        assert rung in ladder
        assert all(rung * size >= n for n in needs)
        assert not any(r < rung and r * size >= max(needs)
                       for r in ladder)


@pytest.mark.parametrize("decode_block", [1, 4])
def test_ladder_serves_what_the_whole_view_serves(served, decode_block):
    """Token for token, greedy and sampled, an engine with the ladder
    serves what the same engine held to the whole view serves (and the
    scan decoder): a shorter view drops only positions that the causal
    mask weighs nought."""
    import ladder_drill
    from veles_tpu.nn import sampling
    lm, wf, _ = served
    ladder, whole = ladder_drill.twins(wf, "eng_blk%d" % decode_block,
                                       decode_block=decode_block)
    assert ladder.view_ladder == (16, 8)
    seen = ladder_drill.record_rungs(ladder)
    reqs = ladder_drill.requests(lambda seed, n: _prompt(lm, seed, n))
    before = counters.snapshot()
    got = ladder_drill.serve_by_ticks(ladder, reqs)
    delta = counters.delta(before)
    assert got == ladder_drill.serve_by_ticks(whole, reqs)
    for req, toks in zip(reqs, got):
        assert toks == sampling.generate(
            wf, req["prompt"], req["n_new"],
            temperature=req["temperature"], seed=req["seed"])
    ladder_drill.assert_shortest_rungs(ladder, seen)
    assert {pages for pages, _ in seen} == {8, 16}
    if decode_block == 4:
        # a chunk that ends exactly at its rung's last position
        assert any(need == pages * 4 for pages, need in seen)
    assert delta["veles_serving_view_positions_total"] == \
        4 * sum(pages for pages, _ in seen)
    assert delta["veles_serving_decode_dispatches_total"] == len(seen)
    assert whole.stats()["view_share"] == 1.0
    assert sorted(k for k in whole._progs if k[0] == "step") == \
        [("step", 16)]
    assert ladder.page_pool.in_use() == whole.page_pool.in_use() == 0


def test_ladder_with_prefix_shared_leading_pages(served):
    """Two rows that adopted the same leading pages read them through
    every rung: the shared pages are the table's first entries, which
    every rung keeps."""
    import ladder_drill
    from veles_tpu.nn import sampling
    lm, wf, _ = served
    ladder, whole = ladder_drill.twins(wf, "eng_pfx", prefix_cache=True)
    seen = ladder_drill.record_rungs(ladder)
    stem = _prompt(lm, 410, 12)
    first = [make_request(stem + _prompt(lm, 411, 3), 4, seed=1)]
    reqs = [make_request(stem + _prompt(lm, 412, 4), 40, seed=2),
            make_request(stem + _prompt(lm, 413, 2), 9, temperature=0.8,
                         seed=3)]
    for engine in (ladder, whole):
        ladder_drill.serve_by_ticks(engine, first)   # fills the index
    got = ladder_drill.serve_by_ticks(ladder, reqs)
    assert ladder.prefix_requests >= 2
    assert got == ladder_drill.serve_by_ticks(whole, reqs)
    for req, toks in zip(reqs, got):
        assert toks == sampling.generate(
            wf, req["prompt"], req["n_new"],
            temperature=req["temperature"], seed=req["seed"])
    ladder_drill.assert_shortest_rungs(ladder, seen)


@pytest.mark.parametrize("mode,temp", [("greedy", 0.0), ("sample", 0.9)])
def test_ladder_after_a_preemption_and_resume(served, mode, temp):
    """A batch row preempted beyond the first rung comes back through
    a longer prefill bucket and goes on at the rung its position asks
    for; the interactive row between runs at the shorter."""
    import ladder_drill
    from veles_tpu.config import root
    from veles_tpu.nn import sampling
    lm, wf, _ = served
    root.common.serving.qos = True
    try:
        engine, _ = ladder_drill.twins(wf, "eng_qos_" + mode, max_slots=1,
                                       buckets=(8, 48))
        seen = ladder_drill.record_rungs(engine)
        req = make_request(_prompt(lm, 420, 6), 50, temperature=temp,
                           seed=7, mode=mode)
        req["priority"] = "batch"
        urgent = make_request(_prompt(lm, 421, 5), 3)
        urgent["priority"] = "interactive"
        t_b, t_i = Ticket(), Ticket()
        assert engine.submit(req, t_b)
        ladder_drill.tick_until(
            engine, lambda: any(len(s.tokens) >= 30
                                for s in engine.scheduler.active()))
        assert seen[-1][0] == 16                # beyond the first rung
        at = len(seen)
        assert engine.submit(urgent, t_i)
        ladder_drill.tick_until(
            engine, lambda: t_b.event.is_set() and t_i.event.is_set())
        assert engine.preemptions == 1
        assert t_b.error is None and t_i.error is None
        assert seen[at][0] == 8                 # the interactive row's
        assert t_b.result["tokens"] == sampling.generate(
            wf, req["prompt"], 50, temperature=temp, seed=7)
        assert t_i.result["tokens"] == sampling.generate(
            wf, urgent["prompt"], 3, temperature=0)
        ladder_drill.assert_shortest_rungs(engine, seen)
        assert engine.page_pool.in_use() == 0
    finally:
        root.common.serving.qos = False


def test_view_share_on_stats_and_metrics(served, api_served):
    """``view_share`` is on ``/generate/stats`` (1.0 on this engine,
    whose ladder has one rung) and the counter on ``/metrics``."""
    lm, wf, api, url = api_served
    _post(url, {"prompt": _prompt(lm, 430, 6), "n_new": 4})
    with urllib.request.urlopen(url + "/stats", timeout=30) as r:
        stats = json.loads(r.read())["continuous"]
    assert api._engine.view_ladder == (api._engine.pages_per_slot,)
    assert stats["view_share"] == 1.0
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % api.port, timeout=30) as r:
        text = r.read().decode()
    found = re.search(r"^veles_serving_view_positions_total (\d+)", text,
                      re.M)
    assert found and int(found.group(1)) > 0


# -- step n+1 is dispatched before step n's tokens are read ----------------------

def _assert_solo(wf, reqs, got):
    import ahead_drill
    for req, toks in zip(reqs, got):
        assert toks == ahead_drill.solo(wf, req), req


@pytest.mark.parametrize("decode_block", [1, 4])
def test_ahead_serves_what_the_serial_order_serves(served, decode_block):
    """Greedy and sampled rows, one that ends by length while the others
    go on, slots used again: the engine gives, token for token, what its
    twin held to the serial order gives and what the scan decoder
    gives; nearly every step of it was dispatched with the one before
    unread, none of the twin's was, and the counters say so."""
    import ahead_drill
    import ladder_drill
    lm, wf, _ = served
    ahead, serial = ahead_drill.twins(wf, "eng_ah%d" % decode_block,
                                      decode_block=decode_block)
    events, events_s = (ahead_drill.record_order(e)
                        for e in (ahead, serial))
    reqs = ladder_drill.requests(lambda seed, n: _prompt(lm, seed, n))
    before = counters.snapshot()
    got = ahead_drill.serve_by_ticks(ahead, reqs)
    delta = counters.delta(before)
    assert got == ahead_drill.serve_by_ticks(serial, reqs)
    _assert_solo(wf, reqs, got)
    steps = sum(1 for e in events if e[0] == "dispatch")
    ran_ahead = ahead_drill.ahead_of(events)
    assert ahead_drill.ahead_of(events_s) == 0
    # only a round's first step (nothing before it) and the step after
    # a tick that had to drain have nothing unread before them
    assert 0.8 * steps <= ran_ahead < steps
    assert ahead.steps_ahead == ran_ahead and serial.steps_ahead == 0
    assert delta["veles_serving_steps_ahead_total"] == ran_ahead
    assert delta["veles_serving_decode_dispatches_total"] == steps
    assert ahead.stats()["steps_ahead_share"] == round(ran_ahead / steps, 4)
    assert serial.stats()["steps_ahead_share"] == 0.0
    # no row ends on an eos_id here: the same row-steps in both orders
    assert ahead_drill.row_steps(events, decode_block) == \
        ahead_drill.row_steps(events_s, decode_block)
    assert ahead._flying is None and ahead._held == []
    assert ahead.programs_built <= ahead.programs_bound()
    assert ahead.page_pool.in_use() == serial.page_pool.in_use() == 0


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("decode_block", [1, 4])
def test_ahead_row_that_ends_on_eos_and_its_slot_used_again(
        served, decode_block, temp):
    """A row ends on its ``eos_id`` with its next step already in
    flight: that row-step is dropped (the one row-step more than the
    serial order runs), and the request admitted next, into the very
    slot and pages, gives its own tokens, as does the co-tenant that
    went on through it all."""
    import ahead_drill
    from veles_tpu.nn import sampling
    lm, wf, _ = served
    ender, short = ahead_drill.ender(
        lambda req: ahead_drill.solo(wf, req),
        lambda i: _prompt(lm, 440 + 10 * i, 6), 20, temp, seed=11)
    assert 3 < len(short) < 20
    reqs = [ender,
            make_request(_prompt(lm, 441, 5), 30, temperature=0.7, seed=12),
            make_request(_prompt(lm, 442, 7), 10, temperature=temp,
                         seed=13)]
    dropped = {}
    for engine in ahead_drill.twins(
            wf, "eng_eos%d%d" % (decode_block, temp > 0), max_slots=2,
            pages=16, decode_block=decode_block):
        events = ahead_drill.record_order(engine)
        tickets = ahead_drill.submit_all(engine, reqs)
        engine._tick()
        first = {s.ticket: (s.idx, set(s.pages))
                 for s in engine.scheduler.active()}
        assert set(first) == set(tickets[:2])       # the third waits
        ahead_drill.tick_until(
            engine, lambda: tickets[0].event.is_set() and len(
                engine.scheduler.active()) == 2)
        (later,) = [s for s in engine.scheduler.active()
                    if s.ticket is tickets[2]]
        idx, pages = first[tickets[0]]
        assert later.idx == idx and set(later.pages) <= pages
        ahead_drill.tick_until(
            engine, lambda: all(t.event.is_set() for t in tickets))
        got = [t.result["tokens"] for t in tickets]
        assert got[0] == short
        _assert_solo(wf, reqs, got)
        ahead_drill.ahead_of(events)
        dropped[engine.name] = (
            ahead_drill.row_steps(events, decode_block)
            - sum(-(-(len(toks) - 1) // decode_block) * decode_block
                  for toks in got))
        assert engine._flying is None
        assert engine.page_pool.in_use() == 0
    name = "eng_eos%d%d" % (decode_block, temp > 0)
    assert dropped[name + "_serial"] == 0
    assert dropped[name + "_ahead"] == decode_block


@pytest.mark.parametrize("ending", ["handoff", "abort", "replica_death",
                                    "decode_fault", "shed", "stop"])
def test_ahead_an_ending_reads_the_step_in_flight(served, monkeypatch,
                                                  ending):
    """With a step in flight, a hand-off, an abort, a shed and ``stop``
    read it first: the error payload's ``resume`` holds every token the
    row was ever dispatched for, they are the scan decoder's, and the
    twin held to the serial order answers the same."""
    import ahead_drill
    lm, wf, _ = served
    reqs = [make_request(_prompt(lm, 450, 6), 24, temperature=0.8, seed=21),
            make_request(_prompt(lm, 451, 5), 24, seed=22)]
    answers = []
    for engine in ahead_drill.twins(wf, "eng_end2_" + ending, max_slots=2):
        tickets = ahead_drill.submit_all(engine, reqs, stream=True)
        ahead_drill.tick_until(
            engine, lambda: all(len(s.tokens) >= 6
                                for s in engine.scheduler.active())
            and len(engine.scheduler.active()) == 2)
        slots = {s.ticket: s for s in engine.scheduler.active()}
        recorded = [len(slots[t].tokens) for t in tickets]
        in_flight = engine._flying is not None
        if ending == "handoff":
            done = threading.Event()
            engine._handoff = ("draining", done, {"count": 0})
            engine._tick()
            assert done.is_set()
        elif ending == "abort":
            engine._abort_active("internal serving error", code=500)
        elif ending == "replica_death":
            monkeypatch.setenv("VELES_FAULTS",
                               "serve.replica_death:raise:times=1")
            engine._tick()
        elif ending == "decode_fault":
            monkeypatch.setenv("VELES_FAULTS",
                               "serve.decode_step:raise:times=1")
            engine._tick()
        elif ending == "shed":
            # the ledger has drifted: the rows hold no page beyond their
            # position, and the allocator refuses the next
            for s in slots.values():
                keep = -(-int(engine._pos[s.idx]) // engine.page_size)
                engine.page_pool.free(s.pages[keep:])
                del s.pages[keep:]
            monkeypatch.setenv("VELES_FAULTS", "serve.page_alloc:raise")
            ahead_drill.tick_until(
                engine, lambda: all(t.event.is_set() for t in tickets))
        else:
            engine.stop()
        monkeypatch.setenv("VELES_FAULTS", "")
        assert engine._flying is None and engine._held == []
        for t, req, had in zip(tickets, reqs, recorded):
            assert t.event.is_set() and t.code in (500, 503)
            resume = t.error_payload()["resume"]["tokens"]
            assert resume == ahead_drill.solo(wf, req)[:len(resume)]
            streamed, ended = _drain(t)
            assert ended and streamed == resume
            if ending != "shed":
                # the step in flight, and no more, beyond what was
                # recorded when the ending came
                assert len(resume) == had + in_flight
        answers.append([t.error_payload()["resume"]["tokens"]
                        for t in tickets])
        assert engine.page_pool.in_use() == 0
        engine.stop()
    if ending != "shed":
        assert in_flight is False       # the twin's, the last one built
        # both were ticked until six tokens were recorded: the engine's
        # answer is the twin's and the step it had in flight
        for mine, twin in zip(*answers):
            assert mine[:-1] == twin


@pytest.mark.parametrize("mode,temp", [("greedy", 0.0), ("sample", 0.9)])
def test_ahead_a_preemption_and_resume(served, mode, temp):
    """QoS preempts a batch row whose next step is in flight: it is read
    first, the row comes back through a longer prefill and goes on; both
    requests' tokens are the scan decoder's, in either order of the
    tick."""
    import ahead_drill
    from veles_tpu.config import root
    lm, wf, _ = served
    root.common.serving.qos = True
    try:
        req = make_request(_prompt(lm, 460, 6), 30, temperature=temp,
                           seed=7, mode=mode)
        req["priority"] = "batch"
        urgent = make_request(_prompt(lm, 461, 5), 3)
        urgent["priority"] = "interactive"
        for engine in ahead_drill.twins(wf, "eng_ahq_" + mode, max_slots=1,
                                        buckets=(8, 48)):
            events = ahead_drill.record_order(engine)
            (t_b,) = ahead_drill.submit_all(engine, [req])
            ahead_drill.tick_until(
                engine, lambda: any(len(s.tokens) >= 12
                                    for s in engine.scheduler.active()))
            flying = engine._flying is not None
            (t_i,) = ahead_drill.submit_all(engine, [urgent])
            engine._tick()
            assert engine.preemptions == 1
            # the preempted progress holds the step that was in flight
            assert len(t_b.progress) == 12 + flying
            ahead_drill.tick_until(
                engine, lambda: t_b.event.is_set() and t_i.event.is_set())
            assert t_b.error is None and t_i.error is None
            _assert_solo(wf, [req, urgent], [t_b.result["tokens"],
                                             t_i.result["tokens"]])
            ahead_drill.ahead_of(events)
            assert engine.page_pool.in_use() == 0
        assert not flying                       # the twin's
    finally:
        root.common.serving.qos = False


def test_ahead_a_change_of_weights_waits_for_the_step_in_flight(served):
    """A row ends on its ``eos_id`` and leaves the pool idle with its
    dropped step in flight; the weights change; the next request is
    served on the new weights, by either order of the tick, and the step
    in flight was read before they were taken."""
    import ahead_drill
    from veles_tpu.nn import sampling
    lm, wf, _ = served
    ender, short = ahead_drill.ender(
        lambda req: ahead_drill.solo(wf, req),
        lambda i: _prompt(lm, 470 + 10 * i, 6), 16, seed=31)
    after = make_request(_prompt(lm, 471, 7), 8, temperature=0.8, seed=32)
    head = wf.forwards[-1]
    bias = head.param_arrays()["bias"]
    kept = numpy.array(bias.mem)
    want = {}
    try:
        for engine in ahead_drill.twins(wf, "eng_swap", max_slots=2):
            events = ahead_drill.record_order(engine)
            assert ahead_drill.serve_by_ticks(engine, [ender]) == [short]
            flying = engine._flying is not None
            bias.map_write()
            bias.mem[...] = kept + numpy.linspace(-2, 2, kept.size).astype(
                kept.dtype).reshape(kept.shape)
            wf._sampler_cache = {}
            want[engine.name] = ahead_drill.solo(wf, after)
            n_events = len(events)
            got = ahead_drill.serve_by_ticks(engine, [after])
            assert got == [want[engine.name]]
            if flying:
                # read before the first dispatch on the new weights
                assert events[n_events][0] == "read"
            ahead_drill.ahead_of(events)
            bias.map_write()
            bias.mem[...] = kept
            wf._sampler_cache = {}
        assert want["eng_swap_ahead"] == want["eng_swap_serial"]
        assert want["eng_swap_ahead"] != sampling.generate(
            wf, after["prompt"], 8, temperature=0.8, seed=32)
    finally:
        bias.map_write()
        bias.mem[...] = kept
        wf._sampler_cache = {}


def test_ahead_with_prefix_shared_leading_pages(served):
    """Rows that adopted the same leading pages, one of them ending on
    an ``eos_id`` with its next step in flight: the dropped row-step
    writes no shared page (the cached blocks still serve the next
    request its exact tokens) and all tokens are the scan decoder's."""
    import ahead_drill
    from veles_tpu.nn import sampling
    lm, wf, _ = served
    stem = _prompt(lm, 480, 12)
    first = [make_request(stem + _prompt(lm, 481, 3), 4, seed=1)]
    ender, short = ahead_drill.ender(
        lambda req: ahead_drill.solo(wf, req),
        lambda i: stem + _prompt(lm, 482 + 10 * i, 2), 14, 0.8, seed=3)
    reqs = [make_request(stem + _prompt(lm, 483, 4), 30, seed=2), ender]
    last = [make_request(stem + _prompt(lm, 484, 3), 6, temperature=0.6,
                         seed=4)]
    results = []
    for engine in ahead_drill.twins(wf, "eng_ahpfx", prefix_cache=True):
        events = ahead_drill.record_order(engine)
        ahead_drill.serve_by_ticks(engine, first)     # fills the index
        cached = numpy.array(sorted(engine.prefix_cache.cached_pages()))
        held = [[numpy.array(a[cached]) for a in pool]
                for pool in engine._caches]
        got = ahead_drill.serve_by_ticks(engine, reqs)
        assert engine.prefix_requests >= 2 and got[1] == short
        got += ahead_drill.serve_by_ticks(engine, last)
        _assert_solo(wf, reqs + last, got)
        for pool, before in zip(engine._caches, held):
            for a, b in zip(pool, before):
                numpy.testing.assert_array_equal(numpy.asarray(a)[cached], b)
        results.append((got, ahead_drill.ahead_of(events)))
        engine.stop()
    assert results[0][0] == results[1][0]
    assert results[0][1] > 0 and results[1][1] == 0


@pytest.mark.parametrize("pool", ["plain", "speculative_beside"])
def test_step_dispatch_precedes_the_read_of_the_step_before(pooled, pool):
    """On a started engine with streaming clients: a plain tick
    dispatches step n+1 and only then reads step n's tokens; a pool that
    also holds a speculative row reads every plain step before anything
    else is dispatched. Either way each stream is first token, each
    step's tokens in order, terminal; and ``steps_ahead_share`` is what
    the recorded order implies."""
    import ahead_drill
    from veles_tpu.nn import sampling
    lm, wf, draft, pool_engine = pooled
    spec = pool == "speculative_beside"
    engine = ContinuousEngine(
        wf, max_slots=3, buckets=(8, 16), max_context=48, page_size=8,
        spec_gamma=3, draft=draft if spec else None,
        name="eng_order_" + pool)
    reqs = [make_request(_prompt(lm, 490 + i, 6 + i), 14,
                         temperature=0.8 * (i % 2), seed=40 + i)
            for i in range(2)]
    tickets = [Ticket(stream=True) for _ in reqs]
    if spec:
        reqs.append(make_request(_prompt(lm, 495, 6), 14,
                                 mode="speculative", gamma=3))
        tickets.append(Ticket(stream=True, mode="speculative"))
    events = ahead_drill.record_order(engine)
    for req, ticket in zip(reqs, tickets):
        assert engine.submit(req, ticket)
    engine.start()
    try:
        for ticket in tickets:
            assert ticket.event.wait(120) and ticket.error is None
    finally:
        engine.stop()
    for req, ticket in zip(reqs[:2], tickets):
        got, ended = _drain(ticket)
        assert ended and got == ticket.result["tokens"]
        assert got == sampling.generate(
            wf, req["prompt"], 14, temperature=req["temperature"],
            seed=req["seed"])
    if spec:
        assert tickets[2].result["tokens"] == pool_engine.serve(
            [dict(reqs[2])])[0]
    steps = sum(1 for e in events if e[0] == "dispatch")
    ran_ahead = ahead_drill.ahead_of(events)
    assert steps >= 13
    if spec:
        # while the speculative row lives, each plain step is read
        # before the round is dispatched: none ahead but after it ended
        first = [i for i, e in enumerate(events) if e[0] == "dispatch"]
        assert all(events[i + 1] == ("read", events[i][1])
                   for i in first[:4])
        assert ran_ahead < steps - 4
    else:
        # dispatch 2, read 1, dispatch 3, read 2, ...
        assert [e[:2] for e in events[:5]] == [
            ("dispatch", 1), ("dispatch", 2), ("read", 1),
            ("dispatch", 3), ("read", 2)]
        assert ran_ahead == steps - 1
    assert engine.steps_ahead == ran_ahead
    assert engine.decode_dispatches >= steps    # the rounds among them
    assert engine.stats()["steps_ahead_share"] == round(
        ran_ahead / engine.decode_dispatches, 4)


def test_steps_ahead_share_on_stats_and_metrics(served, api_served):
    """Three streamed requests over HTTP: the counter on ``/metrics``
    and the share on ``/generate/stats`` rise by what the ticks
    imply (every plain step but a round's first ran ahead)."""
    lm, wf, api, url = api_served
    engine = api._engine
    before = counters.snapshot()
    dispatches0, ahead0 = engine.decode_dispatches, engine.steps_ahead
    answers = {}

    def ask(i):
        answers[i] = _sse(url, {"prompt": _prompt(lm, 500 + i, 6 + i),
                                "n_new": 20})
    threads = [threading.Thread(target=ask, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    for events in answers.values():
        assert events[-1]["done"] and len(events[-1]["tokens"]) == 20
        assert [t for e in events[:-1] for t in e["tokens"]] \
            == events[-1]["tokens"]
    delta = counters.delta(before)
    steps = delta["veles_serving_decode_dispatches_total"]
    ran_ahead = delta["veles_serving_steps_ahead_total"]
    assert steps == engine.decode_dispatches - dispatches0 >= 19
    assert ran_ahead == engine.steps_ahead - ahead0
    # a step is not ahead only when nothing was in flight before it: a
    # burst's first, and the one after a tick whose rows all ended
    assert steps - 4 <= ran_ahead < steps
    with urllib.request.urlopen(url + "/stats", timeout=30) as r:
        share = json.loads(r.read())["continuous"]["steps_ahead_share"]
    assert share == round(engine.steps_ahead / engine.decode_dispatches, 4)
    assert share > 0.5
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % api.port, timeout=30) as r:
        text = r.read().decode()
    found = re.search(r"^veles_serving_steps_ahead_total (\d+)", text, re.M)
    assert found and int(found.group(1)) >= ran_ahead
