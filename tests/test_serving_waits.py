"""The serving path's two waits, timed from inside (beside
tests/test_tick_spans.py, on the same tiny engine).

What is pinned here:
- the chip known to be unfed: a tick with an admission observes
  ``veles_serving_unfed_first_token_seconds`` once (the bucketed prefill
  and the chunked path's final chunk alike), plain ticks after it observe
  nothing and read no clock; a pool that is read every tick (a
  speculative row) observes ``veles_serving_unfed_drain_seconds`` once a
  tick; the loop's idle wait observes neither;
- ``veles_serving_prefill_positions_total`` rises by the bucket at an
  admission and by the chunk on the chunked path;
- a request's waits are observed where each ends, once a request: queue
  wait, TTFT, prefill wait and the first write of a streamed request
  BEFORE it ends; once across preempt, requeue and finish; a request shed
  in the queue observes its queue wait at the terminal and no TTFT.
"""
import json
import threading
import time
import urllib.request

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import prng
from veles_tpu.config import root
from veles_tpu.serving import ContinuousEngine
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import (SlotScheduler, Ticket,
                                         shed_expired)
from veles_tpu.telemetry import spans
from veles_tpu.telemetry.counters import counters, histograms

import ahead_drill
from conftest import import_model
from ladder_drill import tick_until

FIRST = "veles_serving_unfed_first_token_seconds"
DRAIN = "veles_serving_unfed_drain_seconds"
UNFED = (FIRST, DRAIN)
POSITIONS = "veles_serving_prefill_positions_total"
WAITS = ("veles_serving_queue_wait_seconds",
         "veles_serving_prefill_wait_seconds",
         "veles_serving_ttft_seconds",
         "veles_serving_first_write_seconds")
E2E = "veles_serving_e2e_seconds"

#: the engine's shapes here: 2 pages of 8 positions hold a prompt of 12
#: in the bucket of 16, and a chunk is a page
SHAPES = dict(max_slots=2, buckets=(8, 16), max_context=48, page_size=8)


def counts(names):
    return [histograms.count(n) for n in names]


def rise(names, before):
    return [now - was for now, was in zip(counts(names), before)]


@pytest.fixture(scope="module")
def lm_wf():
    lm = import_model("char_lm")
    prng.seed_all(983)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=1,
                           dim=32, n_train=128, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return lm, wf


@pytest.fixture(scope="module")
def prompt(lm_wf):
    lm, _ = lm_wf
    return [int(t) for t in lm.make_corpus(
        numpy.random.RandomState(71), 12)]


# -- the chip known to be unfed ------------------------------------------------

@pytest.mark.parametrize("knobs,prefill_ticks,positions", [
    ({}, 1, 16),                        # one prefill at the bucket
    ({"prefill_chunk": 8}, 2, 16),      # two chunks of a page
], ids=["bucketed", "chunked"])
def test_admission_tick_observes_first_token_once(
        lm_wf, prompt, monkeypatch, knobs, prefill_ticks, positions):
    _, wf = lm_wf
    engine = ContinuousEngine(wf, name="waits_admit", **dict(SHAPES,
                                                             **knobs))
    # every program built first, both rungs: building one is set-up, and the
    # interval that it would end is dropped, not observed
    cold = counts(UNFED)
    ahead_drill.serve_by_ticks(engine, [make_request(prompt, 30)])
    assert rise(UNFED, cold) == [0, 0]
    assert engine._unfed[1] == "drain"
    engine._unfed = None            # as the loop's idle wait does
    ticket = Ticket()
    assert engine.submit(make_request(prompt, 30), ticket)
    before = counts(UNFED)
    built, sent = engine.decode_dispatches, engine._dispatched
    sum0, pos0 = histograms.sum(FIRST), counters.get(POSITIONS)
    seen = spans.recorder.cursor()
    # the ticks that only prefill a chunk read nothing: nothing stamped
    for _ in range(prefill_ticks - 1):
        engine._tick()
        assert engine._unfed is None
    assert rise(UNFED, before) == [0, 0]
    t0 = time.perf_counter()
    engine._tick()          # the first token is read, then the step goes
    tick_s = time.perf_counter() - t0
    assert ticket.first_token is not None and engine._flying is not None
    assert rise(UNFED, before) == [1, 0]
    unfed = histograms.sum(FIRST) - sum0
    assert 0.0 < unfed < tick_s
    assert engine.stats()["unfed_s"] == {
        "first_token": pytest.approx(unfed, abs=1e-6), "drain": 0.0}
    assert counters.get(POSITIONS) - pos0 == positions
    records = [r for r in spans.recorder.records_since(seen)[0]
               if r["name"] == "serving.unfed"]
    assert [(r["cause"], r["depth"]) for r in records] == [
        ("first_token", 0)]
    assert records[0]["dur"] == pytest.approx(unfed)
    # the interval ends when the step's call has returned: inside it
    calls = [r for r in spans.recorder.records_since(seen)[0]
             if r["name"] == "serving.tick.dispatch"]
    ended = records[0]["ts"] + unfed
    assert calls[-1]["ts"] - 5e-3 <= ended <= (
        calls[-1]["ts"] + calls[-1]["dur"] + 5e-3)
    # ten plain ticks: step n+1 goes before step n is read, so no read
    # is of the newest dispatch and no stamp is set. With the spans off
    # (their clock is the same) the tick thread reads no clock at all
    reads = []
    clock, me = time.perf_counter, threading.get_ident()

    def counted():      # this thread's reads: others keep their own time
        if threading.get_ident() == me:
            reads.append(1)
        return clock()

    with monkeypatch.context() as patch:
        patch.setattr(time, "perf_counter", counted)
        patch.setattr(root.common.trace, "spans", False)
        for _ in range(10):
            engine._tick()
            assert engine._flying is not None and engine._unfed is None
        assert not reads
    assert rise(UNFED, before) == [1, 0]
    assert engine.decode_dispatches - built == engine._dispatched \
        - sent - prefill_ticks == 11
    # the ending reads the step in flight with nothing behind it: a
    # stamp that no dispatch follows, and that nothing observes
    tick_until(engine, ticket.event.is_set)
    assert ticket.error is None and engine._unfed[1] == "drain"
    assert rise(UNFED, before) == [1, 0]


@pytest.fixture(scope="module")
def draft(lm_wf):
    lm, _ = lm_wf
    prng.seed_all(984)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=1,
                           dim=16, n_train=128, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return wf


def test_a_pool_read_every_tick_observes_a_drain_a_tick(lm_wf, draft,
                                                        prompt):
    _, wf = lm_wf
    engine = ContinuousEngine(wf, draft=draft, spec_gamma=3,
                              name="waits_spec", **SHAPES)
    ticket = Ticket(mode="speculative")
    assert engine.submit(
        make_request(prompt, 24, mode="speculative", gamma=3), ticket)
    before = counts((FIRST, DRAIN))
    # the admission's tick: the draft's prefill is queued behind the
    # target's when the first token is read (no stamp), and positions
    # count the target's alone; the round it dispatches is read
    pos0 = counters.get(POSITIONS)
    engine._tick()
    assert counters.get(POSITIONS) - pos0 == 16
    assert rise((FIRST, DRAIN), before) == [0, 0]
    assert engine._unfed[1] == "drain"
    ticks = 0
    while not ticket.event.is_set():
        engine._tick()      # the round's dispatch ends the interval
        ticks += 1
        assert rise((FIRST, DRAIN), before) == [0, ticks]
    assert ticket.error is None and ticks >= 3
    assert engine.stats()["steps_ahead_share"] == 0.0


def test_the_idle_wait_observes_neither(lm_wf, prompt):
    _, wf = lm_wf
    engine = ContinuousEngine(wf, name="waits_idle", **SHAPES).start()
    try:
        # the programs built first (an interval that a build would end
        # is dropped)
        assert len(engine.serve([make_request(prompt, 6)])[0]) == 6
        before = counts((FIRST, DRAIN))
        sums = [histograms.sum(FIRST), histograms.sum(DRAIN)]
        assert len(engine.serve([make_request(prompt, 6)])[0]) == 6
        waits = histograms.count("veles_serving_loop_wait_seconds")
        deadline = time.time() + 10
        while histograms.count("veles_serving_loop_wait_seconds") \
                == waits and engine._unfed is not None \
                and time.time() < deadline:
            time.sleep(0.005)
        # back in the loop's wait: the last read's stamp is dropped
        assert engine._unfed is None
        time.sleep(0.3)             # no traffic, for a while
        assert len(engine.serve([make_request(prompt, 6)])[0]) == 6
        # an admission's interval each, and no second of the idle wait
        assert rise((FIRST, DRAIN), before) == [2, 0]
        assert histograms.sum(FIRST) - sums[0] < 0.25
        assert histograms.sum(DRAIN) == sums[1]
    finally:
        engine.stop()


# -- a request's waits, where each ends ----------------------------------------

def test_a_streamed_requests_waits_are_observed_before_it_ends(lm_wf,
                                                               prompt):
    _, wf = lm_wf
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           decode_block=4, name="waits_stream",
                           **SHAPES)
    api.initialize()
    # the first token comes with the prefill: hold every decode step
    # back until the waits have been read
    gate, decode = threading.Event(), api._engine._decode
    api._engine._decode = lambda params: (gate.wait(60), decode(params))
    try:
        before, ended = counts(WAITS), histograms.count(E2E)
        req = urllib.request.Request(
            "http://127.0.0.1:%d/generate" % api.port,
            data=json.dumps({"prompt": prompt, "n_new": 12,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as reply:
            events = (json.loads(line[5:]) for line in reply
                      if line.startswith(b"data:"))
            assert len(next(events)["tokens"]) == 1
            # the handler observes after its write, which the client
            # may have read already
            deadline = time.time() + 10
            while histograms.count(WAITS[3]) == before[3] \
                    and time.time() < deadline:
                time.sleep(0.005)
            assert rise(WAITS, before) == [1, 1, 1, 1]
            assert histograms.count(E2E) == ended
            queue, prefill, ttft, _ = (histograms.sum(n) for n in WAITS)
            gate.set()
            rest = list(events)
        assert rest[-1]["done"] and len(rest[-1]["tokens"]) == 12
        assert len(rest) >= 3           # later events: no work for them
        assert rise(WAITS, before) == [1, 1, 1, 1]
        assert histograms.count(E2E) == ended + 1
        if before == [0, 0, 0, 0]:      # this file alone: the sums too
            assert queue + prefill == pytest.approx(ttft, abs=1e-4)
    finally:
        gate.set()
        api.stop()


def test_waits_once_across_preempt_requeue_and_finish(lm_wf, prompt):
    """tests/test_overload.py's drill: a batch request preempted mid
    decode by an interactive one on a 1-slot pool, queued again and
    finished. Two requests, two samples of each wait; buffered, so no
    first write."""
    _, wf = lm_wf
    root.common.serving.qos = True
    try:
        engine = ContinuousEngine(wf, name="waits_qos",
                                  **dict(SHAPES, max_slots=1))
        before = counts(WAITS)
        t_b, t_i = Ticket(), Ticket()
        batch = dict(make_request(prompt, 12), priority="batch")
        assert engine.submit(batch, t_b)
        tick_until(engine, lambda: any(
            2 <= len(s.tokens) < 8 for s in engine.scheduler.active()))
        assert rise(WAITS, before) == [1, 1, 1, 0]
        assert engine.submit(dict(make_request(prompt[:5], 3),
                                  priority="interactive"), t_i)
        tick_until(engine, lambda: t_b.event.is_set()
                   and t_i.event.is_set())
        assert t_b.error is None and t_i.error is None
        assert engine.preemptions >= 1
        assert rise(WAITS, before) == [2, 2, 2, 0]
    finally:
        root.common.serving.qos = False


def test_shed_in_the_queue_observes_its_queue_wait_and_no_ttft():
    sched = SlotScheduler(1, (8,), 16)
    before = counts(WAITS)
    busy, old = Ticket(), Ticket(deadline=time.time() - 1)
    sched.push(make_request([1, 2], 4), busy)
    sched.take_admissions()
    assert rise(WAITS, before) == [1, 0, 0, 0]     # at the admission
    sched.push(make_request([1, 2], 4), old)
    _, expired = sched.take_admissions()
    assert expired == [old] and rise(WAITS, before) == [1, 0, 0, 0]
    shed_expired(expired)
    shed_expired(expired)           # a second sweep adds nothing
    assert old.outcome == "expired" and old.admitted is None
    assert rise(WAITS, before) == [2, 0, 0, 0]     # at the terminal
    assert busy.fail("swept", code=503) and busy.outcome == "shed"
    assert rise(WAITS, before) == [2, 0, 0, 0]
