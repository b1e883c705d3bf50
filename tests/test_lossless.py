"""Lossless request plane (ISSUE 13): durable router journal,
token-level failover resume, drain-by-handoff.

The contract under test: (1) every request the router ACCEPTS is on
disk (fsync'd, per-record hashed) before its first dispatch and
marked terminal on answer — a router SIGKILL loses zero accepted
requests, replay is idempotent by request_id and sheds expired
entries with the id, and a torn/corrupt record is quarantined with a
counted warning, never a refused start; (2) a decode killed at token
k (injected ``serve.replica_death`` / ``serve.decode_step``) hands
its emitted-token prefix back through the first-terminal ``fail()``,
and the failover retry RESUMES — prompt+prefix re-prefilled in one
bucketed pass, the per-slot PRNG stream advanced k splits — producing
token-for-token the uninterrupted solo decode for greedy AND sampled
modes; (3) a draining replica hands its in-flight tickets back at
the next step boundary (bounded by a handoff, not the longest
generation), with the ``serve.handoff`` fault degrading one ticket
to a plain shed, never blocking the drain. All chaos rides the
registered fault points — no monkeypatched internals.

Budget discipline: the journal/Ticket/gate tests are jax-free; the
identity drills share one tiny char_lm workflow module-wide.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy
import pytest

import veles_tpu as vt
from veles_tpu.resilience.faults import FaultInjected
from veles_tpu.serving import (ContinuousEngine, RequestJournal,
                               Ticket, fold_resume)
from veles_tpu.serving.engine import advanced_prng_key, make_request
from veles_tpu.serving.router import FleetRouter
from veles_tpu.telemetry.counters import counters, histograms

from conftest import import_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _post(url, payload, timeout=120.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


# -- the journal (no jax) ------------------------------------------------------

def test_journal_append_pending_done_order(tmp_path):
    j = RequestJournal(str(tmp_path), fsync=False)
    j.admit("req-b", {"prompt": [1]}, 200.0)
    j.admit("req-a", {"prompt": [2]}, 100.0)
    j.admit("req-c", {"prompt": [3]}, 300.0)
    j.done("req-b", 200)
    # unanswered only, ordered by enqueued_at — the replay order
    assert [r["request_id"] for r in j.pending()] == ["req-a", "req-c"]
    j.done("req-a", 503, "expired")
    j.done("req-c", 200)
    assert j.pending() == []


def test_journal_duplicate_admits_are_idempotent(tmp_path):
    # a crash-looped router may re-journal the same id: replay must
    # yield it once (first admit wins)
    j = RequestJournal(str(tmp_path), fsync=False)
    j.admit("req-1", {"n": 1}, 100.0)
    j.admit("req-1", {"n": 2}, 150.0)
    pending = j.pending()
    assert len(pending) == 1
    assert pending[0]["body"] == {"n": 1}


def test_journal_torn_tail_salvaged_counted(tmp_path):
    j = RequestJournal(str(tmp_path), fsync=False)
    j.admit("req-1", {"prompt": [1]}, 100.0)
    j.admit("req-2", {"prompt": [2]}, 101.0)
    # a power cut mid-append leaves a torn tail line
    with open(j._active_path(), "a") as f:
        f.write('{"op": "admit", "request_id": "req-torn", "enq')
    before = counters.get("veles_journal_salvaged_total")
    pending = j.pending()
    assert [r["request_id"] for r in pending] == ["req-1", "req-2"]
    assert counters.get("veles_journal_salvaged_total") - before == 1


def test_journal_bitrot_fails_record_hash(tmp_path):
    j = RequestJournal(str(tmp_path), fsync=False)
    j.admit("req-1", {"prompt": [1]}, 100.0)
    j.admit("req-2", {"prompt": [2]}, 101.0)
    path = j._active_path()
    with open(path) as f:
        lines = f.readlines()
    # valid JSON, silently flipped payload: the per-record hash is
    # what catches it (a plain JSON parse would accept it)
    rotted = lines[0].replace('"prompt": [1]', '"prompt": [9]')
    assert rotted != lines[0]
    with open(path, "w") as f:
        f.writelines([rotted, lines[1]])
    before = counters.get("veles_journal_salvaged_total")
    assert [r["request_id"] for r in j.pending()] == ["req-2"]
    assert counters.get("veles_journal_salvaged_total") - before == 1


def test_journal_injected_corruption_salvaged(tmp_path, monkeypatch):
    """The router.journal fault point, append side: an armed corrupt
    clause damages the written bytes — replay quarantines the torn
    record with a counted warning instead of refusing to start."""
    j = RequestJournal(str(tmp_path), fsync=False)
    monkeypatch.setenv("VELES_FAULTS",
                       "router.journal:corrupt:times=1")
    inj = counters.get("veles_faults_injected_total")
    j.admit("req-corrupt", {"prompt": [1]}, 100.0)
    assert counters.get("veles_faults_injected_total") - inj == 1
    monkeypatch.delenv("VELES_FAULTS")
    j.admit("req-clean", {"prompt": [2]}, 101.0)
    before = counters.get("veles_journal_salvaged_total")
    pending = j.pending()        # the salvage pass IS the start path
    assert [r["request_id"] for r in pending] == ["req-clean"]
    assert counters.get("veles_journal_salvaged_total") - before == 1


def test_journal_append_raise_propagates(tmp_path, monkeypatch):
    # raise at append = the admission must be REFUSED (the router
    # sheds it), never acknowledged un-journaled
    j = RequestJournal(str(tmp_path), fsync=False)
    monkeypatch.setenv("VELES_FAULTS", "router.journal:raise:times=1")
    with pytest.raises(FaultInjected):
        j.admit("req-1", {"prompt": [1]}, 100.0)
    monkeypatch.delenv("VELES_FAULTS")
    assert j.pending() == []


def test_journal_compaction_keeps_live_only(tmp_path):
    j = RequestJournal(str(tmp_path), rotate_every=16, fsync=False)
    before = counters.get("veles_journal_compactions_total")
    for i in range(10):
        j.admit("req-%d" % i, {"i": i}, 100.0 + i)
        if i % 2 == 0:
            j.done("req-%d" % i, 200)
    j.compact()
    assert counters.get("veles_journal_compactions_total") \
        - before >= 1
    segs = j.segments()
    assert len(segs) == 1
    # the compacted segment carries the checkpoint-chain manifest
    assert os.path.exists(segs[0] + ".manifest.json")
    live = [r["request_id"] for r in j.pending()]
    assert live == ["req-%d" % i for i in range(10) if i % 2]
    # appends continue into the fresh segment; terminals still land
    j.done("req-1", 200)
    assert "req-1" not in [r["request_id"] for r in j.pending()]


def test_journal_auto_rotates_past_rotate_every(tmp_path):
    j = RequestJournal(str(tmp_path), rotate_every=16, fsync=False)
    before = counters.get("veles_journal_compactions_total")
    for i in range(10):
        j.admit("req-%d" % i, {"i": i}, 100.0 + i)
        j.done("req-%d" % i, 200)
    assert counters.get("veles_journal_compactions_total") \
        - before >= 1
    assert j.pending() == []


# -- ticket progress + resume payload (no jax) --------------------------------

def test_error_payload_carries_resume():
    t = Ticket(mode="sample")
    t.set_progress([5, 6, 7])
    assert t.fail("died mid-decode", code=503, retry_after=1.0)
    body = t.error_payload()
    assert body["resume"] == {"tokens": [5, 6, 7], "tokens_done": 3}
    assert body["request_id"] == t.request_id


def test_progress_only_for_step_modes_and_pre_terminal():
    spec = Ticket(mode="speculative")
    spec.set_progress([1, 2])
    assert spec.progress is None        # spec/beam retry from scratch
    t = Ticket(mode="greedy")
    t.fail("gone", code=503)
    t.set_progress([1])                 # after terminal: no-op
    assert t.progress is None
    assert "resume" not in t.error_payload()


def test_fold_resume_arithmetic():
    req = make_request([1, 2, 3], 8, temperature=0.7, seed=4,
                       mode="sample")
    folded = fold_resume(req, [9, 8])
    assert folded["prompt"] == [1, 2, 3, 9, 8]
    assert folded["n_new"] == 6 and folded["resume_k"] == 2
    assert fold_resume(req, [])["resume_k"] == 0
    with pytest.raises(ValueError):
        fold_resume(make_request([1], 2), [7, 7])


def test_advanced_prng_key_matches_split_chain():
    import jax
    key = jax.random.PRNGKey(11)
    for _ in range(5):
        key = jax.random.split(key)[0]
    assert numpy.array_equal(numpy.asarray(key),
                             numpy.asarray(advanced_prng_key(11, 5)))


# -- the identity drills (one tiny LM, module-scoped) -------------------------

@pytest.fixture(scope="module")
def lm_wf():
    lm = import_model("char_lm")
    from veles_tpu import prng
    prng.seed_all(2025)
    wf = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                           dim=32, n_train=64, n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    return lm, wf


@pytest.mark.parametrize("temperature,seed",
                         [(0.0, 0), (0.9, 41)],
                         ids=["greedy", "sampled"])
def test_engine_resume_is_id_exact(lm_wf, monkeypatch, temperature,
                                   seed):
    """THE resume identity, engine-level and deterministic: a decode
    killed at token k (injected serve.decode_step fault) hands back
    its emitted prefix; a SECOND engine serves the folded resume and
    the concatenation equals the uninterrupted solo decode
    token-for-token — greedy AND sampled."""
    from veles_tpu.nn import sampling
    lm, wf = lm_wf
    mode = "sample" if temperature > 0 else "greedy"
    prompt = [1, 5, 3, 2, 4]
    n_new = 12
    solo = sampling.generate(wf, prompt, n_new,
                             temperature=temperature, seed=seed)
    req = make_request(prompt, n_new, temperature=temperature,
                       seed=seed, mode=mode)
    e1 = ContinuousEngine(wf, max_slots=2, buckets=(8, 16, 32),
                          max_context=48, decode_block=1,
                          name="resume_a_" + mode).start()
    try:
        t1 = Ticket(mode=mode)
        monkeypatch.setenv("VELES_FAULTS",
                           "serve.decode_step:raise:after=4,times=1")
        assert e1.submit(req, t1)
        assert t1.event.wait(60)
        monkeypatch.delenv("VELES_FAULTS")
        assert t1.code == 503 and t1.progress
        k = len(t1.progress)
        assert 0 < k < n_new
        assert t1.progress == solo[:k]
        assert t1.error_payload()["resume"]["tokens_done"] == k
    finally:
        e1.stop()
    rt = counters.get("veles_resume_tokens_total")
    e2 = ContinuousEngine(wf, max_slots=2, buckets=(8, 16, 32),
                          max_context=48, decode_block=1,
                          name="resume_b_" + mode).start()
    try:
        t2 = Ticket(mode=mode)
        assert e2.submit(fold_resume(req, t1.progress), t2)
        assert t2.event.wait(60)
        assert t2.error is None, t2.error
        assert t1.progress + t2.result["tokens"] == solo
        assert counters.get("veles_resume_tokens_total") - rt == k
    finally:
        e2.stop()


def test_fleet_death_resume_id_exact_sampled(lm_wf, monkeypatch):
    """THE acceptance drill, HTTP end-to-end with a SAMPLED decode: a
    2-replica fleet, serve.replica_death armed to fire a few decode
    ticks in — the dying replica's gasp (503 + resume) makes the
    router RESUME on the survivor, and the stitched answer equals the
    uninterrupted solo decode exactly, counted and exactly-once."""
    from veles_tpu.nn import sampling
    lm, wf = lm_wf
    prompt = [2, 4, 1, 3, 5]
    n_new = 12
    solo = sampling.generate(wf, prompt, n_new, temperature=0.8,
                             seed=17)
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8, 16, 32),
                             max_context=48,
                             name="gasp_%d" % i) for i in range(2)]
    for api in apis:
        api.initialize()
    router = None
    try:
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=1, retry_budget=2,
            attempt_timeout=60.0, request_timeout=120.0,
            name="gasp_router").start()
        url = "http://127.0.0.1:%d/generate" % router.port
        # warm both replicas' programs outside the armed window
        for api in apis:
            code, _b, _h = _post(
                "http://127.0.0.1:%d/generate" % api.port,
                {"prompt": prompt, "n_new": 2, "mode": "sample",
                 "temperature": 0.8, "seed": 17})
            assert code == 200
        ra = counters.get("veles_resume_attempts_total")
        fo = counters.get("veles_router_failovers_total")
        monkeypatch.setenv(
            "VELES_FAULTS", "serve.replica_death:raise:after=4,times=1")
        code, body, _ = _post(url, {"prompt": prompt, "n_new": n_new,
                                    "mode": "sample",
                                    "temperature": 0.8, "seed": 17})
        monkeypatch.delenv("VELES_FAULTS")
        assert code == 200, body
        assert body["tokens"] == solo          # id-exact across death
        k = body.get("resumed_from", 0)
        assert k >= 1                          # it RESUMED, not redid
        assert counters.get("veles_resume_attempts_total") - ra >= 1
        assert counters.get("veles_router_failovers_total") - fo >= 1
    finally:
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()


def test_window_plane_greedy_resume_and_sampled_409(lm_wf):
    """The window-plane exclusions: a greedy resume MAY ride the
    window worker (deterministic — the folded prompt continues
    exactly); a sampled resume is answered 409 (the PRNG stream lives
    on the slot pool only), which tells a router to retry from
    scratch."""
    from veles_tpu.nn import sampling
    lm, wf = lm_wf
    prompt = [1, 2, 3, 4]
    solo = sampling.generate(wf, prompt, 8, temperature=0)
    api = vt.GenerationAPI(wf, port=0, engine="window",
                           name="window_resume")
    api.initialize()
    base = "http://127.0.0.1:%d/generate" % api.port
    try:
        code, body, _ = _post(base, {
            "prompt": prompt, "n_new": 5, "mode": "greedy",
            "resume_tokens": solo[:3]})
        assert code == 200
        assert solo[:3] + body["tokens"] == solo
        code, body, _ = _post(base, {
            "prompt": prompt, "n_new": 5, "mode": "sample",
            "temperature": 0.8, "seed": 3,
            "resume_tokens": solo[:3]})
        assert code == 409
        assert "resume" in body["error"] and "request_id" in body
    finally:
        api.stop()


def test_router_409_drops_resume_and_retries_from_scratch():
    """A replica that answers 409 to a resume attempt is healthy: the
    router drops the prefix, gives the replica its roster slot back,
    retries from scratch and delivers — without advancing the 409
    replica's breaker."""
    state = {"a_posts": [], "b_posts": []}

    def handler(key, resume_answer):
        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                if self.path == "/readyz":
                    self._reply(200, {"status": "ok"})
                else:
                    self.send_error(404)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                state[key].append(req)
                self._reply(*resume_answer(req))

            def _reply(self, code, payload):
                data = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
        return H

    def a_answer(req):
        # A always dies with a gasp carrying progress
        return 503, {"error": "dying", "request_id":
                     req.get("request_id"),
                     "resume": {"tokens": [7, 8], "tokens_done": 2}}

    def b_answer(req):
        if req.get("resume_tokens"):
            return 409, {"error": "resume not servable here",
                         "request_id": req.get("request_id")}
        return 200, {"tokens": [7, 8, 9, 10],
                     "request_id": req.get("request_id")}

    srv_a = ThreadingHTTPServer(("127.0.0.1", 0),
                                handler("a_posts", a_answer))
    srv_b = ThreadingHTTPServer(("127.0.0.1", 0),
                                handler("b_posts", b_answer))
    for srv in (srv_a, srv_b):
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
    router = None
    try:
        router = FleetRouter(
            ["127.0.0.1:%d" % srv_a.server_port,
             "127.0.0.1:%d" % srv_b.server_port],
            probe_interval=30.0, failure_threshold=3,
            retry_budget=2, attempt_timeout=10.0,
            request_timeout=30.0, name="r409").start()
        # rank A first so the gasp precedes the 409
        for r in router.replicas:
            r.ready = True
            r.slots, r.slots_busy = (
                (4, 0) if str(srv_a.server_port) in r.url else (4, 3))
        answered = router.route({"prompt": [1], "n_new": 4,
                                 "mode": "greedy"})
        assert answered.done and answered.status == 200
        assert answered.body["tokens"] == [7, 8, 9, 10]
        # B saw the resume attempt, then the from-scratch retry
        assert state["b_posts"][0].get("resume_tokens") == [7, 8]
        assert "resume_tokens" not in state["b_posts"][1]
        assert state["b_posts"][1]["n_new"] == 4
        b = [r for r in router.replicas
             if str(srv_b.server_port) in r.url][0]
        assert b.breaker.failures == 0        # 409 is not a failure
    finally:
        if router is not None:
            router.stop()
        srv_a.shutdown()
        srv_b.shutdown()


# -- drain-by-handoff ---------------------------------------------------------

def test_drain_handoff_bounded_by_handoff_not_generation(lm_wf):
    """THE drain acceptance leg: a replica with a LONG in-flight
    generation drains within handoff time, not generation time — the
    ticket comes back 503 + resume progress, and through a router the
    request finishes on the other replica, id-exact."""
    from veles_tpu.nn import sampling
    lm, wf = lm_wf
    prompt = [3, 1, 4, 1, 5]
    n_new = 80
    solo = sampling.generate(wf, prompt, n_new, temperature=0)
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8, 16, 32, 48),
                             max_context=96,
                             name="handoff_%d" % i) for i in range(2)]
    for api in apis:
        api.initialize()
    router = None
    try:
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=2, retry_budget=2,
            attempt_timeout=120.0, request_timeout=180.0,
            name="handoff_router").start()
        url = "http://127.0.0.1:%d/generate" % router.port
        # warm both replicas + measure the uninterrupted decode time
        t0 = time.time()
        for api in apis:
            code, _b, _h = _post(
                "http://127.0.0.1:%d/generate" % api.port,
                {"prompt": prompt, "n_new": n_new})
            assert code == 200
        uninterrupted = (time.time() - t0) / 2
        results = {}

        def long_post():
            results["r"] = _post(url, {"prompt": prompt,
                                       "n_new": n_new})

        t = threading.Thread(target=long_post)
        t.start()
        # catch the request MID-DECODE: poll the engines' slot
        # occupancy (not just the HTTP in-flight count — a request
        # still queued, or already retired, has nothing to hand off)
        busy = None
        deadline = time.time() + 15
        while busy is None and time.time() < deadline:
            busy = next(
                (api for api in apis
                 if api._engine is not None
                 and api._engine.scheduler.busy_count()), None)
            if busy is None:
                time.sleep(0.001)
        assert busy is not None
        ho = counters.get("veles_handoff_requests_total")
        drain_t0 = time.time()
        assert busy.drain(grace=60) is True
        drain_elapsed = time.time() - drain_t0
        assert counters.get("veles_handoff_requests_total") - ho == 1
        # bounded by a handoff, not by the generation: the drained
        # replica never rode out the remaining decode
        assert drain_elapsed < max(1.0, 0.75 * uninterrupted), \
            (drain_elapsed, uninterrupted)
        t.join(timeout=120)
        code, body, _ = results["r"]
        assert code == 200
        assert body["tokens"] == solo          # finished elsewhere
        assert body.get("resumed_from", 0) >= 1
    finally:
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()


def test_handoff_snapshot_fault_degrades_to_plain_shed(lm_wf,
                                                       monkeypatch):
    """serve.handoff chaos: a failed progress snapshot mid-drain
    degrades that ticket to a plain 503 (no resume record) — the
    drain still completes and the caller still gets its terminal."""
    lm, wf = lm_wf
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           max_slots=2, buckets=(8, 16, 32),
                           max_context=64, name="handoff_fault")
    api.initialize()
    base = "http://127.0.0.1:%d" % api.port
    try:
        code, _b, _h = _post(base + "/generate",
                             {"prompt": [1, 2, 3], "n_new": 2})
        assert code == 200                     # warm
        results = {}

        def long_post():
            results["r"] = _post(base + "/generate",
                                 {"prompt": [1, 2, 3, 4],
                                  "n_new": 48})

        t = threading.Thread(target=long_post)
        t.start()
        deadline = time.time() + 15
        while not (api._engine is not None
                   and api._engine.scheduler.busy_count()) \
                and time.time() < deadline:
            time.sleep(0.001)
        ho = counters.get("veles_handoff_requests_total")
        monkeypatch.setenv("VELES_FAULTS", "serve.handoff:raise")
        assert api.drain(grace=60) is True
        monkeypatch.delenv("VELES_FAULTS")
        assert counters.get("veles_handoff_requests_total") == ho
        t.join(timeout=30)
        code, body, _ = results["r"]
        assert code == 503
        assert "resume" not in body            # degraded, not blocked
        assert "request_id" in body
    finally:
        api.stop()


# -- the drain/stop abort path: one terminal per ticket -----------------------

def test_double_drain_stop_records_one_terminal(lm_wf):
    """Satellite regression: stragglers aborted by drain()/stop()
    settle via the first-terminal fail() — histogram sample and
    terminal exactly once however many sweeps run."""
    lm, wf = lm_wf
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8,),
                              max_context=48, decode_block=1,
                              name="double_stop").start()
    req = make_request([1, 2, 3], 32)
    ticket = Ticket(mode="greedy")
    # the one sample is the admission's (the wait is observed where
    # it ends): no sweep after it adds another
    qw = histograms.count("veles_serving_queue_wait_seconds")
    assert engine.submit(req, ticket)
    deadline = time.time() + 15
    while ticket.admitted is None and time.time() < deadline:
        time.sleep(0.005)
    assert ticket.admitted is not None
    engine.stop()
    assert ticket.event.is_set() and ticket.code == 503
    assert ticket.progress                     # abort handed progress
    # the double sweep: a second stop + explicit abort re-run
    engine.stop()
    engine._abort_active("late sweep", code=503)
    engine.scheduler.drain("late sweep")
    assert ticket.fail("third sweep", code=503) is False
    assert histograms.count("veles_serving_queue_wait_seconds") \
        - qw == 1
    assert ticket.outcome == "shed"


def test_restful_stop_sweep_settles_outstanding_once():
    wf = vt.Workflow(name="sweep_wf")
    api = vt.RESTfulAPI(wf, loader=None, port=0)
    ticket = Ticket(mode="greedy")
    api._outstanding.add(ticket)
    qw = histograms.count("veles_serving_queue_wait_seconds")
    api.stop()
    assert ticket.event.is_set() and ticket.code == 503
    assert ticket.retry_after == 5.0
    body = ticket.error_payload()
    assert body["request_id"] == ticket.request_id
    api.stop()                                  # double sweep: no-op
    assert histograms.count("veles_serving_queue_wait_seconds") \
        - qw == 1


# -- journal replay after SIGKILL, end to end on the route CLI ----------------

def _fake_replica(state=None):
    state = dict({"delay": 0.0, "served": []}, **(state or {}))

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if self.path == "/readyz":
                body = json.dumps({"status": "ok"}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_error(404)

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(n) or b"{}")
            if state["delay"]:
                time.sleep(state["delay"])
            state["served"].append(req.get("request_id"))
            body = json.dumps({"tokens": [1, 2, 3],
                               "request_id":
                               req.get("request_id")}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, state


def _start_route_cli(endpoints_file, journal_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "veles_tpu", "route",
         "--endpoints-file", str(endpoints_file), "--port", "0",
         "--probe-interval", "0.2", "--journal", str(journal_dir),
         "--request-timeout", "60"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO)
    line = proc.stdout.readline()
    assert line.startswith("ROUTING port="), line
    return proc, int(line.split("port=")[1].split()[0])


@pytest.mark.skipif(sys.platform.startswith("win"),
                    reason="SIGKILL semantics")
def test_journal_replay_after_sigkill_answers_every_request(tmp_path):
    """THE durability drill: a journaled route CLI is SIGKILLed with
    requests accepted-but-unanswered; the restarted router replays
    them — every journaled request reaches the replica and EXACTLY
    one terminal record, none lost, none double-terminal."""
    srv, state = _fake_replica({"delay": 1.0})
    endpoints = tmp_path / "fleet.txt"
    endpoints.write_text("127.0.0.1:%d\n" % srv.server_port)
    journal_dir = tmp_path / "journal"
    proc, port = _start_route_cli(endpoints, journal_dir)
    url = "http://127.0.0.1:%d/generate" % port
    rids = ["req-kill-%d" % i for i in range(3)]
    try:
        # one request completes before the kill...
        code, body, _ = _post(url, {"prompt": [1], "n_new": 2,
                                    "request_id": "req-done-0"})
        assert code == 200
        # ...three more are accepted (journaled) and in flight when
        # the router is SIGKILLed mid-load
        threads = [threading.Thread(
            target=lambda r=r: _post(url, {"prompt": [1], "n_new": 2,
                                           "request_id": r}),
            daemon=True) for r in rids]
        for t in threads:
            t.start()
        time.sleep(0.4)                 # admitted, not yet answered
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    journal = RequestJournal(str(journal_dir), fsync=False)
    pending = [r["request_id"] for r in journal.pending()]
    assert set(rids) & set(pending), \
        "SIGKILL left nothing pending — the drill never armed"
    assert "req-done-0" not in pending  # terminal before the kill
    # restart: the replay must answer every journaled request
    state["delay"] = 0.0
    before = counters.get("veles_journal_replayed_total")
    proc2, _port2 = _start_route_cli(endpoints, journal_dir)
    try:
        deadline = time.time() + 30
        while journal.pending() and time.time() < deadline:
            time.sleep(0.1)
        assert journal.pending() == [], "replay left entries pending"
        admits, terminals = journal.replay()
        # exactly one terminal per accepted request, every replayed
        # request actually reached the replica
        for rid in rids + ["req-done-0"]:
            assert rid in terminals, rid
        for rid in pending:
            assert rid in state["served"], rid
    finally:
        proc2.send_signal(signal.SIGTERM)
        try:
            assert proc2.wait(timeout=30) == 0
        finally:
            if proc2.poll() is None:
                proc2.kill()
                proc2.wait()
        srv.shutdown()
    # the test-process journal reads never count replays — only the
    # restarted router's own process did the replaying
    assert counters.get("veles_journal_replayed_total") == before
