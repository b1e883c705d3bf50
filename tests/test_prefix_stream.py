"""Prefix-sharing paged KV cache + chunked prefill + token streaming
(veles_tpu/serving/pages.py PrefixCache + engine adoption/COW +
GenerationAPI/FleetRouter SSE) — the heavy-traffic request plane.

The contracts under test: pages are refcounted and a shared page
counts ONCE in every gauge; prefix-cache ON answers are bit-identical
to OFF (and to solo decodes) — greedy AND sampled, post-COW
divergence included; a retired writer never mutates a shared page;
injected match corruption degrades to a full prefill (never wrong
tokens); a chunk fault sheds 503 with a resume payload while
co-tenants keep decoding; streamed responses deliver every token
exactly once with a first event strictly before completion; and the
router's streaming proxy resumes token-level across a replica death.
"""
import json
import os
import time
import urllib.error
import urllib.request

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import prng
from veles_tpu.nn import sampling
from veles_tpu.serving import ContinuousEngine, PagePool, PrefixCache
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import Ticket
from veles_tpu.telemetry.counters import counters

from conftest import import_model


# -- allocator refcounts + prefix index (no jax) ------------------------------

def test_pagepool_refcounts_and_ledger():
    pool = PagePool(6, 4)
    got = pool.alloc(3)
    assert got is not None and pool.in_use() == 3
    assert pool.refcount(got[0]) == 1
    # sharing takes references; free releases one at a time
    assert pool.share(got[0]) == 2
    pool.free([got[0]])
    assert pool.refcount(got[0]) == 1 and pool.in_use() == 3
    pool.free(got)
    assert pool.in_use() == 0 and pool.ledger() == {}
    # a page nobody holds cannot be shared (poisoning guard)
    with pytest.raises(ValueError):
        pool.share(got[0])
    # double free is tolerated like the idempotent slot retire
    pool.free(got)
    assert pool.free_count() == 6


def test_shared_page_counts_once_in_use():
    """Satellite fix: ``in_use`` (and so the fragmentation gauge and
    fleet pages_in_use aggregation) counts a page shared by N holders
    exactly once."""
    pool = PagePool(4, 8)
    page = pool.alloc(1)[0]
    for _ in range(5):
        pool.share(page)
    assert pool.in_use() == 1
    assert pool.refcount(page) == 6


def test_prefix_cache_match_insert_and_divergence():
    pool = PagePool(8, 2)
    cache = PrefixCache(pool, 2)
    pages = pool.alloc(3)
    assert cache.insert([1, 2, 3, 4, 5, 6], pages) == 3
    # full match walks all three blocks, in order, sharing each
    m = cache.match([1, 2, 3, 4, 5, 6, 9])
    assert m == pages
    assert all(pool.refcount(p) == 3 for p in m)   # slot+tree+match
    pool.free(m)
    # divergence in block 2 stops the walk after block 1
    m = cache.match([1, 2, 7, 7, 5, 6])
    assert m == pages[:1]
    pool.free(m)
    # partial trailing block never matches (blocks are page_size)
    assert cache.match([1, 2, 3]) == [pages[0]]
    pool.free([pages[0]])
    # re-inserting the same blocks dedupes (tree keeps its pages)
    other = pool.alloc(2)
    assert cache.insert([1, 2, 3, 4], other) == 0
    pool.free(other)
    pool.free(pages)
    cache.clear()
    assert pool.ledger() == {}


def test_prefix_cache_lru_leaf_eviction_under_pressure():
    """Allocator pressure evicts least-recently-used LEAF blocks via
    the pool's evictor hook before any caller is refused."""
    pool = PagePool(4, 2)
    cache = PrefixCache(pool, 2)
    pool.evictor = cache.evict
    a = pool.alloc(2)
    cache.insert([1, 2, 3, 4], a)
    pool.free(a)                    # only the tree holds both now
    b = pool.alloc(2)
    cache.insert([9, 9, 8, 8], b)
    pool.free(b)
    assert pool.free_count() == 0
    # touch the [1,2] chain so the [9,9] chain is LRU
    pool.free(cache.match([1, 2, 3, 4]))
    ev0 = counters.get("veles_prefix_evictions_total")
    got = pool.alloc(2)             # forces eviction of the LRU chain
    assert got is not None
    assert counters.get("veles_prefix_evictions_total") - ev0 == 2
    assert cache.match([9, 9, 8, 8]) == []          # evicted
    kept = cache.match([1, 2, 3, 4])
    assert len(kept) == 2                           # survivors
    pool.free(kept)
    pool.free(got)
    cache.clear()
    assert pool.ledger() == {}


def test_new_fault_points_registered():
    from veles_tpu.resilience.faults import list_points
    points = list_points()
    assert "serve.prefix_match" in points
    assert "serve.prefill_chunk" in points


# -- engine: id-exactness under sharing ---------------------------------------

@pytest.fixture(scope="module")
def served():
    lm = import_model("char_lm")
    prng.seed_all(1511)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                           dim=32, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    yield lm, wf


@pytest.fixture(scope="module")
def prefix_engine(served):
    lm, wf = served
    engine = ContinuousEngine(wf, max_slots=3, buckets=(8, 16, 32),
                              max_context=48, page_size=8,
                              prefix_cache=True, prefill_chunk=8,
                              name="prefix_t").start()
    yield engine
    engine.stop()


def _corpus(lm, seed, length):
    return [int(t) for t in
            lm.make_corpus(numpy.random.RandomState(seed), length)]


def test_prefix_on_id_exact_greedy_and_sampled(served, prefix_engine):
    """THE acceptance bar: greedy AND sampled decodes with the prefix
    cache on are bit-identical to prefix-cache off AND to solo
    decodes — cold (miss), warm (adoption) and mixed-tenancy."""
    lm, wf = served
    engine = prefix_engine
    shared = _corpus(lm, 7, 16)              # two full 8-token blocks
    reqs = []
    for i in range(4):
        reqs.append(make_request(
            shared + _corpus(lm, 100 + i, 4), 6,
            temperature=0.8 if i % 2 else 0.0,
            seed=40 + i, mode="sample" if i % 2 else "greedy"))
    solo = [sampling.generate(wf, r["prompt"], r["n_new"],
                              temperature=r["temperature"],
                              seed=r["seed"]) for r in reqs]
    hits0 = counters.get("veles_prefix_hits_total")
    chunks0 = engine.chunk_dispatches
    # cold wave: misses, full (chunked) prefills — still id-exact
    assert engine.serve([dict(r) for r in reqs]) == solo
    cold_chunks = engine.chunk_dispatches - chunks0
    # warm wave: every admission adopts the shared blocks
    assert engine.serve([dict(r) for r in reqs]) == solo
    assert counters.get("veles_prefix_hits_total") - hits0 >= 4
    assert counters.get("veles_prefix_shared_pages_total") > 0
    # what sharing is for: an adopted prefix is not prefilled again,
    # so the warm wave runs fewer chunk programs than the cold one
    warm_chunks = engine.chunk_dispatches - chunks0 - cold_chunks
    assert 0 < warm_chunks < cold_chunks, (warm_chunks, cold_chunks)


def test_full_prompt_match_cow_and_post_cow_divergence(served,
                                                      prefix_engine):
    """A FULL-prompt match re-computes only its last position — into a
    copy-on-write duplicate of the last shared page — and a later
    request diverging inside the shared region still answers its own
    solo decode (post-COW divergence, test-locked)."""
    lm, wf = served
    engine = prefix_engine
    prompt = _corpus(lm, 9, 16)           # exactly two full blocks
    solo = sampling.generate(wf, prompt, 5, temperature=0)
    cow0 = counters.get("veles_prefix_cow_copies_total")
    assert engine.serve([make_request(prompt, 5)])[0] == solo
    # second serve fully matches the now-cached prompt -> COW
    assert engine.serve([make_request(prompt, 5)])[0] == solo
    assert counters.get("veles_prefix_cow_copies_total") > cow0
    # divergent second block: matches only block 0, answers its own
    divergent = prompt[:8] + _corpus(lm, 31, 8)
    solo_div = sampling.generate(wf, divergent, 5, temperature=0)
    assert engine.serve([make_request(divergent, 5)])[0] == solo_div
    # sampled full-match rides the same COW path id-exactly
    solo_s = sampling.generate(wf, prompt, 5, temperature=0.7, seed=3)
    assert engine.serve([make_request(prompt, 5, temperature=0.7,
                                      seed=3, mode="sample")]
                        )[0] == solo_s


def test_chunked_prefill_id_exact_without_prefix_cache(served):
    """prefill_chunk alone (no sharing) must be bit-identical to the
    monolithic bucketed prefill."""
    lm, wf = served
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8, 32),
                              max_context=48, page_size=8,
                              prefix_cache=False, prefill_chunk=8,
                              name="chunk_t").start()
    try:
        reqs = [make_request(_corpus(lm, 50 + i, 20), 6,
                             temperature=0.6 if i % 2 else 0.0,
                             seed=60 + i,
                             mode="sample" if i % 2 else "greedy")
                for i in range(3)]
        solo = [sampling.generate(wf, r["prompt"], r["n_new"],
                                  temperature=r["temperature"],
                                  seed=r["seed"]) for r in reqs]
        assert engine.serve(reqs) == solo
        assert engine.chunk_dispatches >= 3
        assert ("pchunk", None) in engine._progs
        assert engine.programs_built <= engine.programs_bound()
    finally:
        engine.stop()


# -- poisoning + ledger -------------------------------------------------------

def test_retired_writer_never_mutates_shared_page(served):
    """THE poisoning regression: after a writer retires, its cached
    (now shared) pages keep their exact bytes through adoption by a
    second slot, that slot's decode writes, its retirement, AND page
    reuse by unrelated traffic — write-after-retire and the COW
    divergence path both covered; the refcount ledger balances to
    zero after the churn."""
    lm, wf = served
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8, 16),
                              max_context=32, page_size=8,
                              prefix_cache=True, prefill_chunk=8,
                              name="poison_t").start()
    try:
        prompt = _corpus(lm, 11, 16)
        engine.serve([make_request(prompt, 4)])
        shared_pages = engine.prefix_cache.cached_pages()
        assert len(shared_pages) == 2
        kp0 = numpy.asarray(engine._caches[0][0])
        before = {p: kp0[p].copy() for p in shared_pages}
        # adoption + decode + retire (a full-prompt match also runs
        # the COW path), then unrelated traffic reusing freed pages
        engine.serve([make_request(prompt, 6)])
        engine.serve([make_request(prompt[:8] + _corpus(lm, 12, 8),
                                   6)])
        engine.serve([make_request(_corpus(lm, 13, 14), 8, seed=5)])
        kp0 = numpy.asarray(engine._caches[0][0])
        for p, content in before.items():
            assert (kp0[p] == content).all(), \
                "shared page %d mutated after its writer retired" % p
        assert engine.scheduler.busy_count() == 0
        # every page now held only by the prefix index
        ledger = engine.page_pool.ledger()
        assert all(rc == 1 for rc in ledger.values())
        cached = set(engine.prefix_cache.cached_pages())
        assert set(ledger) == cached
    finally:
        engine.stop()
    # stop() cleared the index: the ledger balances to zero
    assert engine.page_pool.ledger() == {}
    assert engine.page_pool.in_use() == 0
    assert engine.page_pool.free_count() == engine.pages


def test_stats_truthful_under_sharing(served, prefix_engine):
    """Fragmentation/occupancy stats count a shared page once: the
    occupied estimate can never exceed in_use x page_size (the
    pre-fix per-slot sum did under sharing), and cached blocks report
    as fully occupied."""
    lm, wf = served
    engine = prefix_engine
    prompt = _corpus(lm, 17, 16)
    engine.serve([make_request(prompt, 4)])
    engine.serve([make_request(prompt + _corpus(lm, 18, 4), 4)])
    st = engine.stats()
    assert st["prefix_cache"] == 1
    assert st["prefix_blocks"] >= 2
    assert 0.0 <= st["page_fragmentation"] <= 1.0
    in_use = engine.page_pool.in_use()
    assert in_use >= st["prefix_blocks"]


# -- chaos --------------------------------------------------------------------

def test_prefix_match_fault_degrades_to_full_prefill(served,
                                                     prefix_engine,
                                                     monkeypatch):
    """Injected index loss (raise) AND index rot (corrupt) both
    degrade to a full prefill — identical tokens, never wrong ones."""
    lm, wf = served
    engine = prefix_engine
    prompt = _corpus(lm, 21, 16) + _corpus(lm, 22, 4)
    solo = sampling.generate(wf, prompt, 5, temperature=0)
    assert engine.serve([make_request(prompt, 5)])[0] == solo  # warm
    faults0 = counters.get("veles_faults_injected_total")
    monkeypatch.setenv("VELES_FAULTS", "serve.prefix_match:raise")
    assert engine.serve([make_request(prompt, 5)])[0] == solo
    monkeypatch.setenv("VELES_FAULTS", "serve.prefix_match:corrupt")
    assert engine.serve([make_request(prompt, 5)])[0] == solo
    monkeypatch.setenv("VELES_FAULTS", "")
    assert counters.get("veles_faults_injected_total") - faults0 >= 2
    # and the cache still works after the chaos
    hits0 = counters.get("veles_prefix_hits_total")
    assert engine.serve([make_request(prompt, 5)])[0] == solo
    assert counters.get("veles_prefix_hits_total") - hits0 == 1


def test_prefill_chunk_fault_sheds_503_with_resume_payload(
        served, monkeypatch):
    """An injected chunk fault sheds THAT admission 503 + Retry-After
    with a resume payload while the in-flight co-tenant decodes to
    its exact solo answer."""
    lm, wf = served
    engine = ContinuousEngine(wf, max_slots=2, buckets=(8, 32),
                              max_context=48, page_size=8,
                              prefix_cache=False, prefill_chunk=8,
                              name="chaos_chunk_t").start()
    try:
        cotenant = make_request(_corpus(lm, 25, 6), 16, seed=2)
        solo = sampling.generate(wf, cotenant["prompt"], 16,
                                 temperature=0)
        t_co = Ticket()
        assert engine.submit(cotenant, t_co)
        # wait until the co-tenant is PAST its own prefill chunk (its
        # first token exists) so the armed fault can only hit the
        # long admission's chunks
        deadline = time.time() + 30
        while t_co.first_token is None and time.time() < deadline:
            time.sleep(0.005)
        assert t_co.first_token is not None
        shed0 = counters.get("veles_shed_requests_total")
        monkeypatch.setenv("VELES_FAULTS",
                           "serve.prefill_chunk:raise:times=1")
        t_long = Ticket(mode="greedy")
        assert engine.submit(make_request(_corpus(lm, 26, 20), 4),
                             t_long)
        assert t_long.event.wait(60)
        monkeypatch.setenv("VELES_FAULTS", "")
        assert t_long.code == 503 and t_long.retry_after
        body = t_long.error_payload()
        assert body["resume"] == {"tokens": [], "tokens_done": 0}
        assert counters.get("veles_shed_requests_total") == shed0 + 1
        assert t_co.event.wait(60)
        assert t_co.result["tokens"] == solo
    finally:
        engine.stop()


# -- streaming ----------------------------------------------------------------

def _post_stream(url, payload, timeout=60.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    events, t_first = [], None
    t0 = time.time()
    with urllib.request.urlopen(req, timeout=timeout) as r:
        ctype = r.headers.get("Content-Type", "")
        for line in r:
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            ev = json.loads(line[5:])
            if t_first is None and ev.get("tokens"):
                t_first = time.time() - t0
            events.append(ev)
    return ctype, events, t_first, time.time() - t0


@pytest.fixture(scope="module")
def api_served(served):
    lm, wf = served
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           max_slots=2, buckets=(8, 16, 32),
                           max_context=48, prefix_cache=True,
                           prefill_chunk=8, name="stream_api_t")
    api.initialize()
    yield api
    api.stop()


def test_http_stream_sse_id_exact_and_first_event_early(served,
                                                        api_served):
    lm, wf = served
    url = "http://127.0.0.1:%d/generate" % api_served.port
    prompt = _corpus(lm, 33, 6)
    expected = sampling.generate(wf, prompt, 12, temperature=0)
    ctype, events, t_first, t_total = _post_stream(
        url, {"prompt": prompt, "n_new": 12, "stream": True})
    assert "text/event-stream" in ctype
    toks = [t for ev in events if not ev.get("done")
            for t in ev["tokens"]]
    final = events[-1]
    assert toks == expected
    assert final.get("done") and final["tokens"] == expected
    assert "request_id" in final
    assert t_first is not None and t_first < t_total
    # TTFT histogram stamped a real sample for the streamed request
    from veles_tpu.telemetry.counters import histograms
    assert histograms.count("veles_serving_ttft_seconds") > 0
    # a sampled stream is id-exact too
    exp_s = sampling.generate(wf, prompt, 8, temperature=0.7, seed=9)
    _ct, events, _tf, _tt = _post_stream(
        url, {"prompt": prompt, "n_new": 8, "stream": True,
              "mode": "sample", "temperature": 0.7, "seed": 9})
    assert events[-1]["tokens"] == exp_s


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("finish", ["n_new", "eos_id", "prefill"])
def test_stream_equals_buffered_and_terminal_comes_last(
        served, api_served, finish, sampled):
    """A streamed request's token events, put together by their ``i``
    offsets, are the buffered answer; the terminal event is the last
    and the only one, however the row finishes: by its ``n_new``, by
    ``eos_id`` in the middle of a decode, or in its prefill."""
    lm, wf = served
    url = "http://127.0.0.1:%d/generate" % api_served.port
    body = {"prompt": _corpus(lm, 50 + sampled, 7),
            "n_new": 1 if finish == "prefill" else 14}
    if sampled:
        body.update(mode="sample", temperature=0.8, seed=13)

    def buffered():
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())["tokens"]
    answer = buffered()
    if finish == "eos_id":
        # a token that the answer first shows in its middle
        cut = next(j for j in range(3, 14) if answer[j] not in answer[:j])
        body["eos_id"] = answer[cut]
        answer = buffered()
        assert len(answer) == cut + 1
    _ct, events, _tf, _tt = _post_stream(url, dict(body, stream=True))
    assert [bool(ev.get("done")) for ev in events] \
        == [False] * (len(events) - 1) + [True]
    streamed = []
    for ev in events[:-1]:
        assert ev["i"] == len(streamed) and ev["tokens"]
        streamed.extend(ev["tokens"])
    assert streamed == answer == events[-1]["tokens"]
    # one event a step boundary, the prefill's first token its own
    assert len(events) - 1 == len(answer)


def test_stream_knob_off_answers_buffered(served, api_served):
    from veles_tpu.config import root
    lm, wf = served
    url = "http://127.0.0.1:%d/generate" % api_served.port
    prompt = _corpus(lm, 34, 5)
    root.common.serving.stream = False
    try:
        req = urllib.request.Request(
            url, data=json.dumps({"prompt": prompt, "n_new": 4,
                                  "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert "application/json" in r.headers.get("Content-Type")
            body = json.loads(r.read())
        assert body["tokens"] == sampling.generate(wf, prompt, 4,
                                                   temperature=0)
    finally:
        root.common.serving.stream = True


def test_router_stream_proxies_and_resumes_across_death(served,
                                                        monkeypatch):
    """THE streaming acceptance drill: a 2-replica fleet streams
    through the router; ``serve.replica_death`` kills the serving
    replica mid-stream; the failover RESUMES from the forwarded
    prefix — the client's wire sees every token exactly once, the
    final event matches the solo decode, and ``resumed_from``
    reports the carried prefix."""
    from veles_tpu.serving.router import FleetRouter
    lm, wf = served
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8, 16),
                             max_context=48,
                             name="sdeath_t_%d" % i)
            for i in range(2)]
    for api in apis:
        api.initialize()
    router = FleetRouter(
        ["127.0.0.1:%d" % api.port for api in apis],
        probe_interval=0.2, failure_threshold=1, retry_budget=2,
        attempt_timeout=60.0, name="stream_router_t").start()
    try:
        prompt = _corpus(lm, 35, 5)
        n_new = 12
        expected = sampling.generate(wf, prompt, n_new, temperature=0)
        # warm both replicas outside the armed window
        for api in apis:
            _post_stream("http://127.0.0.1:%d/generate" % api.port,
                         {"prompt": prompt, "n_new": 3,
                          "stream": True})
        ra0 = counters.get("veles_resume_attempts_total")
        monkeypatch.setenv("VELES_FAULTS",
                           "serve.replica_death:raise:after=4,times=1")
        _ct, events, _tf, _tt = _post_stream(
            "http://127.0.0.1:%d/generate" % router.port,
            {"prompt": prompt, "n_new": n_new, "stream": True},
            timeout=90.0)
        monkeypatch.setenv("VELES_FAULTS", "")
        toks = [t for ev in events if not ev.get("done")
                for t in ev["tokens"]]
        final = events[-1]
        assert toks == expected          # exactly once, in order
        assert final.get("done") and final["tokens"] == expected
        assert final.get("resumed_from", 0) >= 1
        assert counters.get("veles_resume_attempts_total") > ra0
    finally:
        router.stop()
        for api in apis:
            api.stop()


# -- registration hygiene ------------------------------------------------------

def test_check_counters_passes_with_prefix_counters():
    """The static registration pass (and its --docs mode) stays green
    with the prefix counters — tier-1-hooked here like the tensormon
    and fleet-tracing suites hook it."""
    import importlib
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    try:
        check_counters = importlib.import_module("check_counters")
        assert check_counters.main([]) == 0
        assert check_counters.main(["--docs"]) == 0
    finally:
        sys.path.pop(0)


