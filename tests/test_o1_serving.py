"""O(1)-state serving lane (ISSUE 16): the recurrent slot pool.

The contract under test: (1) a recurrent stack (Embedding →
SSM/LSTM → LMHead) serves on the continuous plane with a FIXED
per-slot state tensor — no page table, state bytes constant whatever
the token count — through exactly two fixed-shape programs (chunked
scan prefill + recurrent decode); (2) every id-exactness guarantee of
the paged lane carries over verbatim: pooled == solo, resume ==
uninterrupted, cache-hit == cache-miss, greedy AND sampled; (3) the
state-checkpoint prefix cache restores block-boundary snapshots and
degrades to a full re-scan (counted, id-exact) under the
``serve.state_restore`` / ``serve.state_checkpoint`` fault points;
(4) the request plane — GenerationAPI, SSE streaming, serve-artifact
AOT — hosts the lane end-to-end, including the equal-HBM slot
multiplier the bench gate stamps.

Budget discipline: one tiny TRAINED lstm char_lm (the trains-AND-
serves acceptance) plus an initialized ssm/transformer pair, all
module-scoped.
"""
import json
import urllib.error
import urllib.request

import numpy
import pytest

import veles_tpu as vt
from veles_tpu.error import VelesError
from veles_tpu.serving import (O1_COUNTERS, RecurrentEngine, Ticket,
                               fold_resume, generate_recurrent,
                               split_recurrent_stack)
from veles_tpu.serving.engine import ContinuousEngine, make_request
from veles_tpu.telemetry.counters import counters

from conftest import import_model

PROMPT = [1, 5, 3, 2, 4, 6, 1, 2]


@pytest.fixture(scope="module")
def lstm_wf():
    """Trained, not just initialized: the acceptance bar is that the
    LSTM workflow TRAINS (BPTT through the scan) and then serves."""
    lm = import_model("char_lm")
    from veles_tpu import prng
    prng.seed_all(2026)
    wf = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                           dim=32, n_train=64, n_valid=32,
                           arch="lstm")
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return lm, wf


@pytest.fixture(scope="module")
def ssm_wf():
    lm = import_model("char_lm")
    from veles_tpu import prng
    prng.seed_all(2027)
    wf = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                           dim=32, n_train=64, n_valid=32, arch="ssm")
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    return lm, wf


@pytest.fixture(scope="module")
def paged_wf():
    lm = import_model("char_lm")
    from veles_tpu import prng
    prng.seed_all(2028)
    wf = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                           dim=32, n_train=64, n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    return lm, wf


def _engine(wf, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_context", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("name", "o1t_%d" % numpy.random.randint(1 << 30))
    return RecurrentEngine(wf, **kw)


def _post(url, payload, timeout=120.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


# -- stack admission -----------------------------------------------------------

def test_split_stack_accepts_recurrent_rejects_transformer(
        lstm_wf, paged_wf):
    _lm, wf = lstm_wf
    stack = split_recurrent_stack(list(wf.forwards))
    assert stack["blocks"] and all(hasattr(b, "step_state")
                                   for b in stack["blocks"])
    _lm2, twf = paged_wf
    with pytest.raises(VelesError):
        split_recurrent_stack(list(twf.forwards))
    # and the paged engine refuses the recurrent stack right back —
    # the two VelesErrors are what GenerationAPI's fallback chain
    # pivots on
    with pytest.raises(VelesError):
        ContinuousEngine(wf, buckets=(16,), max_context=32,
                         name="o1t_reject")


def test_engine_rejects_spec_and_beam_modes(lstm_wf):
    _lm, wf = lstm_wf
    e = _engine(wf)
    for mode in ("speculative", "beam"):
        req = make_request(PROMPT, 4, mode=mode)
        assert e.accepts(req) is not None
    assert e.accepts(make_request(PROMPT, 4)) is None
    # over-limit lands on the window worker, not a crash
    assert e.accepts(make_request(list(range(80)), 4)) is not None


# -- pooled == solo, both families, both modes ---------------------------------

@pytest.mark.parametrize("temperature,seed",
                         [(0.0, 0), (0.8, 11)],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", ["lstm", "ssm"])
def test_pool_matches_solo_id_exact(lstm_wf, ssm_wf, family,
                                    temperature, seed):
    _lm, wf = lstm_wf if family == "lstm" else ssm_wf
    mode = "sample" if temperature > 0 else "greedy"
    solo = [generate_recurrent(wf, PROMPT, 10, temperature=temperature,
                               seed=seed + i, mode=mode)
            for i in range(3)]
    e = _engine(wf).start()
    try:
        out = e.serve([make_request(PROMPT, 10, temperature=temperature,
                                    seed=seed + i, mode=mode)
                       for i in range(3)])
    finally:
        e.stop()
    assert out == solo
    assert e.compiled_live == 2 and e.programs_bound() == 2


# -- fixed state bytes (the O(1) claim) ----------------------------------------

def test_state_bytes_constant_vs_token_count(lstm_wf):
    _lm, wf = lstm_wf
    e = _engine(wf).start()
    try:
        e.serve([make_request(PROMPT, 4)])
        st_short = e.stats()
        e.serve([make_request(PROMPT, 40)])
        st_long = e.stats()
    finally:
        e.stop()
    # the whole pool is slots × state — token count must not move it
    assert st_short["kv_pool_bytes"] == st_long["kv_pool_bytes"] > 0
    assert st_long["state_bytes_per_slot"] > 0
    assert st_long["pages_total"] == 0 and st_long["pages_in_use"] == 0
    assert st_long["slot_kind"] == "state"
    assert e.scheduler.slot_kind == "state"


# -- token-level failover resume -----------------------------------------------

@pytest.mark.parametrize("temperature,seed",
                         [(0.0, 0), (0.9, 41)],
                         ids=["greedy", "sampled"])
def test_resume_is_id_exact(lstm_wf, monkeypatch, temperature, seed):
    _lm, wf = lstm_wf
    mode = "sample" if temperature > 0 else "greedy"
    n_new = 12
    solo = generate_recurrent(wf, PROMPT, n_new,
                              temperature=temperature, seed=seed,
                              mode=mode)
    req = make_request(PROMPT, n_new, temperature=temperature,
                       seed=seed, mode=mode)
    e1 = _engine(wf, name="o1t_resume_a_" + mode).start()
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
    e2 = _engine(wf, name="o1t_resume_b_" + mode).start()
    try:
        t2 = Ticket(mode=mode)
        assert e2.submit(fold_resume(req, t1.progress), t2)
        assert t2.event.wait(60)
        assert t2.error is None, t2.error
        assert t1.progress + t2.result["tokens"] == solo
        assert counters.get("veles_resume_tokens_total") - rt == k
    finally:
        e2.stop()


# -- state-checkpoint prefix cache + chaos -------------------------------------

def _long_prompt(lm, n=24):
    return [int(t) for t in lm.make_corpus(numpy.random.RandomState(5),
                                           n)]


def test_state_cache_restore_is_id_exact(lstm_wf):
    lm, wf = lstm_wf
    prompt = _long_prompt(lm)
    cold = generate_recurrent(wf, prompt, 8)
    e = _engine(wf, state_cache=True).start()
    try:
        first = e.serve([make_request(prompt, 8)])[0]
        st = e.stats()
        assert st["state_checkpoints"] > 0
        assert st["state_cache_blocks"] > 0
        assert st["state_cache_bytes"] > 0
        r0 = counters.get("veles_o1_state_restores_total")
        again = e.serve([make_request(prompt, 8)])[0]
        st = e.stats()
        assert st["state_restores"] >= 1
        assert counters.get("veles_o1_state_restores_total") > r0
        assert e.prefix_requests >= 1
    finally:
        e.stop()
    # cache hit, cache miss, solo: one answer
    assert first == again == cold


def test_chaos_state_restore_raise_degrades_to_rescan(lstm_wf,
                                                      monkeypatch):
    lm, wf = lstm_wf
    prompt = _long_prompt(lm)
    e = _engine(wf, state_cache=True).start()
    try:
        warm = e.serve([make_request(prompt, 6)])[0]
        r0 = counters.get("veles_o1_state_rescans_total")
        monkeypatch.setenv("VELES_FAULTS",
                           "serve.state_restore:raise:times=1")
        hit = e.serve([make_request(prompt, 6)])[0]
        monkeypatch.delenv("VELES_FAULTS")
        assert counters.get("veles_o1_state_rescans_total") == r0 + 1
        assert e.stats()["state_rescans"] >= 1
    finally:
        e.stop()
    assert hit == warm


def test_chaos_state_restore_corrupt_still_id_exact(lstm_wf,
                                                    monkeypatch):
    lm, wf = lstm_wf
    prompt = _long_prompt(lm)
    e = _engine(wf, state_cache=True).start()
    try:
        warm = e.serve([make_request(prompt, 6)])[0]
        fi = counters.get("veles_faults_injected_total")
        monkeypatch.setenv("VELES_FAULTS",
                           "serve.state_restore:corrupt:times=1")
        hit = e.serve([make_request(prompt, 6)])[0]
        monkeypatch.delenv("VELES_FAULTS")
        assert counters.get("veles_faults_injected_total") > fi
    finally:
        e.stop()
    # a rotted lookup key can only SHORTEN the match — token equality
    # is the authority, the answer must not move
    assert hit == warm


def test_chaos_state_checkpoint_raise_skips_caching(lstm_wf,
                                                    monkeypatch):
    lm, wf = lstm_wf
    prompt = _long_prompt(lm)
    oracle = generate_recurrent(wf, prompt, 6)
    e = _engine(wf, state_cache=True).start()
    try:
        monkeypatch.setenv("VELES_FAULTS",
                           "serve.state_checkpoint:raise:times=1")
        out = e.serve([make_request(prompt, 6)])[0]
        monkeypatch.delenv("VELES_FAULTS")
        st = e.stats()
        assert st["state_cache_blocks"] == 0
        assert st["state_checkpoints"] == 0
    finally:
        e.stop()
    assert out == oracle


# -- request plane: GenerationAPI, SSE, artifact -------------------------------

@pytest.fixture(scope="module")
def api_served(lstm_wf):
    _lm, wf = lstm_wf
    api = vt.GenerationAPI(wf, port=0, engine="recurrent",
                           max_slots=3, max_context=64, page_size=8,
                           state_cache=True, name="o1t_api")
    api.initialize()
    yield api
    api.stop()


def test_api_serves_recurrent_engine(lstm_wf, api_served):
    _lm, wf = lstm_wf
    api = api_served
    assert type(api._engine).__name__ == "RecurrentEngine"
    ref = generate_recurrent(wf, PROMPT, 8)
    url = "http://127.0.0.1:%d/generate" % api.port
    code, body, _ = _post(url, {"prompt": PROMPT, "n_new": 8})
    assert code == 200
    assert body["tokens"] == ref and body["engine"] == "recurrent"
    code, body, _ = _post(url, {"prompt": PROMPT, "n_new": 8,
                                "mode": "sample", "temperature": 0.8,
                                "seed": 3})
    assert code == 200
    assert body["tokens"] == generate_recurrent(
        wf, PROMPT, 8, temperature=0.8, seed=3, mode="sample")


def test_api_streams_sse_id_exact(lstm_wf, api_served):
    _lm, wf = lstm_wf
    api = api_served
    ref = generate_recurrent(wf, PROMPT, 8)
    url = "http://127.0.0.1:%d/generate" % api.port
    req = urllib.request.Request(
        url, data=json.dumps({"prompt": PROMPT, "n_new": 8,
                              "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=120) as r:
        assert "text/event-stream" in r.headers.get("Content-Type", "")
        for line in r:
            line = line.strip()
            if line.startswith(b"data:"):
                events.append(json.loads(line[5:]))
    streamed = [t for ev in events if not ev.get("done")
                for t in ev["tokens"]]
    assert streamed == ref
    assert events[-1].get("done") and events[-1]["tokens"] == ref


def test_api_metrics_report_state_not_pages(lstm_wf, api_served):
    lm, _wf = lstm_wf
    # a repeated long prompt touches the checkpoint AND restore
    # counters (the registry only renders touched counters)
    url = "http://127.0.0.1:%d/generate" % api_served.port
    prompt = _long_prompt(lm)
    for _ in range(2):
        code, _body, _h = _post(url, {"prompt": prompt, "n_new": 4})
        assert code == 200
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % api_served.port,
            timeout=30) as r:
        text = r.read().decode()
    for gauge in ("veles_o1_state_bytes_per_slot",
                  "veles_o1_state_cache_blocks",
                  "veles_o1_state_cache_bytes",
                  "veles_o1_checkpoint_interval"):
        assert gauge in text, gauge
    # pageless slots must NOT feed the fleet's page-occupancy math
    assert "veles_serving_pages_total" not in text
    for name in ("veles_o1_state_checkpoints_total",
                 "veles_o1_state_restores_total",
                 "veles_o1_state_restored_tokens_total"):
        assert name in O1_COUNTERS and name in text, name


def test_api_transformer_recurrent_falls_back_to_window(paged_wf):
    _lm, wf = paged_wf
    api = vt.GenerationAPI(wf, port=0, engine="recurrent",
                           max_slots=2, max_context=48,
                           name="o1t_fallback")
    api.initialize()
    try:
        # the O(1) lane refuses an attention stack; the window worker
        # answers instead of an outage
        assert api._engine is None
    finally:
        api.stop()


def test_artifact_roundtrip_zero_compiles(lstm_wf, tmp_path):
    _lm, wf = lstm_wf
    from veles_tpu.export.serve_artifact import export_serve_artifact
    path = str(tmp_path / "o1_art")
    export_serve_artifact(wf, path, max_slots=3, max_context=64,
                          decode_block=1, page_size=8)
    with open(path + "/contents.json") as f:
        serving = json.load(f)["serving"]
    assert serving["artifact_version"] == 4
    assert serving["signature"]["kind"] == "recurrent"
    assert sorted(serving["programs"]) == ["rscan", "rstep"]
    reqs = [make_request(PROMPT, 8),
            make_request(PROMPT, 8, temperature=0.7, seed=5,
                         mode="sample")]
    live = _engine(wf).start()
    try:
        ref = live.serve([dict(r) for r in reqs])
    finally:
        live.stop()
    aot = _engine(wf, artifact=path).start()
    try:
        out = aot.serve([dict(r) for r in reqs])
        assert aot.artifact_mode
        assert aot.compiled_live == 0
    finally:
        aot.stop()
    assert out == ref


# -- the HBM headline ----------------------------------------------------------

def test_slots_at_equal_hbm_multiplier(lstm_wf, ssm_wf, paged_wf):
    """The lane's reason to exist: per-slot state is ≥4× smaller than
    the paged transformer's per-slot KV allotment at the same
    geometry, so the same HBM holds ≥4× the concurrent slots."""
    _lm, twf = paged_wf
    paged = ContinuousEngine(twf, max_slots=3, buckets=(16, 32, 64),
                             max_context=64, page_size=8,
                             name="o1t_hbm_paged")
    params = paged._prepare_params()
    paged._ensure_pool(params)
    import jax
    kv_per_slot = sum(
        int(leaf.nbytes)
        for leaf in jax.tree_util.tree_leaves(paged._caches)
    ) // paged.max_slots
    assert kv_per_slot > 0
    for _lm2, wf in (lstm_wf, ssm_wf):
        e = _engine(wf)
        per_slot = e.state_bytes_per_slot()
        assert per_slot > 0
        multiplier = kv_per_slot / per_slot
        assert multiplier >= 4.0, \
            "equal-HBM multiplier %.1f < 4 (kv=%d state=%d)" \
            % (multiplier, kv_per_slot, per_slot)
