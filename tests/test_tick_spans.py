"""The serving tick timed from inside, and names for the device work.

What is pinned here:
- every phase of ``ContinuousEngine._tick`` is a span nested under
  ``serving.tick`` and an unlabelled histogram on ``/metrics``
  (``veles_serving_tick_<phase>_seconds``), the SSE write of a handler
  thread likewise; the phases cover the tick and none lies in another
  (``emit`` has two halves: the push under the next dispatch); with
  ``root.common.trace.spans`` false none is observed and the served
  tokens are the same;
- the three program names the benchmark finds programs by
  (``jit_step``, ``jit_prefill``, ``jit__train_step_fn``), the named
  scopes in their lowered text and the three flash kernels' names;
- ``telemetry/spans.py`` imports and records without jax;
- ``telemetry/devtime.py``'s tables of a capture (by program, by scope,
  idle gaps by host span) on a hand-made plane list;
- ``POST /generate/profile`` captures a running server.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn, prng
from veles_tpu.config import root
from veles_tpu.telemetry import devtime, spans
from veles_tpu.telemetry.counters import HISTOGRAMS, histograms

from conftest import import_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("admit", "prefill", "prepare", "dispatch", "device", "emit")
TICK_SPANS = tuple("serving.tick." + p for p in PHASES)


def _post(url, payload, timeout=120.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _stream(url, payload, timeout=120.0):
    """One streamed request: the tokens of its incremental events."""
    req = urllib.request.Request(
        url, data=json.dumps(dict(payload, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    tokens = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for line in r:
            if not line.startswith(b"data:"):
                continue
            event = json.loads(line[5:])
            if event.get("done"):
                assert event.get("code", 200) == 200, event
                break
            tokens.extend(event.get("tokens") or ())
    return tokens


def _closed_loop(url, prompts, n_new=16, clients=3):
    """A few callers, each sending its next streamed request when the
    last is complete; {request index: tokens}."""
    out, lock, todo = {}, threading.Lock(), list(enumerate(prompts))

    def caller():
        while True:
            with lock:
                if not todo:
                    return
                i, prompt = todo.pop(0)
            tokens = _stream(url, {"prompt": prompt, "n_new": n_new})
            with lock:
                out[i] = tokens

    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    return out


def _idle(api, timeout=30.0):
    """Wait until the tick that sent the last token has closed its
    span too: the client has its answer before the tick thread is back
    in the loop's wait."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        seen = spans.recorder.cursor()
        time.sleep(0.1)
        if api._engine.scheduler.busy_count() == 0 \
                and spans.recorder.cursor() == seen:
            return
    raise AssertionError("the engine did not go idle")


@pytest.fixture(scope="module")
def lm_wf():
    lm = import_model("char_lm")
    prng.seed_all(977)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                           dim=32, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return lm, wf


@pytest.fixture(scope="module")
def api(lm_wf, tmp_path_factory):
    _, wf = lm_wf
    api = vt.GenerationAPI(
        wf, port=0, engine="continuous", max_slots=3, buckets=(8, 16),
        max_context=48, decode_block=4, name="tick_spans_t",
        profile_dir=str(tmp_path_factory.mktemp("profiles")))
    api.initialize()
    yield api
    api.stop()


@pytest.fixture(scope="module")
def prompts(lm_wf):
    lm, _ = lm_wf
    return [[int(t) for t in lm.make_corpus(
        numpy.random.RandomState(40 + i), 6 + i)] for i in range(6)]


@pytest.fixture(scope="module")
def served(api, prompts):
    """The closed loop run once with spans on: (tokens by request, the
    span records of the run, the /metrics page after it)."""
    url = "http://127.0.0.1:%d/generate" % api.port
    cursor = spans.recorder.cursor()
    tokens = _closed_loop(url, prompts)
    _idle(api)
    records, _ = spans.recorder.records_since(cursor)
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % api.port, timeout=30) as r:
        page = r.read().decode()
    return tokens, records, page


# -- the tick's phases: histograms, coverage, nesting --------------------------

@pytest.mark.parametrize("histogram", sorted(spans.SPAN_HISTOGRAMS.values()))
def test_span_histogram_on_metrics_unlabelled(served, histogram):
    """Each span-fed histogram is registered (help, buckets from 50 us
    to 1 s) and on /metrics with unlabelled _sum and _count: what the
    benchmark's slice counters pick up."""
    assert histogram in HISTOGRAMS
    bounds = HISTOGRAMS[histogram]["buckets"]
    assert bounds[0] == 0.00005 and bounds[-1] == 1.0
    _, _, page = served
    total = re.search(r"^%s_sum (\S+)$" % histogram, page, re.M)
    count = re.search(r"^%s_count (\d+)$" % histogram, page, re.M)
    assert total and count, histogram
    assert int(count.group(1)) > 0 and float(total.group(1)) > 0


def test_tick_histograms_are_the_six_phases():
    assert sorted(spans.SPAN_HISTOGRAMS) == sorted(
        TICK_SPANS + ("serving.loop.wait", "serving.stream.write"))
    for name, histogram in spans.SPAN_HISTOGRAMS.items():
        assert histogram == "veles_%s_seconds" % name.replace(".", "_")
    # the parent and the inner prefill spans feed none, so that no
    # second is in two sums
    for name in ("serving.tick", "serving.prefill",
                 "serving.prefill_chunk", "serving.decode_step"):
        assert name not in spans.SPAN_HISTOGRAMS


def test_phases_cover_the_tick(served):
    """The phases' durations add up to at least 95 % of the time spent
    in the ticks that dispatched a decode step."""
    _, records, _ = served
    ticks = {r["sid"]: r for r in records if r["name"] == "serving.tick"}
    by_sid = {r["sid"]: r for r in records}
    inside = dict.fromkeys(ticks, 0.0)
    stepped = set()
    for r in records:
        if r["name"] not in TICK_SPANS:
            continue
        top = r
        while top["name"] != "serving.tick":
            top = by_sid[top["parent"]]
        inside[top["sid"]] += r["dur"]
        if r["name"] == "serving.tick.dispatch":
            stepped.add(top["sid"])
    assert len(stepped) >= 5
    whole = sum(ticks[sid]["dur"] for sid in stepped)
    covered = sum(inside[sid] for sid in stepped)
    assert covered <= whole
    assert covered >= 0.95 * whole, (covered, whole)


@pytest.mark.parametrize("name", TICK_SPANS + ("serving.decode_step",
                                               "serving.prefill"))
def test_span_nested_under_the_tick(served, name):
    _, records, _ = served
    by_sid = {r["sid"]: r for r in records}
    mine = [r for r in records if r["name"] == name]
    assert mine, name
    for r in mine:
        chain = []
        while r.get("parent") is not None:
            r = by_sid[r["parent"]]
            chain.append(r["name"])
        assert "serving.tick" in chain, (name, chain)
    if name in ("serving.tick.dispatch", "serving.tick.device"):
        # the step's own span stays their parent, in every mode
        assert all(by_sid[r["parent"]]["name"] == "serving.decode_step"
                   for r in mine)


def test_emit_has_two_halves_and_no_second_is_in_two_sums(served):
    """``serving.tick.emit`` is entered twice under a step's span: the
    push of an earlier step's tokens after the dispatch and before the
    device wait, record-and-finish after the wait. Where a step was
    dispatched with the one before it unread, the wait under its span is
    for that one (dispatch first); where the pipeline is drained the
    span holds no dispatch. No phase's span lies inside another
    phase's."""
    _, records, _ = served
    by_sid = {r["sid"]: r for r in records}
    for r in records:
        if r["name"] in TICK_SPANS:
            up = r
            while up.get("parent") is not None:
                up = by_sid[up["parent"]]
                assert up["name"] not in TICK_SPANS, (r["name"], up["name"])
    emits = [r for r in records if r["name"] == "serving.tick.emit"]
    assert all(by_sid[r["parent"]]["name"] in ("serving.decode_step",
                                                "serving.tick")
               for r in emits)
    steps = [r for r in records if r["name"] == "serving.decode_step"]
    ahead = drained = pushes = 0
    for step in steps:
        inside = sorted((r for r in records
                         if r.get("parent") == step["sid"]),
                        key=lambda r: r["ts"])
        names = [r["name"].rsplit(".", 1)[1] for r in inside]
        waits = names.count("device")
        assert waits <= 1 and names.count("dispatch") <= 1, names
        if waits:
            # whatever is pushed goes before the wait, the record after
            assert names[-2:] == ["device", "emit"], names
            assert set(names[:-2]) <= {"dispatch", "emit"}, names
            pushes += names[:-2].count("emit")
        if "dispatch" in names:
            assert names[0] == "dispatch"
            ahead += waits
        else:
            drained += 1
    # 6 requests of 16 tokens in chunks of 4 by 3 clients: most steps
    # are dispatched with the one before unread, a round's last is
    # read with no dispatch, and going rows' tokens are pushed under one
    assert ahead >= 6 and drained >= 1 and pushes >= 6


def test_tick_and_write_spans_carry_what_a_reader_needs(served):
    _, records, _ = served
    ticks = [r for r in records if r["name"] == "serving.tick"]
    assert ticks and all("active" in r for r in ticks)
    prefills = [r for r in records if r["name"] == "serving.tick.prefill"]
    assert sum(r["admitted"] for r in prefills) == 6
    assert len({r["tid"] for r in ticks}) == 1
    # a handler thread's write leaves no record (one an SSE event would
    # turn the ring over): its histogram and its annotation in a
    # capture carry it, one sample an event and the terminal one
    assert "serving.stream.write" in spans.UNRECORDED
    assert not [r for r in records if r["name"] == "serving.stream.write"]
    assert histograms.count("veles_serving_stream_write_seconds") >= 6 * 5
    # a duration comes from the monotonic clock, a start is epoch seconds
    assert all(r["dur"] >= 0 and r["ts"] > 1.5e9 for r in records)


def test_phases_stay_out_of_the_flight_recorder(served):
    """Nine phase spans a tick would cut the black box's horizon
    ninefold: their histograms keep them; the tick itself is noted."""
    from veles_tpu.telemetry.recorder import flight
    noted = {e.get("name") for e in flight.records("span")}
    assert "serving.tick" in noted and "serving.decode_step" in noted
    assert not noted & set(spans.SPAN_HISTOGRAMS)


def test_spans_off_nothing_observed_same_tokens(api, prompts, served):
    tokens_on, _, _ = served
    url = "http://127.0.0.1:%d/generate" % api.port
    before = {h: histograms.count(h)
              for h in spans.SPAN_HISTOGRAMS.values()}
    cursor = spans.recorder.cursor()
    root.common.trace.spans = False
    try:
        tokens_off = _closed_loop(url, prompts)
        _idle(api)
        records, _ = spans.recorder.records_since(cursor)
        after = {h: histograms.count(h)
                 for h in spans.SPAN_HISTOGRAMS.values()}
    finally:
        root.common.trace.spans = True
    assert tokens_off == tokens_on
    assert all(len(t) == 16 for t in tokens_off.values())
    # the loop's idle wait may close one span that began before the
    # switch; the tick's phases and the writes record and observe nothing
    assert not [r for r in records if r["name"].startswith("serving.")
                and r["name"] != "serving.loop.wait"]
    for histogram in after:
        if histogram != "veles_serving_loop_wait_seconds":
            assert after[histogram] == before[histogram], histogram


def test_begin_reads_four_counters_not_the_registry(monkeypatch):
    from veles_tpu.telemetry.counters import counters

    def no_copy():
        raise AssertionError("a span copied the whole registry")
    monkeypatch.setattr(counters, "snapshot", no_copy)
    counters.inc("veles_dispatches_total", 0)
    with spans.span("t.outer") as outer:
        counters.inc("veles_dispatches_total", 3)
        counters.inc("veles_decode_tokens_total", 5)
    assert outer.record["counters"] == {"veles_dispatches_total": 3}


def test_spans_import_and_record_without_jax():
    code = (
        "import sys\n"
        "sys.path.insert(0, %r)\n"
        "from veles_tpu.telemetry import spans\n"
        "with spans.span('serving.tick.emit', active=2):\n"
        "    pass\n"
        "rec = spans.recorder.records()[-1]\n"
        "assert rec['name'] == 'serving.tick.emit' and rec['dur'] >= 0\n"
        "from veles_tpu.telemetry.counters import histograms\n"
        "assert histograms.count('veles_serving_tick_emit_seconds') == 1\n"
        "assert 'jax' not in sys.modules, 'spans pulled jax in'\n"
        "print('ok')\n" % REPO)
    out = subprocess.run([sys.executable, "-c", code], timeout=120,
                         capture_output=True, text=True)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr


# -- names ---------------------------------------------------------------------

def _op_names(compiled):
    return set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))


@pytest.mark.parametrize("kind,module,scopes", [
    ("step", "jit_step",
     ("page_gather", "page_writeback", "sample", "blk0)/ffn", "blk1)/attn",
      "/embed/", "/head/")),
    ("prefill", "jit_prefill",
     ("page_writeback", "sample", "blk0/ffn", "blk0/attn_qkv", "blk0/rope",
      "blk1/norm2", "/embed/", "/head/")),
])
def test_engine_program_name_and_scopes(api, served, kind, module, scopes):
    """chipbench/metrics/decode_step_ms.py finds the decode program by
    the substring ``jit_step``: the program names are pinned here, not
    renamed. The scopes are the ones ``trace self-time`` groups by."""
    engine = api._engine
    key = next(k for k in engine._progs if k[0] == kind)
    compiled = engine._progs[key].compiled()
    text = compiled.as_text()
    assert re.search(r"^HloModule %s[, ]" % module, text, re.M)
    names = _op_names(compiled)
    for scope in scopes:
        assert any(scope in n for n in names), (scope, kind)


def test_train_program_name_and_scopes(lm_wf):
    lm, _ = lm_wf
    prng.seed_all(978)
    layers = ([{"type": "embedding", "vocab_size": lm.VOCAB, "dim": 32,
                "solver": "adam", "learning_rate": 0.003,
                "name": "embed"}]
              + [{"type": "transformer_block", "n_heads": 4,
                  "ffn_hidden": 64, "causal": True, "rope": True,
                  "solver": "adam", "learning_rate": 0.003,
                  "name": "blk%d" % i} for i in range(2)]
              + [{"type": "lm_head", "vocab_size": lm.VOCAB,
                  "solver": "adam", "learning_rate": 0.003,
                  "name": "head"}])
    wf = nn.StandardWorkflow(
        name="names-t", layers=layers,
        loader_unit=lm.CharLMLoader(None, n_train=128, n_valid=64,
                                    minibatch_size=64, name="chars"),
        loss_function="softmax_seq", steps_per_dispatch=1,
        decision_config=dict(max_epochs=1, fail_iterations=50))
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    step = wf.train_step
    compiled = step._jit_cache["train"]._jitted.lower(
        *step._jit_arg_shapes["train"]).compile()
    assert re.search(r"^HloModule jit__train_step_fn[, ]",
                     compiled.as_text(), re.M)
    names = _op_names(compiled)
    for scope in ("jvp(forward)/blk0/ffn", "jvp(forward)/embed/embed",
                  "jvp(forward)/head/head", "jvp(loss)",
                  "transpose(jvp(forward))/blk1/attn_qkv",
                  "optimizer/blk0", "optimizer/head", "accumulate"):
        assert any(scope in n for n in names), scope
    # and the reader's view of them
    assert devtime.scope_of(
        "jit(_train_step_fn)/transpose(jvp(forward))/blk1/attn_qkv/"
        "dot_general") == "backward/blk1"


@pytest.mark.parametrize("kernel", ["veles_flash_fwd",
                                    "veles_flash_bwd_dkv",
                                    "veles_flash_bwd_dq"])
def test_flash_kernels_are_named(kernel):
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.flash_attention import flash_attention
    q = jnp.ones((1, 128, 2, 32), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=True).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert kernel in text


def test_fused_fc_kernel_is_named():
    import inspect
    from veles_tpu.ops import fused_fc
    assert 'name="veles_fused_fc"' in inspect.getsource(fused_fc)


# -- devtime's tables on a hand-made capture -----------------------------------

@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/while/body/closed_call/vmap(blk3)/ffn/dot_general",
     "blk3/ffn"),
    ("jit(step)/vmap(page_gather)/jit(_take)/gather", "page_gather"),
    ("jit(step)/while/body/closed_call/sample/vmap(jit(_gumbel))/"
     "jit(_uniform)/add", "sample"),
    ("jit(_train_step_fn)/jvp(forward)/blk0/attn/veles_flash_fwd/"
     "pallas_call", "forward/blk0"),
    ("jit(_train_step_fn)/transpose(jvp(forward))/blk0/ffn/dot_general",
     "backward/blk0"),
    ("jit(_train_step_fn)/transpose(jvp(loss))/jit(log_softmax)/sub",
     "backward/loss"),
    ("jit(_train_step_fn)/optimizer/head/jit(_where)/select_n",
     "optimizer/head"),
    ("jit(step)/vmap(blk0)/attn/reshape;vmap(blk0)/attn_out/dot_general",
     "blk0/attn"),
    ("jit(prefill)/jit(_take)/gather", devtime.NO_SCOPE),
    ("", devtime.NO_SCOPE),
])
def test_scope_of(op_name, scope):
    assert devtime.scope_of(op_name) == scope


def _capture():
    """One device plane, two calls of ``jit_step`` (ops 0-40 and 100-140
    us) with a Pallas call in each, and the dispatching thread's spans:
    the first idle gap (40-100 us) lies inside ``serving.tick.emit``,
    the second (140-200) across ``emit`` and ``admit`` with its middle
    in ``admit``, the third (240-300) under no span."""
    us = 1000
    kernel = ('%veles_flash_fwd.3 = f32[8,128]{1,0} custom-call(f32[8,128] '
              '%q), custom_call_target="tpu_custom_call"')
    ops = []
    for t0 in (0, 100, 200):
        ops += [("%fusion.1 = f32[8]{0} fusion(%p)", t0 * us, 10 * us,
                 "jit(step)/vmap(blk0)/ffn/dot_general"),
                ("%fusion.2 = f32[8]{0} fusion(%p)", (t0 + 10) * us,
                 10 * us, "jit(step)/vmap(blk0)/attn/exp"),
                (kernel, (t0 + 20) * us, 15 * us,
                 "jit(step)/vmap(blk0)/attn/veles_flash_fwd/pallas_call"),
                ("%copy.4 = f32[8]{0} copy(%p)", (t0 + 35) * us, 5 * us,
                 "")]
    ops.append(("%fusion.9 = f32[8]{0} fusion(%p)", 300 * us, 10 * us,
                "jit(prefill)/blk0/ffn/dot_general"))
    modules = [("jit_step(123)", 0, 40 * us, ""),
               ("jit_step(123)", 100 * us, 40 * us, ""),
               ("jit_step(123)", 200 * us, 40 * us, ""),
               ("jit_prefill(7)", 300 * us, 10 * us, "")]
    engine = [("serving.tick", 30 * us, 200 * us, ""),
              ("serving.tick.emit", 35 * us, 130 * us, ""),
              ("serving.tick.admit", 168 * us, 40 * us, "")]
    handler = [("serving.stream.write", 45 * us, 200 * us, "")]
    return [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops},
                {"name": "XLA Modules", "events": modules}]},
            {"name": "/host:CPU", "lines": [
                {"name": "engine", "events": engine},
                {"name": "handler", "events": handler}]}]


def test_capture_tables_programs_scopes_gaps():
    s = devtime.summarize_capture(_capture())
    assert s["programs"]["jit_step"] == [3, pytest.approx(120e-6)]
    assert s["programs"]["jit_prefill"] == [1, pytest.approx(10e-6)]
    assert s["busy_s"] == pytest.approx(130e-6)
    assert s["window_s"] == pytest.approx(310e-6)
    sc = s["scopes"]
    assert sc[("jit_step", "blk0/ffn")] == pytest.approx(30e-6)
    assert sc[("jit_step", "blk0/attn")] == pytest.approx(30e-6)
    # a Pallas call stands under its kernel's name
    assert sc[("jit_step", "veles_flash_fwd")] == pytest.approx(45e-6)
    assert sc[("jit_step", devtime.NO_SCOPE)] == pytest.approx(15e-6)
    assert sc[("jit_prefill", "blk0/ffn")] == pytest.approx(10e-6)
    assert s["unnamed"] == {"copy.4": pytest.approx(15e-6)}
    # gaps by the innermost span of the dispatching thread (the line
    # with the most spans; the handler's long write is not asked)
    assert s["gaps"] == {
        "serving.tick.emit": [1, pytest.approx(60e-6)],
        "serving.tick.admit": [1, pytest.approx(60e-6)],
        devtime.NO_SPAN: [1, pytest.approx(60e-6)]}


def test_capture_tables_deeper_and_as_text():
    s = devtime.summarize_capture(_capture(), depth=1)
    assert s["scopes"][("jit_step", "blk0")] == pytest.approx(60e-6)
    text = "\n".join(devtime.format_capture(
        devtime.summarize_capture(_capture())))
    assert "jit_step" in text and "3 call(s)" in text
    assert "blk0/ffn" in text and "veles_flash_fwd" in text
    assert "serving.tick.emit" in text and devtime.NO_SPAN in text
    # 130 of 130 us of operations, 15 of them unnamed
    assert "88.5 % of device time is under a named scope" in text
    assert "copy.4" in text


def test_capture_without_host_spans_or_device():
    planes = [p for p in _capture() if p["name"].startswith("/device")]
    s = devtime.summarize_capture(planes)
    assert s["gaps"] == {devtime.NO_SPAN: [3, pytest.approx(180e-6)]}
    empty = devtime.summarize_capture([])
    assert empty["programs"] == {} and empty["busy_s"] == 0.0
    assert devtime.format_capture(empty)


# -- a profile a server can be asked for ---------------------------------------

def test_profile_endpoint_refused_without_a_directory(lm_wf):
    _, wf = lm_wf
    plain = vt.GenerationAPI(wf, port=0, engine="continuous", max_slots=2,
                             buckets=(8, 16), max_context=48,
                             name="no_profile_t")
    assert plain.profile(1)[0] == 403


@pytest.mark.parametrize("seconds", [0, -1, "3", True, None])
def test_profile_endpoint_bad_seconds(api, seconds):
    url = "http://127.0.0.1:%d/generate/profile" % api.port
    code, body = _post(url, {"seconds": seconds})
    assert code == 400 and "seconds" in body["error"]


def test_profile_endpoint_captures_a_running_server(api, prompts, served):
    base = "http://127.0.0.1:%d/generate" % api.port
    answers = {}

    def ask(key, seconds):
        answers[key] = _post(base + "/profile", {"seconds": seconds})

    first = threading.Thread(target=ask, args=("first", 1.5), daemon=True)
    first.start()
    # while it runs: a second asker is told so, and requests are served
    deadline = time.time() + 30
    while not api._profile_lock.locked() and time.time() < deadline:
        time.sleep(0.01)
    assert api._profile_lock.locked()
    assert _post(base + "/profile", {"seconds": 1})[0] == 409
    tokens = _closed_loop(base, prompts[:3])
    first.join(120)
    assert not first.is_alive()
    code, body = answers["first"]
    assert code == 200 and body["seconds"] == 1.5
    assert os.path.dirname(body["dir"]) == api.profile_dir
    assert tokens == {i: served[0][i] for i in range(3)}
    capture = devtime.find_capture(body["dir"])
    assert capture is not None
    host = [p for p in devtime.load_capture(capture)
            if p["name"].startswith(devtime.HOST_PLANE)]
    names = {e[0] for p in host for ln in p["lines"] for e in ln["events"]}
    assert "serving.tick" in names and "serving.tick.device" in names
    assert "serving.stream.write" in names
    # the CPU's capture has no device plane: the tables come out empty,
    # and the tool says so without failing
    from veles_tpu.__main__ import main
    assert main(["trace", "self-time", body["dir"]]) == 0
    # a second capture gets a directory of its own
    code, again = _post(base + "/profile", {"seconds": 0.2})
    assert code == 200 and again["dir"] != body["dir"]
