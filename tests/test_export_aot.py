"""AOT serving artifacts (export/serve_artifact.py) + package format
v3 (export/package.py quant blocks).

The contracts under test: an exported artifact serves id-exact greedy
(and sampled) tokens vs the live-jit engine with ZERO jit compiles at
initialize+serve; a corrupt / injected-fault / mismatched artifact
falls back to live jit with a counted warning and the API keeps
serving; plain packages still stamp format_version 2 and import/run
everywhere; quantized packages stamp v3, round-trip their quant
metadata and dequantize on import."""
import json
import os
import urllib.request

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn, prng
from veles_tpu.export import package_export, package_import, run_package
from veles_tpu.export.serve_artifact import (export_serve_artifact,
                                             load_serve_programs)
from veles_tpu.error import VelesError
from veles_tpu.loader import FullBatchLoader
from veles_tpu.serving import ContinuousEngine
from veles_tpu.serving.engine import make_request
from veles_tpu.telemetry.counters import counters

from conftest import import_model

KNOBS = dict(max_slots=3, buckets=(8, 16), max_context=48)


@pytest.fixture(scope="module")
def served_artifact(tmp_path_factory):
    """Trained LM + a serve-artifact exported with the same knobs the
    engines under test boot with."""
    lm = import_model("char_lm")
    prng.seed_all(971)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                           dim=32, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    art = str(tmp_path_factory.mktemp("aot") / "artifact")
    export_serve_artifact(wf, art, **KNOBS)
    return lm, wf, art


def _prompt(lm, seed, length=10):
    return [int(t) for t in
            lm.make_corpus(numpy.random.RandomState(seed), length)]


def _reqs(lm):
    return [make_request(_prompt(lm, 80 + s, 5 + s % 6), 6,
                         temperature=0.7 if s % 2 else 0.0,
                         seed=80 + s)
            for s in range(4)]


# -- artifact contents ---------------------------------------------------------

def test_artifact_is_a_v3_package_with_serving_block(served_artifact):
    lm, wf, art = served_artifact
    with open(os.path.join(art, "contents.json")) as fin:
        contents = json.load(fin)
    assert contents["format_version"] == 3
    serving = contents["serving"]
    # v6: the decode step takes the tokens of the step before back as it
    # gave them ("step_tokens" in the signature)
    assert serving["artifact_version"] == 6
    assert serving["signature"]["step_tokens"] == "device"
    assert sorted(serving["programs"]) == ["decode", "prefill_16",
                                           "prefill_8"]
    for fname in serving["programs"].values():
        assert os.path.getsize(os.path.join(art, fname)) > 0
    sig = serving["signature"]
    assert sig["buckets"] == [8, 16]
    assert sig["max_slots"] == 3
    assert sig["quant_weights"] is False
    # the artifact is still a readable package (program-only: the
    # params stay runtime inputs, so it survives further training)
    assert package_import(art)["contents"]["units"] == []


# -- artifact serving: id-exact, zero compiles ---------------------------------

def test_artifact_serves_id_exact_with_zero_compiles(served_artifact):
    lm, wf, art = served_artifact
    from veles_tpu.nn import sampling
    reqs = _reqs(lm)
    live = ContinuousEngine(wf, name="aot_live", **KNOBS).start()
    try:
        ref = live.serve(list(reqs))
        assert live.compiled_live >= 2          # the cost AOT deletes
    finally:
        live.stop()
    loads0 = counters.get("veles_artifact_loads_total")
    compiles0 = counters.get("veles_compiles_total")
    compile_s0 = counters.get("veles_serving_compile_seconds_total")
    engine = ContinuousEngine(wf, artifact=art, name="aot_eng",
                              **KNOBS).start()
    try:
        assert engine.artifact_mode
        out = engine.serve(list(reqs))
        # greedy AND sampled answers equal the live engine AND the
        # scan decoder — the artifact is the same program, serialized
        assert out == ref
        for r, toks in zip(reqs, out):
            assert toks == sampling.generate(
                wf, r["prompt"], r["n_new"],
                temperature=r["temperature"], seed=r["seed"])
        st = engine.stats()
        assert st["artifact_mode"] == 1
        assert st["compiled_live"] == 0
        assert engine.programs_built <= engine.programs_bound()
        assert engine.programs_bound() == len(engine.buckets) + 1
    finally:
        engine.stop()
    assert counters.get("veles_artifact_loads_total") == loads0 + 1
    assert counters.get("veles_compiles_total") == compiles0
    assert counters.get("veles_serving_compile_seconds_total") == \
        compile_s0


def test_artifact_keeps_the_whole_view_and_compiles_nothing(
        served_artifact, tmp_path):
    """Where a live engine has a ladder of two, the artifact holds the
    step at the whole view alone: the engine that loads it keeps that
    one rung, serves the same tokens and compiles nothing."""
    lm, wf, _ = served_artifact
    knobs = dict(max_slots=3, buckets=(8, 16), max_context=64,
                 page_size=4)
    art = str(tmp_path / "artifact")
    export_serve_artifact(wf, art, **knobs)
    reqs = [make_request(_prompt(lm, 90, 5), 40, seed=1),
            make_request(_prompt(lm, 91, 9), 12, temperature=0.7,
                         seed=2)]
    live = ContinuousEngine(wf, name="aot_lad_live", **knobs).start()
    try:
        ref = live.serve(list(reqs))
        assert live.view_ladder == (16, 8)
        assert live.programs_bound() == 2 + 2
        assert live.stats()["view_share"] < 1.0
    finally:
        live.stop()
    engine = ContinuousEngine(wf, artifact=art, name="aot_lad",
                              **knobs).start()
    try:
        assert engine.artifact_mode
        assert engine.view_ladder == (16,)
        assert engine.programs_bound() == 2 + 1
        assert engine.serve(list(reqs)) == ref
        assert engine.compiled_live == 0
        assert engine.stats()["view_share"] == 1.0
        assert engine.programs_built <= engine.programs_bound()
    finally:
        engine.stop()


def test_artifact_step_runs_ahead_and_compiles_nothing(served_artifact):
    """The exported step has the live step's signature (the tokens of
    the step before among its inputs): an engine on the artifact
    dispatches step n+1 before it reads step n, serves what its twin
    held to the serial
    order serves and what the scan decoder gives, a row ending on an
    ``eos_id`` among them, and compiles nothing."""
    import ahead_drill
    lm, wf, art = served_artifact
    reqs = _reqs(lm)
    ender, short = ahead_drill.ender(
        lambda req: ahead_drill.solo(wf, req),
        lambda i: _prompt(lm, 700 + 10 * i, 6), 6, 0.7, seed=5, earliest=2)
    reqs.append(ender)
    compiles0 = counters.get("veles_compiles_total")
    answers = []
    for engine in (
            ContinuousEngine(wf, artifact=art, name="aot_ahead", **KNOBS),
            ahead_drill.hold_serial(ContinuousEngine(
                wf, artifact=art, name="aot_serial", **KNOBS))):
        engine._load_artifact()
        assert engine.artifact_mode
        events = ahead_drill.record_order(engine)
        got = ahead_drill.serve_by_ticks(engine, reqs)
        assert got[-1] == short
        for req, toks in zip(reqs, got):
            assert toks == ahead_drill.solo(wf, req)
        answers.append((got, ahead_drill.ahead_of(events)))
        assert engine.compiled_live == 0
        engine.stop()
    assert answers[0][0] == answers[1][0]
    assert answers[0][1] > 0 and answers[1][1] == 0
    assert counters.get("veles_compiles_total") == compiles0


# -- fallback paths ------------------------------------------------------------

def _fallback_engine(wf, art, name):
    fails0 = counters.get("veles_artifact_load_failures_total")
    engine = ContinuousEngine(wf, artifact=art, name=name,
                              **KNOBS).start()
    assert not engine.artifact_mode
    assert counters.get("veles_artifact_load_failures_total") == \
        fails0 + 1
    return engine


def test_corrupt_artifact_falls_back_to_live_jit(served_artifact,
                                                 tmp_path):
    import shutil
    lm, wf, art = served_artifact
    from veles_tpu.nn import sampling
    bad = str(tmp_path / "bad_art")
    shutil.copytree(art, bad)
    blob = os.path.join(bad, "serve_decode.bin")
    with open(blob, "rb") as fin:
        raw = fin.read()
    with open(blob, "wb") as fout:
        fout.write(raw[: len(raw) // 2])
    engine = _fallback_engine(wf, bad, "aot_corrupt")
    try:
        req = make_request(_prompt(lm, 90), 5)
        assert engine.serve([req])[0] == sampling.generate(
            wf, req["prompt"], req["n_new"], temperature=0)
    finally:
        engine.stop()


def test_missing_and_mismatched_artifacts_fall_back(served_artifact,
                                                    tmp_path):
    lm, wf, art = served_artifact
    engine = _fallback_engine(wf, str(tmp_path / "nowhere"),
                              "aot_missing")
    engine.stop()
    # geometry mismatch: an engine with different buckets must refuse
    # the shape-committed programs, not run them on reinterpreted pools
    fails0 = counters.get("veles_artifact_load_failures_total")
    engine = ContinuousEngine(wf, artifact=art, max_slots=3,
                              buckets=(8, 32), max_context=48,
                              name="aot_geom").start()
    try:
        assert not engine.artifact_mode
        assert counters.get("veles_artifact_load_failures_total") == \
            fails0 + 1
    finally:
        engine.stop()
    with pytest.raises(VelesError, match="different"):
        load_serve_programs(art, {"buckets": [8, 32]})


@pytest.mark.parametrize("version,lacks", [
    (4, ("tp", "mesh", "step_tokens")), (5, ("step_tokens",))])
def test_v4_artifact_refused_with_counted_live_fallback(
        served_artifact, tmp_path, version, lacks):
    """Format-migration contract (v4 -> v5 -> v6): an artifact exported
    before the mesh geometry ("tp"/"mesh") joined the signature, or
    before the decode step kept its tokens on the device
    ("step_tokens": its step has another calling convention), is
    REFUSED, counted in veles_artifact_load_failures_total, and the
    engine serves correct tokens via live jit instead of running
    programs whose commitments are unknown."""
    import shutil
    lm, wf, art = served_artifact
    from veles_tpu.nn import sampling
    old = str(tmp_path / ("v%d_art" % version))
    shutil.copytree(art, old)
    cpath = os.path.join(old, "contents.json")
    with open(cpath) as fin:
        contents = json.load(fin)
    contents["serving"]["artifact_version"] = version
    for key in lacks:
        contents["serving"]["signature"].pop(key, None)
    with open(cpath, "w") as fout:
        json.dump(contents, fout)
    with pytest.raises(VelesError, match="different"):
        load_serve_programs(old, ContinuousEngine(
            wf, name="aot_v%d_sig" % version, **KNOBS).stack_signature())
    engine = _fallback_engine(wf, old, "aot_v%d" % version)
    try:
        req = make_request(_prompt(lm, 93), 5)
        assert engine.serve([req])[0] == sampling.generate(
            wf, req["prompt"], req["n_new"], temperature=0)
    finally:
        engine.stop()


def test_injected_artifact_load_fault_falls_back(served_artifact,
                                                 monkeypatch):
    lm, wf, art = served_artifact
    monkeypatch.setenv("VELES_FAULTS", "artifact.load:raise:times=1")
    engine = _fallback_engine(wf, art, "aot_fault")
    engine.stop()
    monkeypatch.setenv("VELES_FAULTS", "")


def test_api_survives_corrupt_artifact_over_http(served_artifact,
                                                 tmp_path):
    """The operator-facing guarantee: a server booted with a corrupt
    artifact WARNS and serves correct answers via live jit — 200s,
    not a crash loop."""
    import shutil
    lm, wf, art = served_artifact
    from veles_tpu.nn import sampling
    bad = str(tmp_path / "bad_api_art")
    shutil.copytree(art, bad)
    with open(os.path.join(bad, "contents.json"), "w") as fout:
        fout.write("{ not json")
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           artifact=bad, name="aot_api", **KNOBS)
    api.initialize()
    try:
        p = _prompt(lm, 91, 9)
        body = json.dumps({"prompt": p, "n_new": 5}).encode()
        req = urllib.request.Request(
            "http://127.0.0.1:%d/generate" % api.port, data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
            out = json.loads(r.read())
        assert out["tokens"] == sampling.generate(wf, p, 5,
                                                  temperature=0)
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % api.port,
                timeout=30) as r:
            text = r.read().decode()
        assert "veles_serving_artifact_mode 0" in text
        assert "veles_artifact_load_failures_total" in text
    finally:
        api.stop()


def test_quantized_artifact_round_trip(served_artifact, tmp_path):
    """Artifact exported under the int8 policy: the signature pins the
    quant flags, a quant-matched engine loads it and serves the same
    tokens as the live int8 engine."""
    lm, wf, art = served_artifact
    qart = str(tmp_path / "q_art")
    export_serve_artifact(wf, qart, quant_weights=True, quant_kv=True,
                          **KNOBS)
    reqs = _reqs(lm)
    live = ContinuousEngine(wf, quant_weights=True, quant_kv=True,
                            name="aot_qlive", **KNOBS).start()
    try:
        ref = live.serve(list(reqs))
    finally:
        live.stop()
    engine = ContinuousEngine(wf, artifact=qart, quant_weights=True,
                              quant_kv=True, name="aot_qeng",
                              **KNOBS).start()
    try:
        assert engine.artifact_mode
        assert engine.serve(list(reqs)) == ref
    finally:
        engine.stop()
    # a float engine must NOT load the int8 artifact
    engine = _fallback_engine(wf, qart, "aot_qmismatch")
    engine.stop()


# -- package format: v2 back-compat + v3 quant blocks --------------------------

class _SmallVecs(FullBatchLoader):
    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(2)
        n = 64
        self.create_originals(
            rng.rand(n, 12).astype(numpy.float32),
            rng.randint(0, 4, n).astype(numpy.int32))
        self.class_lengths = [0, 16, 48]


@pytest.fixture(scope="module")
def dense_wf():
    wf = nn.StandardWorkflow(
        name="quant-pkg-net",
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 24},
            {"type": "softmax", "output_sample_shape": 4},
        ],
        loader_unit=_SmallVecs(None, minibatch_size=16, name="vecs"),
        loss_function="softmax",
        decision_config=dict(max_epochs=1), steps_per_dispatch=2)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return wf


def test_plain_package_still_v2_and_runs(dense_wf, tmp_path):
    pkg = str(tmp_path / "plain")
    package_export(dense_wf, pkg, with_stablehlo=False)
    loaded = package_import(pkg)
    assert loaded["contents"]["format_version"] == 2
    assert all("quant" not in u for u in loaded["contents"]["units"])
    batch = dense_wf.loader.original_data.mem[:6].copy()
    out = run_package(pkg, batch)
    assert out.shape == (6, 4)


def test_quant_package_v3_round_trips_metadata(dense_wf, tmp_path):
    pkg_fp = str(tmp_path / "fp")
    pkg_q = str(tmp_path / "q")
    package_export(dense_wf, pkg_fp, with_stablehlo=False)
    package_export(dense_wf, pkg_q, with_stablehlo=False, quant=True)
    contents = package_import(pkg_q)["contents"]
    assert contents["format_version"] == 3
    assert contents["quant"]["granularity"] == "per_channel"
    assert contents["quant"]["params"] >= 1
    # the eligible 2-D weight is int8 on disk with a scale sidecar...
    unit0 = contents["units"][0]
    assert unit0["quant"]["weights"]["scheme"] == "int8"
    raw = numpy.load(os.path.join(
        pkg_q, unit0["params"]["weights"]))
    assert raw.dtype == numpy.int8
    assert os.path.exists(os.path.join(
        pkg_q, unit0["quant"]["weights"]["scale"]))
    # ...but import dequantizes: consumers see float tensors within
    # the per-channel rounding bound of the plain export
    params_fp = package_import(pkg_fp)["params"]
    params_q = package_import(pkg_q)["params"]
    w_fp = params_fp["all2all_tanh0"]["weights"]
    w_q = params_q["all2all_tanh0"]["weights"]
    assert w_q.dtype == w_fp.dtype
    bound = numpy.abs(w_fp).max(axis=0) / (2 * 127) + 1e-6
    assert (numpy.abs(w_q - w_fp) <= bound[None, :]).all()
    # small tensors (softmax head here) stay float and bit-identical
    assert (params_q["softmax1"]["weights"]
            == params_fp["softmax1"]["weights"]).all()
    batch = dense_wf.loader.original_data.mem[:6].copy()
    numpy.testing.assert_allclose(
        run_package(pkg_q, batch), run_package(pkg_fp, batch),
        rtol=0.1, atol=0.05)
