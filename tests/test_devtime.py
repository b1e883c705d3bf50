"""Device-time reading of a capture (veles_tpu/telemetry/devtime.py)
and the ISSUE-9 roofline features.

The load-bearing locks:
- trace-event parsing math: device streams identified, envelope lanes
  ("XLA Modules") and host processes excluded, nested/overlapping
  events interval-unioned (never double counted), torn traces
  salvaged like ``spans.read_jsonl``;
- span attribution: device intervals clip onto telemetry span windows,
  the spans being the capture's own annotations or records under an
  explicit clock offset;
- the fused scale-bias-activation epilogue and bf16 activation
  storage are BIT-IDENTICAL off, and the epilogue removes (not just
  renames) standalone-chain dispatches — the dispatch-count lock;
- the epilogue composes with TensorMonitor taps (monitoring on keeps
  the plan active — no silent unfused fallback);
- ``veles-tpu trace self-time`` summarizes real and torn traces.
"""
import gzip
import json
import os
import sys

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn, prng
from veles_tpu.config import root
from veles_tpu.loader import FullBatchLoader
from veles_tpu.memory import Array
from veles_tpu.ops.fused_fc import install_epilogues, plan_epilogues
from veles_tpu.telemetry import devtime
from veles_tpu.telemetry.counters import counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_knobs():
    """Every test starts from the shipped defaults (all ISSUE-9 knobs
    OFF) and leaves no residue."""
    yield
    root.common.engine.fused_epilogue = False
    root.common.engine.bf16_activations = False
    root.common.engine.conv_lane_pad = False
    root.common.engine.mixed_precision = False
    root.common.telemetry.tensormon.enabled = False


def _fake_trace(extra=()):
    """A minimal XLA-shaped trace: one TPU device process with an
    "XLA Ops" stream (two overlapping events covering 150 us) and an
    enveloping "XLA Modules" lane, plus a busy host process that must
    not count."""
    return [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 10,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 11,
         "args": {"name": "XLA Modules"}},
        {"ph": "X", "pid": 1, "tid": 10, "ts": 0.0, "dur": 100.0,
         "name": "fusion.1"},
        {"ph": "X", "pid": 1, "tid": 10, "ts": 50.0, "dur": 100.0,
         "name": "fusion.2"},
        {"ph": "X", "pid": 1, "tid": 11, "ts": 0.0, "dur": 1000.0,
         "name": "jit_epoch_block"},
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0.0, "dur": 99999.0,
         "name": "python"},
    ] + list(extra)


# -- parsing math -------------------------------------------------------------

def test_interval_union_never_double_counts():
    union = devtime._interval_union_us
    assert union([]) == 0.0
    assert union([(0, 10)]) == 10.0
    assert union([(0, 10), (5, 15)]) == 15.0        # overlap merges
    assert union([(0, 10), (2, 5)]) == 10.0         # nested absorbs
    assert union([(0, 10), (20, 30)]) == 20.0       # disjoint sums
    assert union([(20, 30), (0, 10), (5, 12)]) == 22.0  # unsorted ok


def test_device_self_time_filters_streams():
    st = devtime.device_self_time(_fake_trace())
    # 150 us on the ops stream; the Modules envelope and the host
    # process are excluded (they would triple the number)
    assert st["device_time_s"] == pytest.approx(150e-6)
    assert st["n_events"] == 2
    assert list(st["by_stream"]) == ["/device:TPU:0/XLA Ops"]


def test_device_self_time_without_ops_thread_uses_all_device_lanes():
    evs = [e for e in _fake_trace()
           if not (e.get("ph") == "M" and e.get("tid") == 10
                   and e.get("name") == "thread_name")
           and not (e.get("ph") == "M" and e.get("tid") == 11
                    and e.get("name") == "thread_name")]
    st = devtime.device_self_time(evs)
    # no named "XLA Ops" lane: every device-pid thread counts,
    # per-thread unions summed (two streams here)
    assert st["device_time_s"] == pytest.approx(150e-6 + 1000e-6)
    assert st["n_events"] == 3


def test_attribute_spans_clips_and_aggregates():
    evs = _fake_trace()
    spans = [
        {"name": "train_step.epoch_block", "ts": 0.0, "dur": 75e-6},
        {"name": "train_step.epoch_block", "ts": 100e-6, "dur": 50e-6},
        {"name": "unit.loader", "ts": 200e-6, "dur": 50e-6},
    ]
    per = devtime.attribute_spans(evs, spans, offset_us=0.0)
    blk = per["train_step.epoch_block"]
    # window 1 covers [0, 75) of the 150 us union; window 2 [100, 150)
    assert blk["device_time_s"] == pytest.approx(125e-6)
    assert blk["spans"] == 2
    assert per["unit.loader"]["device_time_s"] == 0.0
    # records from another clock come with the explicit offset of one
    # common instant; nothing is estimated
    shifted = [dict(s, ts=s["ts"] + 1000.0) for s in spans]
    per2 = devtime.attribute_spans(evs, shifted, offset_us=-1000.0e6)
    assert per2["train_step.epoch_block"]["device_time_s"] == \
        pytest.approx(125e-6)


def test_attribute_spans_takes_the_captures_own_annotations():
    """A span is also a profiler annotation (telemetry/spans.py), so a
    capture holds it on the device operations' own timeline: with no
    records given, the host process's events that carry a span's name
    are the spans. Other host events (the python tracer's) are not."""
    evs = _fake_trace(extra=[
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0.0, "dur": 75.0,
         "name": "train_step.epoch_block"},
        {"ph": "X", "pid": 2, "tid": 1, "ts": 100.0, "dur": 50.0,
         "name": "train_step.epoch_block"},
        {"ph": "X", "pid": 2, "tid": 7, "ts": 120.0, "dur": 10.0,
         "name": "serving.stream.write"},
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0.0, "dur": 150.0,
         "name": "json.dumps"}])
    assert [r["name"] for r in devtime.annotation_spans(evs)] == [
        "train_step.epoch_block", "train_step.epoch_block",
        "serving.stream.write"]
    per = devtime.attribute_spans(evs)
    assert per["train_step.epoch_block"]["device_time_s"] == \
        pytest.approx(125e-6)
    assert per["train_step.epoch_block"]["spans"] == 2
    assert per["serving.stream.write"]["device_time_s"] == \
        pytest.approx(10e-6)
    assert "json.dumps" not in per and "python" not in per
    assert devtime.attribute_spans(_fake_trace()) == {}


# -- trace loading + salvage --------------------------------------------------

def test_load_trace_events_plain_gz_and_bare_list(tmp_path):
    doc = {"displayTimeUnit": "ns", "traceEvents": _fake_trace()}
    plain = tmp_path / "t.json"
    plain.write_text(json.dumps(doc))
    assert len(devtime.load_trace_events(str(plain))) == 8
    gz = tmp_path / "t.json.gz"
    with gzip.open(str(gz), "wb") as f:
        f.write(json.dumps(doc).encode())
    assert len(devtime.load_trace_events(str(gz))) == 8
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(_fake_trace()))
    assert len(devtime.load_trace_events(str(bare))) == 8


def test_torn_trace_salvaged_with_warning(tmp_path, caplog):
    """A capture killed mid-write must still summarize — the
    spans.read_jsonl hardening, mirrored: complete event prefix
    parsed, ONE counted warning, no raise."""
    raw = json.dumps({"traceEvents": _fake_trace()})
    torn = tmp_path / "torn.json"
    # tear inside the LAST event object: 7 complete events survive
    torn.write_text(raw[:raw.rindex('{"ph": "X", "pid": 2') + 10])
    import logging
    with caplog.at_level(logging.WARNING, "veles_tpu.telemetry"):
        evs = devtime.load_trace_events(str(torn))
    assert len(evs) == 7
    assert any("salvaged" in r.message for r in caplog.records)
    st = devtime.device_self_time(evs)
    assert st["device_time_s"] == pytest.approx(150e-6)


def test_self_time_cli(tmp_path, capsys):
    from veles_tpu.__main__ import main
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": _fake_trace(extra=[
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0.0, "dur": 150.0,
         "name": "train_step.epoch_block"}])}))
    rc = main(["trace", "self-time", str(trace)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "/device:TPU:0/XLA Ops" in out
    assert "train_step.epoch_block" in out
    # the span file of old is no flag any more: the capture holds the
    # spans itself
    with pytest.raises(SystemExit):
        main(["trace", "self-time", str(trace), "--spans", "run.jsonl"])
    capsys.readouterr()
    # a missing file is a clean rc=1, not a traceback
    assert main(["trace", "self-time",
                 str(tmp_path / "nope.json")]) == 1


# -- roofline features: bit-identical off, fewer dispatches on ---------------

class BlobsLoader(FullBatchLoader):
    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(7)
        data = rng.randn(120, 10).astype(numpy.float32)
        labels = (data.sum(axis=1) > 0).astype(numpy.int32)
        self.create_originals(data, labels)
        self.class_lengths = [0, 40, 80]


def _train(epilogue=False, bf16=False, amp=False, tensormon=False,
           epochs=2):
    """A tiny chain WITH a standalone activation unit (the epilogue's
    fold target) trained for two epochs; returns the workflow."""
    root.common.engine.fused_epilogue = epilogue
    root.common.engine.bf16_activations = bf16
    root.common.engine.mixed_precision = amp
    root.common.telemetry.tensormon.enabled = tensormon
    prng.seed_all(1234)
    loader = BlobsLoader(None, minibatch_size=40, name="dv-blobs")
    wf = nn.StandardWorkflow(
        name="dv-wf",
        layers=[{"type": "all2all", "output_sample_shape": 8},
                {"type": "activation_tanh"},
                {"type": "softmax", "output_sample_shape": 2}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=epochs, fail_iterations=100))
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return wf


def _state_leaves(wf):
    import jax
    return jax.tree_util.tree_leaves(jax.device_get(
        (wf.train_step.params, wf.train_step.opt_state)))


def _assert_bit_identical(wf_a, wf_b):
    la, lb = _state_leaves(wf_a), _state_leaves(wf_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        numpy.testing.assert_array_equal(numpy.asarray(a),
                                         numpy.asarray(b))


def test_epilogue_train_step_bit_identical_and_plan_active():
    wf_off = _train(epilogue=False)
    wf_on = _train(epilogue=True)
    assert wf_off.train_step._epilogue is None
    assert wf_on.train_step._epilogue       # the plan engaged
    _assert_bit_identical(wf_off, wf_on)
    assert wf_off.train_step._dispatch_counts == \
        wf_on.train_step._dispatch_counts


def test_epilogue_composes_with_tensormon_no_silent_fallback():
    """Satellite lock: monitoring ON must keep the epilogue plan
    active (the taps read the post-epilogue head output) — never a
    silent fall-back to the unfused chain."""
    wf = _train(epilogue=True, tensormon=True)
    assert wf.train_step._epilogue          # still fused
    assert wf.train_step._tensormon is not None
    wf_ref = _train(epilogue=False, tensormon=True)
    _assert_bit_identical(wf_ref, wf)


def test_fused_fc_reject_message_mentions_epilogue_path():
    """Satellite lock: the fused_fc_scan tensormon rejection names the
    epilogue path as what the general scan keeps."""
    root.common.engine.fused_fc_scan = "force"
    root.common.telemetry.tensormon.enabled = True
    msgs = []
    prng.seed_all(99)
    loader = BlobsLoader(None, minibatch_size=40, name="rj-blobs")
    wf = nn.StandardWorkflow(
        name="rj-wf",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 8},
                {"type": "softmax", "output_sample_shape": 2}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=1, fail_iterations=100))
    orig = wf.train_step.info
    wf.train_step.info = lambda fmt, *a: msgs.append(fmt % a if a
                                                    else fmt)
    try:
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    finally:
        wf.train_step.info = orig
        root.common.engine.fused_fc_scan = False
        root.common.telemetry.tensormon.enabled = False
    assert wf.train_step._fused_fc is None
    rejected = [m for m in msgs if "ineligible" in m]
    assert rejected and "fused scale-bias-activation epilogue" in \
        rejected[0]


def test_standalone_epilogue_removes_dispatches_bit_exact():
    """THE dispatch-count lock: on the standalone forward path the
    fused epilogue must REMOVE the activation unit's dispatch (2 → 1
    per batch) while producing bit-identical output."""
    root.common.engine.compute_dtype = "float32"

    def chain(fold):
        prng.seed_all(77)
        wf = vt.Workflow(name="c")
        a = nn.All2All(wf, name="fc", output_sample_shape=6)
        t = nn.ForwardTanh(wf, name="act")
        rngs = numpy.random.RandomState(3)
        x = rngs.randn(4, 5).astype(numpy.float32)
        a.input = Array(x, name="x")
        dev = vt.XLADevice(mesh_axes={"data": 1})
        a.initialize(device=dev)
        t.input = a.output
        t.initialize(device=dev)
        plan = install_epilogues([a, t], force=fold)
        assert bool(plan) == fold
        before = counters.get("veles_dispatches_total")
        a.run()
        t.run()
        n = int(counters.get("veles_dispatches_total") - before)
        return numpy.asarray(t.output.map_read()), n

    y_off, n_off = chain(False)
    y_on, n_on = chain(True)
    numpy.testing.assert_array_equal(y_off, y_on)
    assert (n_off, n_on) == (2, 1)      # removed, not renamed


def test_epilogue_keeps_every_stage_output_fresh_and_uninstalls():
    """Review hardening: (1) the fused program assigns EVERY stage's
    output array — a non-chain consumer linked to the producer's
    output must read exactly what the unfused path wrote, not stale
    device memory; (2) re-installing with the knob off restores the
    unfused dispatch layout (no sticky fold flags, no stale jitted
    closure)."""
    root.common.engine.compute_dtype = "float32"
    prng.seed_all(77)
    wf = vt.Workflow(name="c2")
    a = nn.All2All(wf, name="fc", output_sample_shape=6)
    t = nn.ForwardTanh(wf, name="act")
    rngs = numpy.random.RandomState(3)
    x = rngs.randn(4, 5).astype(numpy.float32)
    a.input = Array(x, name="x")
    dev = vt.XLADevice(mesh_axes={"data": 1})
    a.initialize(device=dev)
    t.input = a.output
    t.initialize(device=dev)

    def run_chain():
        before = counters.get("veles_dispatches_total")
        a.run()
        t.run()
        return (numpy.asarray(a.output.map_read()).copy(),
                numpy.asarray(t.output.map_read()).copy(),
                int(counters.get("veles_dispatches_total") - before))

    mm_off, act_off, n_off = run_chain()        # unfused reference
    install_epilogues([a, t], force=True)
    mm_on, act_on, n_on = run_chain()
    assert (n_off, n_on) == (2, 1)
    # the PRODUCER's output array (pre-activation) is fresh too
    numpy.testing.assert_array_equal(mm_off, mm_on)
    numpy.testing.assert_array_equal(act_off, act_on)
    # uninstall: knob off → re-install clears flags + cached closure
    root.common.engine.fused_epilogue = False
    assert install_epilogues([a, t]) == {}
    assert a._epilogue_tails is None and not t._epilogue_folded
    mm_back, act_back, n_back = run_chain()
    assert n_back == 2                          # unfused layout back
    numpy.testing.assert_array_equal(act_back, act_off)


def test_plan_epilogues_geometry():
    wf = vt.Workflow(name="p")
    t0 = nn.ForwardTanh(wf, name="t0")
    a = nn.All2All(wf, name="fc1", output_sample_shape=4)
    t1 = nn.ForwardTanh(wf, name="t1")
    m = nn.ForwardMul(wf, name="scale", factor=0.5)
    d = nn.DropoutForward(wf, name="drop", dropout_ratio=0.5)
    b = nn.All2All(wf, name="fc2", output_sample_shape=4)
    t2 = nn.ForwardTanh(wf, name="t2")
    # leading activation has no producer: never folded; the tanh+mul
    # run folds into fc1; dropout (rng- and train-dependent) is never
    # an epilogue and fc2's run restarts after it
    plan = plan_epilogues([t0, a, t1, m, d, b, t2])
    assert [(p.name, [t.name for t in ts]) for p, ts in plan] == \
        [("fc1", ["t1", "scale"]), ("fc2", ["t2"])]


def test_bf16_activations_off_bit_identical_on_stores_bf16():
    wf_amp = _train(amp=True)
    wf_off = _train(amp=True, bf16=False)
    _assert_bit_identical(wf_amp, wf_off)
    # ON: interlayer activations that would leave a unit f32 are
    # stored bfloat16; masters stay f32 and training stays finite
    seen = {}
    wf_on = _train(amp=True, bf16=True)
    assert wf_on.train_step._bf16_acts
    import jax
    import jax.numpy as jnp

    ts = wf_on.train_step

    class Probe:
        def __init__(self, inner):
            self.inner = inner
            self.name = inner.name
            self.PARAMETERIZED = inner.PARAMETERIZED

        def apply(self, p, x, *, train=False, rng=None):
            seen["dtype"] = x.dtype
            return self.inner.apply(p, x, train=train, rng=rng)

        def __getattr__(self, k):
            return getattr(self.inner, k)

    # force an f32 interlayer value: a probe wrapping the activation
    # unit records what dtype the NEXT layer receives after the cast
    orig = ts.forwards[1]
    f32_out = Probe(orig)
    f32_out.apply = lambda p, x, train=False, rng=None: \
        orig.apply(p, x, train=train, rng=rng).astype(jnp.float32)
    probe = Probe(ts.forwards[2])
    ts.forwards = [ts.forwards[0], f32_out, probe]
    x = jnp.asarray(numpy.random.RandomState(0).randn(4, 10),
                    jnp.bfloat16)
    ts._forward_pure({k: jax.device_get(v)
                      for k, v in ts.params.items()}, x, False, None)
    assert seen["dtype"] == jnp.bfloat16    # the knob's cast fired
    for leaf in jax.tree_util.tree_leaves(ts.params):
        assert leaf.dtype == jnp.float32    # masters stay f32


def test_bf16_activations_without_amp_is_inert():
    wf = _train(bf16=True, amp=False)
    assert not wf.train_step._bf16_acts
    wf_base = _train()
    _assert_bit_identical(wf_base, wf)


def test_conv_lane_padding_off_identical_on_equal():
    from veles_tpu.nn.conv import lane_padded_channels
    assert lane_padded_channels(96) == 128      # 1.33x: worth it
    assert lane_padded_channels(100) == 128
    assert lane_padded_channels(3) == 3         # 42x: never
    assert lane_padded_channels(64) == 64       # 2x: beyond headroom
    assert lane_padded_channels(128) == 128     # aligned already
    assert lane_padded_channels(130) == 130     # 1.97x: beyond

    prev = root.common.engine.compute_dtype
    root.common.engine.compute_dtype = "float32"
    try:
        def conv_out(pad, cls=nn.Conv, c=96, **kw):
            root.common.engine.conv_lane_pad = pad
            prng.seed_all(42)
            wf = vt.Workflow(name="cl")
            u = cls(wf, name="u", **kw)
            rng = numpy.random.RandomState(5)
            x = rng.randn(2, 6, 6, c).astype(numpy.float32)
            u.input = Array(x, name="x")
            u.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
            u.xla_run()
            return numpy.asarray(u.output.map_read())

        a = conv_out(False, n_kernels=4, kx=3, ky=3)
        b = conv_out(True, n_kernels=4, kx=3, ky=3)
        # zero channels contribute exact-zero partial products
        numpy.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        a = conv_out(False, cls=nn.Deconv, n_channels=4, kx=3, ky=3)
        b = conv_out(True, cls=nn.Deconv, n_channels=4, kx=3, ky=3)
        numpy.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    finally:
        root.common.engine.compute_dtype = prev
        root.common.engine.conv_lane_pad = False


def test_check_counters_still_green():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import check_counters
    finally:
        sys.path.remove(os.path.join(REPO, "scripts"))
    assert check_counters.find_unregistered() == []
