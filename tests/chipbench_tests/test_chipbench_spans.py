"""The readers of what the program times and names itself: the tick's
phases and the SSE write from the slice's ``/metrics`` differences
(``tick_ms.*``, ``stream_write_ms``), the flash kernels by name
(``flash_step_ms``). Hand-made ``ctx`` dicts, values by hand; a program
without the spans or the names (the parent commit, a CPU rehearsal) gives
``None`` and the metric is left out of the line."""
import copy
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reduce  # noqa: E402

PHASES = {"admit": 0.02, "prefill": 0.11, "prepare": 0.30,
          "dispatch": 0.05, "device": 4.40, "emit": 0.07}
STEPS = 100.0


def reader(name):
    return importlib.import_module("chipbench.run").metric_reader(name)


def counters(**extra):
    out = {"veles_serving_tick_%s_seconds_sum" % p: s
           for p, s in PHASES.items()}
    out.update({"veles_serving_tick_%s_seconds_count" % p: STEPS
                for p in PHASES})
    out["veles_serving_decode_dispatches_total"] = STEPS
    out["veles_serving_loop_wait_seconds_sum"] = 0.25
    out["veles_serving_stream_write_seconds_sum"] = 9.0
    out.update(extra)
    return out


def ctx(metric, counters_=None, window_s=6.0):
    piece = None if counters_ is None else {
        "from_s": 12.0, "to_s": 12.0 + window_s, "window_s": window_s,
        "counters": counters_}
    return {"metric": metric, "report": {"slice": piece, "requests": []}}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_tick_ms_is_the_phase_sum_over_the_dispatches(phase):
    name = "tick_ms." + phase
    assert reader(name)(ctx(name, counters())) == pytest.approx(
        1000.0 * PHASES[phase] / STEPS)


def test_tick_ms_unaccounted_by_hand():
    """A tick is 6.0 s / 100 = 60 ms; the phases hold 49.5 of it and the
    loop's idle wait 2.5: 8 ms are under no span."""
    name = "tick_ms.unaccounted"
    assert reader(name)(ctx(name, counters())) == pytest.approx(
        60.0 - 49.5 - 2.5)
    # a phase that a later PR adds is taken off without an edit here,
    # a series of another family is not
    more = counters(veles_serving_tick_x_seconds_sum=0.3,
                    veles_serving_other_seconds_sum=1.0)
    assert reader(name)(ctx(name, more)) == pytest.approx(8.0 - 3.0)
    # a saturated server never waits: the series may be missing
    dry = counters()
    del dry["veles_serving_loop_wait_seconds_sum"]
    assert reader(name)(ctx(name, dry)) == pytest.approx(60.0 - 49.5)


@pytest.mark.parametrize("name", ["tick_ms.admit", "tick_ms.device",
                                  "tick_ms.unaccounted",
                                  "stream_write_ms"])
def test_serving_readers_give_nothing_without_their_series(name):
    read = reader(name)
    # no traced slice
    assert read(ctx(name, None)) is None
    # the parent commit: dispatches counted, no span-fed histogram
    parent = {"veles_serving_decode_dispatches_total": STEPS,
              "veles_serving_queue_wait_seconds_sum": 1.0}
    assert read(ctx(name, parent)) is None
    # no decode step in the slice
    idle = counters(veles_serving_decode_dispatches_total=0.0)
    assert read(ctx(name, idle)) is None
    assert read(ctx(name, {})) is None


def test_tick_ms_of_a_phase_the_program_lacks():
    name = "tick_ms.emit"
    without = counters()
    del without["veles_serving_tick_emit_seconds_sum"]
    assert reader(name)(ctx(name, without)) is None
    assert reader("tick_ms.admit")(ctx("tick_ms.admit", without)) \
        == pytest.approx(0.2)


def test_stream_write_ms_by_hand():
    """9 s of writes by all handler threads over 100 steps: 90 ms a
    tick, more than the tick itself."""
    name = "stream_write_ms"
    assert reader(name)(ctx(name, counters())) == pytest.approx(90.0)


# -- flash_step_ms ------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "chipbench", "testdata",
                           "trace_train_step.json")) as f:
        return json.load(f)


def train_ctx(trace, steps=1):
    return {"metric": "flash_step_ms", "report": {
        "trace": trace, "slice": {"window_s": 1.0, "steps": steps,
                                  "tokens": 4096 * steps}}}


def test_flash_step_ms_nothing_before_the_names(recorded):
    """The recorded step dates from before the kernels had names
    (``jvp__.N``, ``transpose_jvp___.N``): nothing to read."""
    trace = reduce.reduce_trace(recorded["planes"], recorded["window_s"])
    assert trace["kernels"]
    assert reader("flash_step_ms")(train_ctx(trace)) is None


def test_flash_step_ms_on_the_recorded_step_renamed(recorded):
    """The same step with its Pallas calls named as ops/flash_attention.py
    names them now: the forward calls have three operands, of the
    backward ones the call with one result is dq. Their seconds are the
    ones ``flash_roofline`` divides by, found by name."""
    planes = copy.deepcopy(recorded["planes"])
    want, renamed = 0.0, 0
    for plane in planes:
        for line in plane["lines"]:
            for event in line["events"]:
                if reduce.KERNEL_TARGET not in event[0]:
                    continue
                types = reduce.kernel_types(event[0])
                if not types["operands"][0].endswith("[16,4096,128]"):
                    continue
                kind = ("veles_flash_fwd" if len(types["operands"]) == 3
                        else "veles_flash_bwd_dq"
                        if len(types["results"]) == 1
                        else "veles_flash_bwd_dkv")
                head, _, rest = event[0].partition(" = ")
                event[0] = "%%%s.%s = %s" % (kind, head.rsplit(".", 1)[-1],
                                             rest)
                want += event[2] / 1e9
                renamed += 1
    assert renamed == 24
    trace = reduce.reduce_trace(planes, recorded["window_s"])
    read = reader("flash_step_ms")
    assert read(train_ctx(trace)) == pytest.approx(1000.0 * want)
    assert read(train_ctx(trace, steps=4)) == pytest.approx(250.0 * want)
    assert 0.45 < want / trace["busy_s"] < 0.55
    names = {k.split(".")[0] for k in trace["kernels"]}
    assert {"veles_flash_fwd", "veles_flash_bwd_dq",
            "veles_flash_bwd_dkv"} <= names


def test_flash_step_ms_by_hand_and_other_kernels_left_out():
    ms = 1000000

    def call(name):
        return ('%%%s = f32[16,4096,128]{2,1,0} custom-call('
                'f32[16,4096,128]{2,1,0} %%q), '
                'custom_call_target="tpu_custom_call"' % name)
    planes = [{"name": "/device:TPU:0", "lines": [{
        "name": "XLA Ops", "events": [
            [call("veles_flash_fwd.1"), 0, 8 * ms],
            [call("veles_flash_bwd_dkv.2"), 8 * ms, 9 * ms],
            [call("veles_flash_bwd_dq.3"), 17 * ms, 7 * ms],
            [call("veles_fused_fc.4"), 24 * ms, 5 * ms],
            ["%fusion.5 = f32[8]{0} fusion(%p)", 29 * ms, 1 * ms]]}]}]
    trace = reduce.reduce_trace(planes, 0.03)
    read = reader("flash_step_ms")
    assert read(train_ctx(trace, steps=2)) == pytest.approx(12.0)
    assert read(train_ctx(trace, steps=0)) is None
    assert read({"metric": "flash_step_ms",
                 "report": {"slice": {"steps": 2}}}) is None
    assert read({"metric": "flash_step_ms",
                 "report": {"trace": trace, "slice": None}}) is None
