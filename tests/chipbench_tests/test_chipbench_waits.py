"""The readers of the serving path's two waits (PR 38): what the chip
stood unfed for, by the sync that emptied it (``unfed_ms.*``), a request's
waits before its first token is on the wire (``request_ms.*``) and the
prompt positions a tick prefills (``prefill_positions_mean``), each from
the slice's ``/metrics`` differences. Hand-made ``ctx`` dicts, values by
hand; a program without the series (the parent commit) or a slice without
decode dispatches gives ``None`` and the metric is left out of the line."""
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELL = "mistral7b_decode_sat"
STEPS = 280.0
#: histogram: (seconds, samples) that a slice of 280 steps might hold
WAITS = {"veles_serving_queue_wait_seconds": (0.24, 24.0),
         "veles_serving_prefill_wait_seconds": (0.96, 24.0),
         "veles_serving_ttft_seconds": (1.20, 24.0),
         "veles_serving_first_write_seconds": (0.06, 24.0)}
#: the seven, as the issue's table has them: name -> (reader file,
#: unit, layer, source, the reading of ``counters()`` by hand)
SEVEN = {
    "unfed_ms.first_token":
        ("unfed_ms", "ms", "engine", "program_span", 1000 * 0.196 / 280),
    "unfed_ms.drain":
        ("unfed_ms", "ms", "engine", "program_span", 1000 * 0.014 / 280),
    "request_ms.queue":
        ("request_ms", "ms", "scheduler", "program_counter", 10.0),
    "request_ms.prefill":
        ("request_ms", "ms", "engine", "program_counter", 40.0),
    "request_ms.ttft":
        ("request_ms", "ms", "engine", "program_counter", 50.0),
    "request_ms.first_write":
        ("request_ms", "ms", "request_plane", "program_span", 2.5),
    "prefill_positions_mean":
        ("prefill_positions_mean", "positions", "scheduler",
         "program_counter", 4480 / 280),
}


def reader(name):
    return importlib.import_module("chipbench.run").metric_reader(name)


def counters(**extra):
    out = {"veles_serving_decode_dispatches_total": STEPS,
           "veles_serving_unfed_first_token_seconds_sum": 0.196,
           "veles_serving_unfed_first_token_seconds_count": 24.0,
           "veles_serving_unfed_drain_seconds_sum": 0.014,
           "veles_serving_unfed_drain_seconds_count": 2.0,
           "veles_serving_unfed_late_reads_total": 3.0,
           "veles_serving_prefill_dispatches_total": 24.0,
           "veles_serving_prefill_positions_total": 4480.0}
    for name, (seconds, samples) in WAITS.items():
        out[name + "_sum"], out[name + "_count"] = seconds, samples
    out.update(extra)
    return out


def without(*gone):
    return {k: v for k, v in counters().items()
            if not k.startswith(gone)}


def ctx(metric, counters_):
    piece = None if counters_ is None else {
        "from_s": 12.0, "to_s": 18.0, "window_s": 6.0,
        "counters": counters_}
    return {"metric": metric, "report": {"slice": piece, "requests": []}}


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_reading_by_hand(name):
    assert reader(name)(ctx(name, counters())) == pytest.approx(
        SEVEN[name][4])


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_nothing_without_a_slice_or_the_series(name):
    read = reader(name)
    assert read(ctx(name, None)) is None
    assert read(ctx(name, {})) is None
    # the parent commit's report: the two standing histograms, observed
    # at a request's end, and none of what this PR adds
    parent = without("veles_serving_unfed_",
                     "veles_serving_prefill_positions_total",
                     "veles_serving_prefill_wait_seconds",
                     "veles_serving_first_write_seconds")
    standing = ("request_ms.queue", "request_ms.ttft")
    assert (read(ctx(name, parent)) is None) == (name not in standing)


@pytest.mark.parametrize("name", sorted(
    n for n in SEVEN if not n.startswith("request_ms.")))
def test_nothing_without_decode_dispatches(name):
    for dry in (without("veles_serving_decode_dispatches_total"),
                counters(veles_serving_decode_dispatches_total=0.0)):
        assert reader(name)(ctx(name, dry)) is None


def test_a_wait_that_no_request_ended_in_the_slice_reads_nothing():
    name = "request_ms.first_write"
    quiet = counters(veles_serving_first_write_seconds_count=0.0,
                     veles_serving_first_write_seconds_sum=0.0)
    assert reader(name)(ctx(name, quiet)) is None
    # a request's waits are over its own samples, not the dispatches
    assert reader(name)(ctx(name, without(
        "veles_serving_decode_dispatches_total"))) == pytest.approx(2.5)


def test_a_cause_that_never_came_reads_nought_beside_the_other():
    """The program renders a histogram from its first sample on: a
    saturated plain pool never drains, and its slice holds the first
    token's series alone."""
    plain = without("veles_serving_unfed_drain_")
    assert reader("unfed_ms.drain")(
        ctx("unfed_ms.drain", plain)) == 0.0
    assert reader("unfed_ms.first_token")(
        ctx("unfed_ms.first_token", plain)) == pytest.approx(0.7)
    # a cause the engine does not know is no metric
    assert reader("unfed_ms.other")(
        ctx("unfed_ms.other", counters())) is None
    assert reader("request_ms.other")(
        ctx("request_ms.other", counters())) is None


def test_the_seven_entries_are_the_last_and_as_the_table_has_them():
    assert len(MANIFEST["per_layer"]) == 41
    new = MANIFEST["per_layer"][-7:]
    assert [m["name"] for m in new] == list(SEVEN)
    for m in new:
        stem, unit, layer, source, _ = SEVEN[m["name"]]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", stem + ".py"))
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": layer,
                     "moves": "out_tokens_per_s", "workloads": [CELL]}
