"""The hybrid stacks' full layers read through the page table
(``HybridBlock.serve_step`` handed ``ContinuousEngine._paged_attend``,
the kernel of ops/paged_attention.py interpreted here) against the same
engine reading gathered views, at the ``tiny-olmo`` size (delta-rule
layers on state rows among full multi-head layers) and the ``tiny-mimo``
size (window layers on rings among full layers whose keys are wider than
their values, four query heads on one KV head). Token for token, greedy and sampled, over the view ladder's two
rungs, ``decode_block`` 1 and 4, a preemption and resume; the greedy
answers are also the reference's first choices. The predicate is forced
for one engine of each pair (off the chip it keeps the gather), and only
the full layers take it: a ring and a state are a slot's row already.
``paged_share`` and ``live_share`` are held to what tests/ladder_drill.py
reckons from the steps' own inputs.

Both sides are float32 and differ in the order of their sums alone, so
the tokens are equal; the reference's tolerance is tests/
test_hybrid_serving.py's and tests/test_olmo_serving.py's ``TOL``."""
import json
import os
import sys

import numpy
import pytest

from veles_tpu.ops import paged_attention as pa
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import Ticket
from veles_tpu.telemetry.counters import counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOL = 1e-4
SEED = 5
#: the configuration, and which of its blocks keep K and V in pages
STACKS = {"tiny-olmo": (False, False, False, True) * 2,
          "tiny-mimo": (True, False, False, True)}
#: the drill's geometry at buckets the delta rule's chunked prefill takes:
#: 16 pages of 4 positions a slot, rungs of 64 and 32 positions
KNOBS = dict(buckets=(16, 32))


def _load(name):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=sorted(STACKS))
def stack(request):
    """(configuration, reference, workflow with the seed's weights)."""
    from veles_tpu import prng
    from veles_tpu.backends import XLADevice
    from chipbench import model_file, modules, serve_side
    cfg = _load(request.param)
    ref = modules.reference_of(cfg, serving=True)
    keep = prng.RandomGenerator.fill_normal
    model_file.skip_host_draw()
    try:
        wf = serve_side.build_workflow(cfg, {"init_positions": 8})
        wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
    finally:
        prng.RandomGenerator.fill_normal = keep
    weights = ref.make_weights(cfg, SEED)
    for f in wf.forwards:
        for k, arr in f.param_arrays().items():
            arr.assign_devmem(weights[f.name][k])
    return cfg, ref, wf


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # blocks of two pages, so a row's read crosses blocks
    monkeypatch.setattr(pa, "BLOCK_POSITIONS", 8)


def _prompt(cfg):
    def prompt(seed, length):
        return numpy.random.default_rng(seed).integers(
            0, cfg["vocab_size"], length).tolist()
    return prompt


def _twins(wf, name, **knobs):
    import ladder_drill
    paged, gather = ladder_drill.twins(wf, name, **dict(KNOBS, **knobs))
    gather.view_ladder = paged.view_ladder      # both climb the ladder
    return ladder_drill.force_paged(paged), gather


def _first_choices(cfg, ref, reqs, got):
    """The greedy answers are the reference's first choices."""
    for req, toks in zip(reqs, got):
        if req["temperature"] == 0.0:
            gaps, first = ref.served_gaps(cfg, SEED, req["prompt"], toks,
                                          pad=96)
            assert list(first) == list(toks)
            assert float(gaps.max()) <= TOL


@pytest.mark.parametrize("decode_block", [1, 4])
def test_paged_serves_what_the_gather_serves(stack, decode_block):
    """The drill's requests, one of which crosses from the half view into
    the whole: the same tokens through the kernel and through the views;
    only the full layers read through the table, every step of the first
    engine, none of the second's; the counters and the stats say so, and
    ``live_share`` is the drill's own reckoning."""
    import ladder_drill
    cfg, ref, wf = stack
    paged, gather = _twins(wf, "hpgd_%s_blk%d" % (cfg["name"], decode_block),
                           decode_block=decode_block)
    assert paged.view_ladder == (16, 8)
    seen = ladder_drill.record_reads(paged)
    rungs = ladder_drill.record_rungs(paged)
    reqs = ladder_drill.requests(_prompt(cfg))
    before = counters.snapshot()
    got = ladder_drill.serve_by_ticks(paged, reqs)
    delta = counters.delta(before)
    assert got == ladder_drill.serve_by_ticks(gather, reqs)
    _first_choices(cfg, ref, reqs, got)
    assert {pages for pages, _ in rungs} == {8, 16}
    assert paged._paged_blocks() == STACKS[cfg["name"]]
    assert not any(gather._paged_blocks())
    assert delta["veles_serving_paged_reads_total"] == len(seen) == \
        delta["veles_serving_decode_dispatches_total"]
    assert delta["veles_serving_paged_positions_total"] == \
        sum(rows for _, rows in seen)
    stats = paged.stats()
    assert stats["paged_share"] == 1.0
    assert gather.stats()["paged_share"] == 0.0
    assert stats["live_share"] == ladder_drill.live_share(paged, seen)
    assert 0 < stats["live_share"] < 1
    assert paged.page_pool.in_use() == gather.page_pool.in_use() == 0


def test_paged_step_gathers_no_view(stack):
    """The kernel's program holds no gather of a pool by the table; the
    gather's program holds the gather. Each full layer's kernel runs
    inside its block's ``full_attn`` scope and bears that scope's name
    (interpreted here, its own operations are ``<blk>/full_attn/
    jit(_call)/full_attn/...``; on the chip the one device operation is
    named ``full_attn``, which is what ``scope_ms.full_attn`` reads)."""
    import ladder_drill
    cfg, _, wf = stack
    texts = []
    for engine in _twins(wf, "hpgd_hlo_" + cfg["name"]):
        ladder_drill.serve_by_ticks(
            engine, [make_request(_prompt(cfg)(470, 5), 4, seed=1)])
        (key,) = [k for k in engine._progs if k[0] == "step"]
        texts.append(engine._progs[key].compiled().as_text())
    paged, gather = texts
    full = [b.name for b, on in zip(wf.forwards[1:], STACKS[cfg["name"]])
            if on]
    assert "page_gather" not in paged and "page_gather" in gather
    assert "page_writeback" in paged
    for name in full:
        assert "%s/full_attn/jit(_call)/full_attn/" % name in paged
    assert "jit(_call)" not in gather


@pytest.mark.parametrize("mode,temp", [("greedy", 0.0), ("sample", 0.9)])
def test_paged_after_a_preemption_and_resume(stack, mode, temp):
    """QoS preempts a batch row beyond the first rung; it comes back
    through a longer prefill, which rebuilds its rings and states, and
    goes on reading through the table: both requests' tokens are what the
    gathering twin serves."""
    import ladder_drill
    from veles_tpu.config import root
    cfg, ref, wf = stack
    prompt = _prompt(cfg)
    root.common.serving.qos = True
    try:
        got = {}
        for engine in _twins(wf, "hpgd_qos_%s_%s" % (cfg["name"], mode),
                             max_slots=1, buckets=(16, 64)):
            req = make_request(prompt(420, 6), 50, temperature=temp,
                               seed=7, mode=mode)
            req["priority"] = "batch"
            urgent = make_request(prompt(421, 5), 3)
            urgent["priority"] = "interactive"
            t_b, t_i = Ticket(), Ticket()
            assert engine.submit(req, t_b)
            ladder_drill.tick_until(
                engine, lambda: any(len(s.tokens) >= 30
                                    for s in engine.scheduler.active()))
            assert engine.submit(urgent, t_i)
            ladder_drill.tick_until(
                engine, lambda: t_b.event.is_set() and t_i.event.is_set())
            assert engine.preemptions == 1
            assert t_b.error is None and t_i.error is None
            assert engine.page_pool.in_use() == 0
            got[engine] = [t_b.result["tokens"], t_i.result["tokens"]]
        paged, gather = got
        assert got[paged] == got[gather]
        assert paged.stats()["paged_share"] == 1.0
        _first_choices(cfg, ref, [req, urgent], got[paged])
    finally:
        root.common.serving.qos = False


def test_the_predicate_takes_the_full_layers_alone(stack):
    """Unforced, the CPU keeps the gather for every block; forced, a
    window layer's ring and a delta-rule layer's state are never read
    through the kernel."""
    import ladder_drill
    cfg, _, wf = stack
    plain, forced = ladder_drill.twins(wf, "hpgd_pred_" + cfg["name"],
                                       **KNOBS)
    ladder_drill.force_paged(forced)
    for engine in (plain, forced):
        ladder_drill.serve_by_ticks(
            engine, [make_request(_prompt(cfg)(480, 5), 3, seed=1)])
    assert not any(plain._paged_blocks())
    assert forced._paged_blocks() == STACKS[cfg["name"]]
    for blk, on in zip(forced.stack["blocks"], STACKS[cfg["name"]]):
        assert (forced._slot_row(blk) is None) == on


def test_a_full_layer_with_a_sink_keeps_the_gather(stack, monkeypatch):
    """The kernel's softmax merges no learned sink: a full layer that
    kept one would read its gathered view, forced or not."""
    import ladder_drill
    cfg, _, wf = stack
    forced = ladder_drill.force_paged(ladder_drill.twins(
        wf, "hpgd_sink_" + cfg["name"], **KNOBS)[0])
    full = [blk for blk, on in zip(forced.stack["blocks"],
                                   STACKS[cfg["name"]]) if on]
    assert full and all(forced._paged_read(blk) for blk in full)
    for blk in full:
        monkeypatch.setattr(blk, "has_sink", True)
    assert not any(forced._paged_read(blk) for blk in full)
