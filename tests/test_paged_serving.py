"""The float pool's decode step read through the page table
(ops/paged_attention.py, interpreted here) against the same engine
reading gathered views: token for token, greedy and sampled, and equal
to the scan decoder, over the view ladder's two rungs, ``decode_block``
1 and 4, prefix-shared leading pages, a preemption and resume. The
predicate is forced for one engine of each pair (off the chip it keeps
the gather); ``paged_share`` and ``live_share`` are held to what
tests/ladder_drill.py reckons from the steps' own inputs."""
import numpy
import pytest

import veles_tpu as vt
from veles_tpu import prng
from veles_tpu.ops import paged_attention as pa
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import Ticket
from veles_tpu.telemetry.counters import counters

from conftest import import_model


@pytest.fixture(scope="module")
def lm_wf():
    lm = import_model("char_lm")
    prng.seed_all(971)
    wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                           dim=32, n_train=256, n_valid=64)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    return lm, wf


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # blocks of two pages, so a row's read crosses blocks
    monkeypatch.setattr(pa, "BLOCK_POSITIONS", 8)


def _prompt(lm, seed, length=12):
    return [int(t) for t in
            lm.make_corpus(numpy.random.RandomState(seed), length)]


def _twins(wf, name, **knobs):
    import ladder_drill
    paged, gather = ladder_drill.twins(wf, name, **knobs)
    gather.view_ladder = paged.view_ladder      # both climb the ladder
    return ladder_drill.force_paged(paged), gather


def _solo(wf, reqs, got):
    from veles_tpu.nn import sampling
    for req, toks in zip(reqs, got):
        assert toks == sampling.generate(
            wf, req["prompt"], req["n_new"],
            temperature=req["temperature"], seed=req["seed"]), req


@pytest.mark.parametrize("decode_block", [1, 4])
def test_paged_serves_what_the_gather_serves(lm_wf, decode_block):
    """The drill's requests, one of which crosses from the half view
    into the whole: the same tokens through the kernel and through the
    views, and the scan decoder's; every step of the first read
    through the table, none of the second's; the counters and the
    stats say so, and ``live_share`` is the drill's own reckoning."""
    import ladder_drill
    lm, wf = lm_wf
    paged, gather = _twins(wf, "pgd_blk%d" % decode_block,
                           decode_block=decode_block)
    assert paged.view_ladder == (16, 8)
    seen = ladder_drill.record_reads(paged)
    rungs = ladder_drill.record_rungs(paged)
    reqs = ladder_drill.requests(lambda seed, n: _prompt(lm, seed, n))
    before = counters.snapshot()
    got = ladder_drill.serve_by_ticks(paged, reqs)
    delta = counters.delta(before)
    assert got == ladder_drill.serve_by_ticks(gather, reqs)
    _solo(wf, reqs, got)
    assert {pages for pages, _ in rungs} == {8, 16}
    assert paged._paged_blocks() == (True, True)
    assert gather._paged_blocks() == (False, False)
    assert delta["veles_serving_paged_reads_total"] == len(seen) == \
        delta["veles_serving_decode_dispatches_total"]
    assert delta["veles_serving_paged_positions_total"] == \
        sum(rows for _, rows in seen)
    stats = paged.stats()
    assert stats["paged_share"] == 1.0
    assert gather.stats()["paged_share"] == 0.0
    assert stats["live_share"] == ladder_drill.live_share(paged, seen)
    assert 0 < stats["live_share"] < 1
    assert gather.stats()["live_share"] == 0.0
    assert paged.page_pool.in_use() == gather.page_pool.in_use() == 0


def test_paged_step_gathers_no_view(lm_wf):
    """The kernel's program holds the Pallas call and no gather of the
    pool by the table; the gather's program holds the gather."""
    import ladder_drill
    lm, wf = lm_wf
    texts = []
    for engine in _twins(wf, "pgd_hlo"):
        ladder_drill.serve_by_ticks(
            engine, [make_request(_prompt(lm, 470, 5), 4, seed=1)])
        (key,) = [k for k in engine._progs if k[0] == "step"]
        texts.append(engine._progs[key].compiled().as_text())
    paged, gather = texts
    assert "page_gather" not in paged and "page_gather" in gather
    assert "page_writeback" in paged


def test_paged_with_prefix_shared_leading_pages(lm_wf):
    """Two rows that adopted the same leading pages read them through
    the table as the gather reads them through its views."""
    import ladder_drill
    lm, wf = lm_wf
    paged, gather = _twins(wf, "pgd_pfx", prefix_cache=True)
    stem = _prompt(lm, 410, 12)
    first = [make_request(stem + _prompt(lm, 411, 3), 4, seed=1)]
    reqs = [make_request(stem + _prompt(lm, 412, 4), 40, seed=2),
            make_request(stem + _prompt(lm, 413, 2), 9, temperature=0.8,
                         seed=3)]
    for engine in (paged, gather):
        ladder_drill.serve_by_ticks(engine, first)   # fills the index
    got = ladder_drill.serve_by_ticks(paged, reqs)
    assert paged.prefix_requests >= 2
    assert got == ladder_drill.serve_by_ticks(gather, reqs)
    _solo(wf, reqs, got)
    assert paged.stats()["paged_share"] == 1.0


@pytest.mark.parametrize("mode,temp", [("greedy", 0.0), ("sample", 0.9)])
def test_paged_after_a_preemption_and_resume(lm_wf, mode, temp):
    """QoS preempts a batch row beyond the first rung; it comes back
    through a longer prefill and goes on reading through the table:
    both requests' tokens are the scan decoder's."""
    import ladder_drill
    from veles_tpu.config import root
    lm, wf = lm_wf
    root.common.serving.qos = True
    try:
        engine, _ = _twins(wf, "pgd_qos_" + mode, max_slots=1,
                           buckets=(8, 48))
        req = make_request(_prompt(lm, 420, 6), 50, temperature=temp,
                           seed=7, mode=mode)
        req["priority"] = "batch"
        urgent = make_request(_prompt(lm, 421, 5), 3)
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
        _solo(wf, [req, urgent], [t_b.result["tokens"],
                                  t_i.result["tokens"]])
        assert engine.stats()["paged_share"] == 1.0
        assert engine.page_pool.in_use() == 0
    finally:
        root.common.serving.qos = False


def test_predicate_keeps_the_gather_off_the_chip_and_for_int8(lm_wf):
    """Unforced, the CPU keeps the gather; the int8 pool never reads
    through the kernel, forced or not."""
    lm, wf = lm_wf
    import ladder_drill
    plain, quant = ladder_drill.twins(wf, "pgd_pred")
    quant.quant_kv = True
    ladder_drill.serve_by_ticks(
        plain, [make_request(_prompt(lm, 480, 5), 3, seed=1)])
    assert plain._paged_blocks() == (False, False)
    assert not any(quant._paged_read(b) for b in quant.stack["blocks"])


TP_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["VELES_REPO"])
sys.path.insert(0, os.path.join(os.environ["VELES_REPO"], "tests"))
import numpy
import veles_tpu as vt
from veles_tpu import prng
from veles_tpu.serving import ContinuousEngine
from veles_tpu.serving.engine import make_request
from conftest import import_model
import ladder_drill

lm = import_model("char_lm")
prng.seed_all(971)
wf = lm.build_workflow(epochs=1, minibatch_size=64, n_blocks=2,
                       dim=32, n_train=256, n_valid=64)
wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
wf.run()
import jax
tp = jax.device_count()


def prompt(seed, length=10):
    return [int(t) for t in
            lm.make_corpus(numpy.random.RandomState(seed), length)]


reqs = [make_request(prompt(1, 10), 20),
        make_request(prompt(2, 7), 30, temperature=0.8, seed=5),
        make_request(prompt(3, 3), 12)]


def run(tp_n, paged):
    eng = ContinuousEngine(wf, max_slots=3, buckets=(8, 16, 32),
                           max_context=64, page_size=4, tp=tp_n,
                           name="pgd_tp%d_%d" % (tp_n, paged))
    if paged:
        ladder_drill.force_paged(eng)
    eng.start()
    try:
        return eng.serve([dict(r) for r in reqs]), eng.stats()
    finally:
        eng.stop()


gather, _ = run(1, False)
sharded, st = run(tp, True)
print(json.dumps({"devices": tp, "equal": gather == sharded,
                  "paged_share": st["paged_share"], "tp": st["tp"]}))
"""


def test_paged_read_per_shard_under_tp():
    """Under ``tp`` 2 every shard's kernel reads its own KV heads of the
    pool: the sharded engine reading through the table serves what the
    solo engine reading views serves (a 2-device CPU mesh, in a child
    whose device count is pinned before jax starts)."""
    from test_tp_serving import _run_child
    doc = _run_child(TP_CHILD, "0,1")
    assert doc == {"devices": 2, "equal": True, "paged_share": 1.0,
                   "tp": 2}, doc
