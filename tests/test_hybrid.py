"""The hybrid block (nn/hybrid.py, nn/delta_rule.py, nn/experts.py) at a
tiny size in float32, against the plain reference
(chipbench/reference_hybrid.py: the recurrence token by token, the experts
as a dense masked sum, nothing of the program's):

- the chunked scan is the recurrence, forward and gradient, over several
  chunks and a ragged end;
- a block of each kind and three train steps: loss, first gradient, the
  parameters' change, every leaf;
- routing with every token on one expert drops nothing;
- partial rotary leaves the other dimensions bit-equal and is ``_rope`` at
  factor 1;
- the share test: the parts that all the shares of a sparse block give,
  the shared expert counted once, add up to the uncut layer;
- the counters are the reference's routing counts;
- a ``transformer_block`` model's lowered train step is the parent's text.
"""
import hashlib
import json
import os
import sys

import numpy
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


HYBRID = load("configs", "tiny-hybrid.json")
TRAIN = dict(load("workloads", "tiny_hybrid_train.json"), chips=1)
HI = "highest"


@pytest.fixture(autouse=True)
def plain_float32():
    """float32 products on both sides, and the program's host draw put
    back for the files that this worker runs next."""
    import jax
    from veles_tpu import prng
    from veles_tpu.config import root
    keep = (prng.RandomGenerator.fill_normal,
            root.common.engine.get("mixed_precision", False),
            root.common.engine.compute_dtype)
    root.common.engine.mixed_precision = False
    root.common.engine.compute_dtype = "float32"
    with jax.default_matmul_precision(HI):
        yield
    prng.RandomGenerator.fill_normal = keep[0]
    root.common.engine.mixed_precision = keep[1]
    root.common.engine.compute_dtype = keep[2]


def rule_inputs(t, seed=0, b=2, h=3, dk=16, dv=8):
    import jax.numpy as jnp
    rng = numpy.random.default_rng(seed)
    q, k = (rng.normal(size=(b, t, h, dk)) for _ in range(2))
    q = q / numpy.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / numpy.linalg.norm(k, axis=-1, keepdims=True)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        q, k, rng.normal(size=(b, t, h, dv)),
        -rng.uniform(0, 2, size=(b, t, h)), rng.uniform(0, 1, size=(b, t, h))))


@pytest.mark.parametrize("t", [64, 160, 200, 7])
def test_the_chunked_scan_is_the_recurrence(t):
    """T of one chunk, of whole chunks plus half, of a ragged end, and
    shorter than a chunk: forward and every input's gradient."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference_hybrid
    from veles_tpu.nn.delta_rule import (chunked_delta_rule,
                                         recurrent_delta_rule)
    args = rule_inputs(t)
    want = reference_hybrid.delta_rule(*args)
    got = chunked_delta_rule(*args)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * scale
    assert float(jnp.max(jnp.abs(
        recurrent_delta_rule(*args)[0] - want))) <= 2e-5 * scale
    g_want = jax.grad(lambda *a: jnp.sum(jnp.sin(
        reference_hybrid.delta_rule(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    g_got = jax.grad(lambda *a: jnp.sum(jnp.sin(
        chunked_delta_rule(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * float(
            jnp.max(jnp.abs(b)))


def block_unit(cfg, i):
    from chipbench import reference_hybrid
    from veles_tpu.nn.hybrid import HybridBlock
    layer = dict(reference_hybrid.layer_list(cfg)[1 + i])
    layer.pop("type")
    return HybridBlock(None, **layer)


@pytest.mark.parametrize("i", [0, 3], ids=["delta_rule", "attention"])
def test_a_block_against_the_reference(i):
    import jax.numpy as jnp
    from chipbench import reference_hybrid as ref
    params = ref.make_weights(HYBRID, 11)["blk%d" % i]
    x = jnp.asarray(numpy.random.default_rng(3).normal(
        size=(2, 96, HYBRID["hidden_size"])), jnp.float32)
    want = ref._block(params, x, HYBRID, ref.is_attention(HYBRID, i), None)
    got = block_unit(HYBRID, i).apply(params, x, train=True)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def three_steps():
    """Three train steps of the tiny hybrid through StandardWorkflow and
    TrainStep in float32, and the reference's three on the same rows."""
    import jax
    from veles_tpu import prng
    from veles_tpu.backends import XLADevice
    from veles_tpu.config import root
    from veles_tpu.telemetry.counters import counters, histograms
    from chipbench import model_file, reference_hybrid as ref
    keep = (prng.RandomGenerator.fill_normal,
            root.common.engine.get("mixed_precision", False),
            root.common.engine.compute_dtype)
    root.common.engine.mixed_precision = False
    root.common.engine.compute_dtype = "float32"
    seed = 5
    try:
        with jax.default_matmul_precision(HI):
            model_file.skip_host_draw()
            wf = model_file.build_workflow(HYBRID, TRAIN, seed)
            wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
            step, loader = wf.train_step, wf.loader
            step.params = model_file.place_like(
                ref.make_weights(HYBRID, seed), step.params)
            before = (counters.snapshot(), histograms.snapshot())
            fed, losses, grad1 = [], [], None
            for n in range(TRAIN["rows_per_epoch"] // TRAIN["minibatch"]):
                loader.run()
                if n < 3:
                    fed.append(loader.minibatch_indices.map_read()
                               [:loader.minibatch_size].copy().tolist())
                step.run()
                if n < 3:
                    losses.append(float(step.last_loss))
                if n == 0:
                    grad1 = jax.device_get(ref.leaf_norms(
                        {u: s["m"] for u, s in step.opt_state.items()}))
                if n == 2:
                    delta = ref.floats(jax.device_get(ref.delta_norms(
                        step.params, HYBRID, seed)))
            assert bool(loader.epoch_ended)
            step.drain_epoch_blocks()
            after = (counters.snapshot(), histograms.snapshot())
            rows = ref.make_tokens(seed, TRAIN["rows_per_epoch"],
                                   TRAIN["seq_len"], HYBRID["vocab_size"])
            batches = [rows[idx] for idx in fed]
            want = ref.train_reference(HYBRID, seed, batches,
                                       TRAIN["learning_rate"])
    finally:
        prng.RandomGenerator.fill_normal = keep[0]
        root.common.engine.mixed_precision = keep[1]
        root.common.engine.compute_dtype = keep[2]
    got = {"loss": losses, "delta": delta, "grad1": {
        u: {k: float(x) / (1.0 - 0.9) for k, x in leaves.items()}
        for u, leaves in grad1.items()}}
    return got, want, before, after, batches


def test_three_train_steps_every_leaf(three_steps):
    from chipbench import check
    got, want = three_steps[:2]
    for a, b in zip(got["loss"], want["loss"]):
        assert a == pytest.approx(b, rel=2e-6)
    assert set(got["grad1"]) == set(want["grad1"])
    median = numpy.median([g for leaves in want["grad1"].values()
                           for g in leaves.values()])
    for unit, leaves in want["grad1"].items():
        assert set(got["grad1"][unit]) == set(leaves)
        for leaf, g in leaves.items():
            assert abs(got["grad1"][unit][leaf] - g) <= 2e-4 * max(
                g, median), (unit, leaf)
    still = check.still_leaves(want["grad1"])
    worst, where = check.norm_gap(got["delta"], want["delta"], skip=still)
    assert worst <= 2e-3, where


def test_the_counters_are_the_reference_s_routing_counts(three_steps):
    """What the step counted and drained with the epoch's metrics, against
    the reference's own routing of the same rows on the seed's weights
    (the first step's; the later steps' by their totals)."""
    import jax.numpy as jnp
    from chipbench import reference_hybrid as ref
    from veles_tpu.nn.experts import ASSIGNED, HELD, PEAK_LOAD
    _, _, before, after, batches = three_steps
    steps = TRAIN["rows_per_epoch"] // TRAIN["minibatch"]
    layers = HYBRID["num_hidden_layers"]
    tokens = TRAIN["minibatch"] * TRAIN["seq_len"]

    def rise(name):
        return after[0].get(name, 0) - before[0].get(name, 0)
    assert rise(ASSIGNED) == steps * layers * tokens * HYBRID[
        "num_experts_per_tok"]
    hist = after[1][PEAK_LOAD]
    was = before[1].get(PEAK_LOAD, {"count": 0, "sum": 0.0})
    assert hist["count"] - was["count"] == steps * layers
    assert 0 < rise(HELD) < rise(ASSIGNED)
    # the first layer of the first step, routed by the reference
    params = ref.make_weights(HYBRID, 5)
    rows = jnp.asarray(batches[0])
    x = jnp.take(params["embed"]["table"], rows[:, :-1], axis=0)
    x = ref._norm(x + ref._delta_layer(
        params["blk0"], ref._norm(x, params["blk0"]["ln1_w"], 1e-6), HYBRID,
        None), params["blk0"]["ln2_w"], 1e-6)
    dense = numpy.asarray(ref.route(params["blk0"], x.reshape(tokens, -1),
                                    HYBRID))
    loads = (dense[:, ref.held_ids(HYBRID)] > 0).sum(axis=0)
    # every step's layers hold about that many; the peak is a held
    # expert's load, so the histogram's mean lies between mean and total
    mean_held = rise(HELD) / (steps * layers)
    assert abs(mean_held - loads.sum()) <= 0.2 * loads.sum()
    peak_mean = (hist["sum"] - was["sum"]) / (steps * layers)
    assert mean_held / len(loads) <= peak_mean <= mean_held


def experts_case(top_k, held, routed=16, n=96, seed=2, positive=False):
    import jax.numpy as jnp
    from chipbench import reference_hybrid as ref
    cfg = dict(HYBRID, num_routed_experts=routed, num_experts=len(held),
               experts_held_first=held[0], num_experts_per_tok=top_k,
               num_hidden_layers=1, full_attention_interval=4)
    p = ref.make_weights(cfg, seed)["blk0"]
    x = numpy.random.default_rng(seed).normal(size=(1, n, cfg["hidden_size"]))
    x = jnp.asarray(numpy.abs(x) if positive else x, jnp.float32)
    return cfg, p, x


def program_experts(cfg, p, x, block=8):
    from veles_tpu.nn.delta_rule import plain_scope
    from veles_tpu.nn.experts import sparse_experts
    from chipbench import reference_hybrid as ref
    local_of = numpy.full((cfg["num_routed_experts"],), -1, numpy.int32)
    held = ref.held_ids(cfg)
    local_of[held] = numpy.arange(len(held))
    return sparse_experts(p, x, top_k=cfg["num_experts_per_tok"],
                          local_of=local_of, n_held=len(held),
                          precision=None, scope=plain_scope, block=block)


def test_every_token_on_one_expert_drops_nothing():
    """The router sends all 96 tokens to expert 6 (twelve blocks of eight
    rows of one expert): the result is the reference's, whose experts see
    every token."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference_hybrid as ref
    from veles_tpu.telemetry import steptaps
    from veles_tpu.nn.experts import HELD, PEAK_LOAD
    cfg, p, x = experts_case(1, list(range(4, 12)), positive=True)
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, 6].set(1.0))
    with steptaps.collecting() as taps:
        got = program_experts(cfg, p, x)
    want = ref._experts(p, x[0], cfg, None)
    assert float(jnp.max(jnp.abs(got[0] - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))
    assert float(taps[steptaps.counter_key(HELD)]) == 96
    assert float(taps[steptaps.histogram_key(PEAK_LOAD, "sum")]) == 96
    # and its gradient, through the loop's own backward pass
    g_got = jax.grad(lambda q: jnp.sum(jnp.sin(program_experts(cfg, q, x))))(p)
    g_want = jax.grad(lambda q: jnp.sum(jnp.sin(
        ref._experts(q, x[0], cfg, None))))(p)
    for leaf in ("e_gate", "e_up", "e_down", "s_mix", "s_down"):
        assert float(jnp.max(jnp.abs(g_got[leaf] - g_want[leaf]))) <= \
            2e-5 * float(jnp.max(jnp.abs(g_want[leaf]))), leaf


def test_the_shares_add_up_to_the_layer():
    """THE SHARE TEST. Sixteen experts over four chips, four each: the
    four parts of a sparse block's result, the shared expert (which every
    chip computes alike) counted once, add up to what the reference gives
    for the uncut layer with all sixteen held."""
    import jax.numpy as jnp
    from chipbench import reference_hybrid as ref
    whole_cfg, whole_p, x = experts_case(4, list(range(16)))
    want = ref._experts(whole_p, x[0], whole_cfg, None)
    shared = ref._experts(
        dict(whole_p, e_down=jnp.zeros_like(whole_p["e_down"])), x[0],
        whole_cfg, None)
    total = jnp.zeros_like(want)
    for share in range(4):
        held = list(range(4 * share, 4 * share + 4))
        cfg = dict(whole_cfg, num_experts=4, experts_held_first=held[0])
        p = dict(whole_p, **{k: whole_p[k][held[0]:held[0] + 4]
                             for k in ("e_gate", "e_up", "e_down")})
        part = program_experts(cfg, p, x)[0]
        # the reference, given the same share, gives the same part
        assert float(jnp.max(jnp.abs(part - ref._experts(
            p, x[0], cfg, None)))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
        total = total + (part - shared)
    assert float(jnp.max(jnp.abs(total + shared - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(shared))) > 0


def test_partial_rotary():
    import jax.numpy as jnp
    from chipbench import reference_hybrid as ref
    from veles_tpu.nn.hybrid import partial_rope
    from veles_tpu.nn.transformer import _rope
    x = jnp.asarray(numpy.random.default_rng(1).normal(size=(2, 9, 3, 16)),
                    jnp.float32)
    got = partial_rope(jnp, x, 1e7, 4)
    assert (numpy.asarray(got[..., 4:]) == numpy.asarray(x[..., 4:])).all()
    assert not (numpy.asarray(got[:, 1:, :, :4])
                == numpy.asarray(x[:, 1:, :, :4])).all()
    assert float(jnp.max(jnp.abs(got - ref._rope_part(x, 1e7, 4)))) <= 1e-6
    assert (numpy.asarray(partial_rope(jnp, x, 1e7, 16))
            == numpy.asarray(_rope(jnp, x, 1e7))).all()
    assert (numpy.asarray(partial_rope(numpy, numpy.asarray(x), 1e7, 4))
            [..., 4:] == numpy.asarray(x[..., 4:])).all()


#: sha256 of ``jit(TrainStep._train_step_fn).lower(...).as_text()`` for
#: ``tiny`` under ``tiny_train`` (two ``transformer_block`` layers), made on
#: the parent commit b09c58f with and without ``--mixed-precision``
PARENT_TEXT = {
    True: "f3c766cbf1e621b7b61389bae958323e82130ab289e36581ff71d46c2c6bdb62",
    False: "6f626a0ad14d7fc633dbe5142a600736a7ffcc38ed69892dd448fcf0d1d77fdc",
}


@pytest.mark.parametrize("mixed", [True, False],
                         ids=["mixed_precision", "float32"])
def test_a_transformer_block_s_lowered_step_is_the_parent_s(mixed):
    """What this PR put into TrainStep (leaves kept in float32, counters
    out of the step) leaves a model that uses neither the text it had."""
    import jax
    from veles_tpu.backends import XLADevice
    from veles_tpu.config import root
    from chipbench import model_file
    root.common.engine.mixed_precision = mixed
    root.common.engine.compute_dtype = "bfloat16"
    with jax.default_matmul_precision("default"):
        wf = model_file.build_workflow(
            load("configs", "tiny.json"),
            dict(load("workloads", "tiny_train.json"), chips=1), seed=1)
        wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
        step = wf.train_step
        wf.loader.run()
        dataset, labels, targets, indices, mask = step._inputs()
        text = jax.jit(step._train_step_fn, donate_argnums=(0, 1, 2)).lower(
            step.params, step.opt_state, step._make_zero_accum(mon=True),
            dataset, labels, targets, indices, mask,
            jax.numpy.float32(1.0), step._rng.jax_key()).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[mixed]
