"""Latent attention (``nn/hybrid.py`` ``mixer="latent"``) with the scaled
sigmoid experts and their ungated shared ones (``nn/experts.py``) at
``tiny-mla`` in float32, against the plain reference
(``chipbench/reference_mla.py``: MLA decompressed per head, attention by
blocks of queries, the experts a dense masked sum, nothing of the
program's):

- a dense block and an expert block, forward and every leaf's gradient;
  and two faults (the rope head left on one head, the routed scale
  dropped) that the same tolerance catches;
- the same block through the flash kernel (interpreted) at keys wider
  than values;
- three train steps through ``StandardWorkflow`` and ``TrainStep``: loss,
  first gradient and the parameters' change of every leaf; the selection's
  correction unmoved;
- the share test: the 8 chips' parts of an expert layer, the shared
  experts counted once, add up to the uncut layer.
"""
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


MLA = load("configs", "tiny-mla.json")
TRAIN = dict(load("workloads", "tiny_mla_train.json"), chips=1)
HI = "highest"
#: the block's output and its gradients, against the largest entry of the
#: reference's: float32 both sides, in another order of operations
TOL = 2e-5


@pytest.fixture(autouse=True)
def plain_float32():
    """float32 products on both sides, and the program's host draw and
    flash setting put back for the files that this worker runs next."""
    import jax
    from veles_tpu import prng
    from veles_tpu.config import root
    keep = (prng.RandomGenerator.fill_normal,
            root.common.engine.get("mixed_precision", False),
            root.common.engine.compute_dtype,
            root.common.engine.flash_attention)
    root.common.engine.mixed_precision = False
    root.common.engine.compute_dtype = "float32"
    with jax.default_matmul_precision(HI):
        yield
    prng.RandomGenerator.fill_normal = keep[0]
    root.common.engine.mixed_precision = keep[1]
    root.common.engine.compute_dtype = keep[2]
    root.common.engine.flash_attention = keep[3]


def block_unit(cfg, i, **changed):
    from chipbench import reference_mla
    from veles_tpu.nn.hybrid import HybridBlock
    layer = dict(reference_mla.layer_list(cfg)[1 + i], **changed)
    layer.pop("type")
    return HybridBlock(None, **layer)


def gap(got, want):
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(got - want))) / float(
        jnp.max(jnp.abs(want)))


def block_case(i, t=96, seed=11):
    import jax.numpy as jnp
    from chipbench import reference_mla as ref
    params = ref.make_weights(MLA, seed)["blk%d" % i]
    x = jnp.asarray(numpy.random.default_rng(3).normal(
        size=(2, t, MLA["hidden_size"])), jnp.float32)
    return params, x


def block_and_grads(apply, params, x):
    import jax
    import jax.numpy as jnp

    def loss(p, x_):
        return jnp.sum(jnp.sin(apply(p, x_)))
    return (jax.jit(apply)(params, x),
            jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x))


def reference_block(i):
    from chipbench import reference_mla as ref
    return lambda p, x: ref._block(p, x, MLA, ref.is_dense(MLA, i), None)


def worst_gap(got, want):
    (out, (g, dx)), (w_out, (w_g, w_dx)) = got, want
    assert set(g) == set(w_g)
    return max([gap(out, w_out), gap(dx, w_dx)]
               + [gap(g[k], w_g[k]) for k in w_g if float(
                   abs(w_g[k]).max()) > 0])


@pytest.mark.parametrize("i", [0, 1], ids=["dense", "experts"])
def test_a_block_against_the_reference(i):
    params, x = block_case(i)
    unit = block_unit(MLA, i)
    got = block_and_grads(
        lambda p, x_: unit.apply(p, x_, train=True), params, x)
    want = block_and_grads(reference_block(i), params, x)
    assert worst_gap(got, want) <= TOL


def test_the_faults_are_caught():
    """The rope key on the first head alone (the others' rope part left
    nought), and the routed scale dropped: each moves the block far past
    the tolerance."""
    import jax.numpy as jnp
    from chipbench import reference_mla as ref
    params, x = block_case(1)
    want = block_and_grads(reference_block(1), params, x)
    dropped = block_unit(MLA, 1, routed_scale=1.0)
    got = block_and_grads(
        lambda p, x_: dropped.apply(p, x_, train=True), params, x)
    assert worst_gap(got, want) > 100 * TOL
    real = ref._rope_key
    ref._rope_key = lambda k_pe, h, theta: jnp.pad(
        real(k_pe, 1, theta), ((0, 0), (0, 0), (0, h - 1), (0, 0)))
    try:
        broken = block_and_grads(reference_block(1), params, x)
    finally:
        ref._rope_key = real
    unit = block_unit(MLA, 1)
    got = block_and_grads(
        lambda p, x_: unit.apply(p, x_, train=True), params, x)
    assert worst_gap(got, broken) > 100 * TOL


def test_a_block_through_the_flash_kernel():
    """Keys of 24 over values of 12, 128 positions: the interpreted flash
    kernels, forward and backward pair, give the reference's block."""
    from veles_tpu.config import root
    from veles_tpu.telemetry.counters import counters
    root.common.engine.flash_attention = "force"
    params, x = block_case(1, t=128)
    unit = block_unit(MLA, 1)
    before = counters.snapshot().get("veles_flash_attention_traces_total", 0)
    got = block_and_grads(
        lambda p, x_: unit.apply(p, x_, train=True), params, x)
    assert counters.snapshot()["veles_flash_attention_traces_total"] > before
    assert worst_gap(got, block_and_grads(reference_block(1), params, x)) \
        <= TOL


@pytest.fixture(scope="module")
def three_steps():
    """Three train steps of tiny-mla through StandardWorkflow and
    TrainStep in float32, and the reference's three on the same rows."""
    import jax
    from veles_tpu import prng
    from veles_tpu.backends import XLADevice
    from veles_tpu.config import root
    from chipbench import model_file, reference_mla as ref
    keep = (prng.RandomGenerator.fill_normal,
            root.common.engine.get("mixed_precision", False),
            root.common.engine.compute_dtype)
    root.common.engine.mixed_precision = False
    root.common.engine.compute_dtype = "float32"
    seed = 2 ** 33 + 5
    try:
        with jax.default_matmul_precision(HI):
            model_file.skip_host_draw()
            wf = model_file.build_workflow(MLA, TRAIN, seed)
            wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
            step, loader = wf.train_step, wf.loader
            step.params = model_file.place_like(
                ref.make_weights(MLA, seed), step.params)
            fed, losses, grad1 = [], [], None
            for n in range(3):
                loader.run()
                fed.append(loader.minibatch_indices.map_read()
                           [:loader.minibatch_size].copy().tolist())
                step.run()
                losses.append(float(step.last_loss))
                if n == 0:
                    grad1 = jax.device_get(ref.leaf_norms(
                        {u: s["m"] for u, s in step.opt_state.items()}))
            delta = ref.floats(jax.device_get(ref.delta_norms(
                step.params, MLA, seed)))
            rows = ref.make_tokens(seed, TRAIN["rows_per_epoch"],
                                   TRAIN["seq_len"], MLA["vocab_size"])
            want = ref.train_reference(MLA, seed, [rows[i] for i in fed],
                                       TRAIN["learning_rate"])
    finally:
        prng.RandomGenerator.fill_normal = keep[0]
        root.common.engine.mixed_precision = keep[1]
        root.common.engine.compute_dtype = keep[2]
    got = {"loss": losses, "delta": delta, "grad1": {
        u: {k: float(x) / (1.0 - 0.9) for k, x in leaves.items()}
        for u, leaves in grad1.items()}}
    return got, want


def test_three_train_steps_every_leaf(three_steps):
    from chipbench import check
    got, want = three_steps
    for a, b in zip(got["loss"], want["loss"]):
        assert a == pytest.approx(b, rel=2e-6)
    assert set(got["grad1"]) == set(want["grad1"]) >= {"norm", "head"}
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
    # the selection's correction gets no gradient, so Adam leaves it as
    # it was (its "change" is the seed's weights made again, to the bit
    # the same on both sides)
    for unit in ("blk1", "blk2"):
        assert want["grad1"][unit]["router_bias"] == 0.0
        assert got["grad1"][unit]["router_bias"] == 0.0
        assert got["delta"][unit]["router_bias"] == \
            want["delta"][unit]["router_bias"]
        assert "s_mix" not in got["delta"][unit]


class Frozen(dict):
    """A configuration that can be a static argument of a jitted call."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def program_experts(cfg, p, x, block=8):
    from veles_tpu.nn.delta_rule import plain_scope
    from veles_tpu.nn.experts import sparse_experts
    from chipbench import reference_mla as ref
    local_of = numpy.full((cfg["router_width"],), -1, numpy.int32)
    held = ref.held_ids(cfg)
    local_of[held] = numpy.arange(len(held))
    return sparse_experts(p, x, top_k=cfg["num_experts_per_tok"],
                          local_of=local_of, n_held=len(held),
                          precision=None, scope=plain_scope, block=block,
                          router="sigmoid",
                          routed_scale=cfg["routed_scaling_factor"],
                          shared_gate=False)


def test_the_shares_add_up_to_the_layer():
    """THE SHARE TEST. Sixteen experts over eight chips, two each: the
    eight parts of an expert layer's result, the shared experts (which
    every chip computes alike) counted once, add up to what the reference
    gives for the uncut layer with all sixteen held."""
    import jax.numpy as jnp
    from chipbench import reference_mla as ref
    whole_cfg = dict(MLA, n_routed_experts=16, experts_held_first=0,
                     num_hidden_layers=2)
    whole_p = ref.make_weights(whole_cfg, 2)["blk1"]
    x = jnp.asarray(numpy.random.default_rng(2).normal(
        size=(1, 96, MLA["hidden_size"])), jnp.float32)
    import jax
    experts = jax.jit(ref._experts, static_argnums=(2, 3))
    want = experts(whole_p, x[0], Frozen(whole_cfg), None)
    shared = ref._swiglu(x[0], whole_p["s_gate"], whole_p["s_up"],
                         whole_p["s_down"], None)
    total = jnp.zeros_like(want)
    for share in range(8):
        held = [2 * share, 2 * share + 1]
        cfg = dict(whole_cfg, n_routed_experts=2, experts_held_first=held[0])
        p = dict(whole_p, **{k: whole_p[k][held[0]:held[0] + 2]
                             for k in ("e_gate", "e_up", "e_down")})
        part = jax.jit(program_experts, static_argnums=0)(Frozen(cfg), p, x)
        total = total + (part[0] - shared)
    assert gap(total + shared, want) <= 1e-5
    assert float(jnp.max(jnp.abs(shared))) > 0


@pytest.mark.parametrize("tokens, top_k, experts, rows", [
    (8192, 10, 512, 256),   # qwen3-next: 160 expected, the least block
    (8192, 6, 64, 1024),    # moonlight: 768 expected
    (8192, 8, 256, 256),    # mimo: 256 expected
    (96, 2, 8, 256),        # the tiny blocks
    (65536, 8, 256, 2048)])
def test_a_training_block_holds_an_expert_s_expected_rows(tokens, top_k,
                                                          experts, rows):
    from veles_tpu.nn.experts import block_rows_for
    assert block_rows_for(tokens, top_k, experts) == rows


def test_an_expert_layer_s_result_is_the_same_whatever_its_block():
    """Blocks of one expert's rows only group the work: a layer's result is
    the same in blocks of 8 rows as in one block that holds every row."""
    import jax
    from chipbench import reference_mla as ref
    p = ref.make_weights(MLA, 3)["blk1"]
    x = numpy.random.default_rng(3).normal(
        size=(1, 96, MLA["hidden_size"])).astype(numpy.float32)
    run = jax.jit(program_experts, static_argnums=(0, 3))
    small = run(Frozen(MLA), p, x, 8)
    whole = run(Frozen(MLA), p, x, 96 * MLA["num_experts_per_tok"])
    assert gap(small, whole) <= 1e-6
