"""End-to-end training through StandardWorkflow: the full fused-step loop
(Repeater → Loader → TrainStep → Decision) must converge on synthetic
separable data. Mirrors the reference's model-convergence tests (the Znicz
MNIST regression tests, SURVEY.md §4)."""
import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn
from veles_tpu.loader import FullBatchLoader, TRAIN, VALID, TEST


class BlobsLoader(FullBatchLoader):
    """3-class Gaussian blobs: 600 train / 150 valid / 90 test."""

    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(7)
        n_per, d, k = 280, 10, 3
        centers = rng.randn(k, d) * 3
        data, labels = [], []
        for c in range(k):
            data.append(centers[c] + rng.randn(n_per, d))
            labels.append(numpy.full(n_per, c))
        data = numpy.concatenate(data).astype(numpy.float32)
        labels = numpy.concatenate(labels).astype(numpy.int32)
        perm = rng.permutation(len(data))
        data, labels = data[perm], labels[perm]
        self.create_originals(data, labels)
        self.class_lengths = [90, 150, 600]


def make_workflow(minibatch_size=50, **decision_kw):
    loader = BlobsLoader(None, minibatch_size=minibatch_size, name="blobs")
    wf = nn.StandardWorkflow(
        name="blobs-train",
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 16},
            {"type": "softmax", "output_sample_shape": 3},
        ],
        loader_unit=loader,
        loss_function="softmax",
        decision_config=dict(max_epochs=12, fail_iterations=50,
                             **decision_kw),
    )
    return wf


def test_training_converges():
    wf = make_workflow()
    dev = vt.XLADevice(mesh_axes={"data": 1})
    wf.initialize(device=dev)
    wf.run()
    assert bool(wf.stopped)
    d = wf.decision
    assert d.epoch_number == 12
    # separable blobs: validation error should collapse under 5%
    assert d.best_metric is not None
    assert d.best_metric < 0.05, d.epoch_metrics
    # all three sets were evaluated
    for s in (TEST, VALID, TRAIN):
        assert len(d.epoch_metrics[s]) == 12


def test_metrics_and_results():
    wf = make_workflow()
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    res = wf.gather_results()
    assert "best_err" in res and res["best_err"] < 0.05
    assert res["epochs"] == 12


def test_trained_params_reach_arrays():
    """After stop, TrainStep must sync device params back into the forward
    units' Arrays (snapshot coherence)."""
    wf = make_workflow()
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    w_before = wf.forwards[0].weights.map_read().copy()
    wf.run()
    w_after = wf.forwards[0].weights.map_read()
    assert not numpy.allclose(w_before, w_after)


def test_data_parallel_8dev_matches_semantics():
    """Same workflow on an 8-device data mesh: XLA SPMD partitioning of the
    fused step (batch sharded over 'data') must still converge — the psum
    equivalent of the reference's master-slave averaging."""
    wf = make_workflow(minibatch_size=48)
    dev = vt.XLADevice(mesh_axes={"data": 8})
    assert dev.mesh.devices.size == 8
    wf.initialize(device=dev)
    step = wf.train_step
    assert step._shardings is not None
    wf.run()
    assert wf.decision.best_metric < 0.05
    # params replicated over all 8 devices; minibatch indices sharded
    w = step.params[wf.forwards[0].name]["weights"]
    assert len(w.sharding.device_set) == 8
    idx = wf.loader.minibatch_indices.devmem
    assert len(idx.sharding.device_set) == 8
    assert not idx.sharding.is_fully_replicated


def test_data_parallel_requires_divisible_minibatch():
    wf = make_workflow(minibatch_size=50)
    dev = vt.XLADevice(mesh_axes={"data": 8})
    with pytest.raises(vt.Bug):
        wf.initialize(device=dev)


def test_extract_forward_workflow_inference():
    """Inference extraction: trained forwards chained, fed a real batch
    (must NOT see the never-filled fused minibatch zeros)."""
    wf = make_workflow()
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()
    fwf = wf.extract_forward_workflow()
    x = wf.loader.original_data.mem[:20]
    y_true = wf.loader.original_labels.mem[:20]
    from veles_tpu.memory import Array
    wf.forwards[0].input = Array(x, name="x")
    fwf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    fwf.run()
    probs = wf.forwards[-1].output.map_read()
    assert probs.shape == (20, 3)
    acc = (probs.argmax(1) == y_true).mean()
    assert acc > 0.9, acc


def test_layer_config_reaches_gd_units():
    """Per-layer learning_rate/weights_decay must reach the GD units."""
    loader = BlobsLoader(None, minibatch_size=50, name="blobs")
    wf = nn.StandardWorkflow(
        name="lr-check",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 4,
                 "learning_rate": 0.05, "weights_decay": 1e-3,
                 "gradient_moment": 0.9}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=1))
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    gd = wf.train_step.gds[0]
    assert gd.learning_rate == 0.05
    assert gd.weight_decay == 1e-3
    assert gd.momentum == 0.9


def test_mixed_precision_converges():
    """AMP knob (root.common.engine.mixed_precision): forward/backward on
    a bf16 cast of params+batch (activation storage halves — the HBM
    lever for image-scale conv nets), f32 masters/loss. Must converge
    like the f32 run and leave master params f32."""
    import jax.numpy as jnp
    from veles_tpu.config import root
    root.common.engine.mixed_precision = True
    try:
        wf = make_workflow()
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.train_step.mixed_precision
        wf.run()
    finally:
        root.common.engine.mixed_precision = False
    d = wf.decision
    assert d.best_metric is not None
    assert d.best_metric < 0.05, d.epoch_metrics
    for tree in wf.train_step.params.values():
        for leaf in tree.values():
            assert leaf.dtype == jnp.float32


def test_evaluation_mode_downgrades_block_dispatch():
    """--test of a config trained with epochs_per_dispatch>1 is a
    capability, not an error: entering evaluation mode downgrades the
    loader's block serving to the classic per-epoch loop (a fused
    H-epoch block would re-evaluate the same sets H times). Mirrors
    launcher._enter_test_mode's sequence. Params must not move."""
    import jax
    from veles_tpu import prng
    prng.seed_all(123)
    loader = BlobsLoader(None, minibatch_size=50, name="blobs-evb")
    wf = nn.StandardWorkflow(
        name="evb",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 3}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=1, fail_iterations=50),
        epochs_per_dispatch=4)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    assert loader.block_epochs == 4
    step = wf.train_step
    step.evaluation_mode = True
    assert loader.block_epochs == 1
    before = jax.device_get(step.params)
    wf.run()
    assert wf.decision.epoch_number == 1
    after = jax.device_get(step.params)
    for name, tree in before.items():
        for k, v in tree.items():
            numpy.testing.assert_array_equal(numpy.asarray(after[name][k]),
                                             numpy.asarray(v))


def test_epoch_block_matches_classic():
    """epochs_per_dispatch=H fuses H whole epochs (eval+train) into ONE
    device dispatch; the Decision replays per-epoch bookkeeping from the
    stacked accums. Same seed → the trajectory and final weights must
    match the classic per-epoch loop."""
    import jax
    from veles_tpu import prng

    def run(h):
        prng.seed_all(99)
        loader = BlobsLoader(None, minibatch_size=50, name="blobs-blk")
        wf = nn.StandardWorkflow(
            name="blk-%d" % h,
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 3},
            ],
            loader_unit=loader, loss_function="softmax",
            decision_config=dict(max_epochs=12, fail_iterations=50),
            lr_schedule=nn.exp_decay(0.95),
            epochs_per_dispatch=h,
        )
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        wf.run()
        d = wf.decision
        return {
            "train": numpy.asarray(d.epoch_metrics[TRAIN]),
            "valid": numpy.asarray(d.epoch_metrics[VALID]),
            "test": numpy.asarray(d.epoch_metrics[TEST]),
            "epochs": d.epoch_number,
            "w": numpy.asarray(jax.device_get(
                wf.train_step.params[wf.forwards[0].name]["weights"])),
        }

    classic = run(1)
    for h in (4, 5):
        # h=5 does NOT divide max_epochs=12: the final block clamps to
        # the 2 remaining epochs, so the weights stop exactly at the cap
        block = run(h)
        assert classic["epochs"] == block["epochs"] == 12
        for k in ("train", "valid", "test"):
            assert classic[k].shape == block[k].shape == (12,)
            numpy.testing.assert_allclose(block[k], classic[k],
                                          atol=0.02)
        numpy.testing.assert_allclose(block["w"], classic["w"],
                                      rtol=2e-3, atol=2e-4)


def test_plan_height_clamps_to_class_ceiling():
    """The plan height is static and fully scanned, so rows past the
    class ceiling are mask-zero dead compute. A large minibatch
    (ceil(600/200)=3 < the default 16 steps) must clamp plan_steps at
    initialize — and the clamped run must trace the SAME trajectory as
    an explicit steps_per_dispatch=3 config (the clamp removes only
    dead rows). Found on chip: the mb=256 conv-AE burned 12/16 plan
    rows masked, quadrupling the work per served sample."""
    import jax
    from veles_tpu import prng

    def run(steps):
        prng.seed_all(123)
        loader = BlobsLoader(None, minibatch_size=200, name="blobs-big")
        wf = nn.StandardWorkflow(
            name="clamp-%s" % steps,
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 3},
            ],
            loader_unit=loader, loss_function="softmax",
            decision_config=dict(max_epochs=6, fail_iterations=50),
            steps_per_dispatch=steps,
        )
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.loader.plan_steps == 3        # clamped (or explicit)
        wf.run()
        return {
            "valid": numpy.asarray(wf.decision.epoch_metrics[VALID]),
            "w": numpy.asarray(jax.device_get(
                wf.train_step.params[wf.forwards[0].name]["weights"])),
        }

    clamped = run(16)       # default-style config, clamp kicks in
    explicit = run(3)       # exactly-sized plan, no dead rows either
    numpy.testing.assert_array_equal(clamped["valid"],
                                     explicit["valid"])
    numpy.testing.assert_array_equal(clamped["w"], explicit["w"])


def test_epoch_block_with_data_axis():
    """Block dispatch composes with data parallelism: plans shard over
    the minibatch axis, trajectory still converges."""
    from veles_tpu import prng
    prng.seed_all(99)
    loader = BlobsLoader(None, minibatch_size=48, name="blobs-blk8")
    wf = nn.StandardWorkflow(
        name="blk-dp",
        layers=[
            {"type": "all2all_tanh", "output_sample_shape": 16},
            {"type": "softmax", "output_sample_shape": 3},
        ],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=8, fail_iterations=50),
        epochs_per_dispatch=4,
    )
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 8}))
    wf.run()
    d = wf.decision
    assert d.epoch_number == 8
    assert d.best_metric is not None and d.best_metric < 0.05, \
        d.epoch_metrics


def test_block_drain_improved_flag_ors_over_epochs():
    """The snapshot gate reads `improved` once per drain: improvement at
    an INTERIOR epoch of a block must leave it True even if the final
    epochs plateau (else best models never snapshot under long blocks)."""
    from veles_tpu.nn.decision import DecisionGD
    from veles_tpu.mutable import Bool

    class FakeLoader:
        epoch_ended = Bool(True)

    class FakeStep:
        def __init__(self, blocks):
            self.blocks = blocks

        def drain_epoch_blocks(self):
            return self.blocks

    wf = vt.Workflow(name="t")
    d = DecisionGD(wf, max_epochs=10)
    d.loader = FakeLoader()
    # err improves at epoch 2 of 4, then plateaus
    d.step_unit = FakeStep([
        {TRAIN: {"n_err": 50.0, "n_samples": 100.0}},
        {TRAIN: {"n_err": 10.0, "n_samples": 100.0}},
        {TRAIN: {"n_err": 30.0, "n_samples": 100.0}},
        {TRAIN: {"n_err": 30.0, "n_samples": 100.0}},
    ])
    d.run()
    assert d.epoch_number == 4
    assert d.best_metric == 0.1 and d.best_epoch == 2
    assert bool(d.improved)      # interior improvement kept visible


def test_mixed_precision_composes_with_remat():
    """AMP + remat: jax.checkpoint wraps the bf16 forward — both knobs
    on together must still converge with f32 masters."""
    import jax.numpy as jnp
    from veles_tpu.config import root
    from veles_tpu import prng
    prng.seed_all(5)
    root.common.engine.mixed_precision = True
    try:
        loader = BlobsLoader(None, minibatch_size=50, name="blobs-ar")
        wf = nn.StandardWorkflow(
            name="amp-remat",
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 3},
            ],
            loader_unit=loader, loss_function="softmax",
            decision_config=dict(max_epochs=8, fail_iterations=50),
            remat=True,
        )
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.train_step.mixed_precision and wf.train_step.remat
        wf.run()
    finally:
        root.common.engine.mixed_precision = False
    d = wf.decision
    assert d.best_metric is not None and d.best_metric < 0.05, \
        d.epoch_metrics
    for tree in wf.train_step.params.values():
        for leaf in tree.values():
            assert leaf.dtype == jnp.float32


def test_bf16_dataset_storage_converges():
    """engine.dataset_dtype='bfloat16': dataset stored/staged at half
    width (the HBM lever for image data); training on the bf16
    dataset must still converge."""
    from veles_tpu.config import root
    from veles_tpu import prng
    import jax.numpy as jnp
    prng.seed_all(6)
    root.common.engine.dataset_dtype = "bfloat16"
    try:
        wf = make_workflow()
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.loader.original_data.mem.dtype == jnp.bfloat16
        wf.run()
    finally:
        root.common.engine.dataset_dtype = None
    assert wf.decision.best_metric is not None
    assert wf.decision.best_metric < 0.06, wf.decision.epoch_metrics


def test_grad_accumulation_matches_direct_step():
    """grad_accumulation=G: G sequential chunk backwards + ONE update
    from the valid-weighted mean gradient must reproduce the direct
    full-minibatch step (no dropout in this net, so the only
    difference is reduction order)."""
    import jax
    from veles_tpu import prng

    def run(ga):
        prng.seed_all(321)
        loader = BlobsLoader(None, minibatch_size=50, name="blobs-ga")
        wf = nn.StandardWorkflow(
            name="ga-%d" % ga,
            layers=[{"type": "all2all_tanh", "output_sample_shape": 16},
                    {"type": "softmax", "output_sample_shape": 3}],
            loader_unit=loader, loss_function="softmax",
            decision_config=dict(max_epochs=6, fail_iterations=50),
            grad_accumulation=ga)
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.train_step.grad_accumulation == ga
        wf.run()
        return (numpy.asarray(wf.decision.epoch_metrics[VALID]),
                numpy.asarray(jax.device_get(
                    wf.train_step.params["a2a0"]["weights"])
                    if "a2a0" in wf.train_step.params else
                    jax.device_get(list(
                        wf.train_step.params.values())[0]["weights"])))

    e1, w1 = run(1)
    e2, w2 = run(5)
    numpy.testing.assert_allclose(e2, e1, atol=0.025)
    numpy.testing.assert_allclose(w2, w1, rtol=2e-3, atol=2e-4)


def test_grad_accumulation_refuses_pipeline():
    from veles_tpu import prng
    prng.seed_all(5)
    loader = BlobsLoader(None, minibatch_size=48, name="blobs-gap")
    wf = nn.StandardWorkflow(
        name="ga-pp",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "name": "b%d" % i} for i in range(4)]
        + [{"type": "softmax", "output_sample_shape": 3}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=1), grad_accumulation=2)
    with pytest.raises(vt.Bug, match="grad_accumulation"):
        wf.initialize(device=vt.XLADevice(mesh_axes={"pipeline": 4}))


def test_grad_accumulation_composes_with_data_axis():
    from veles_tpu import prng
    prng.seed_all(77)
    loader = BlobsLoader(None, minibatch_size=48, name="blobs-gad")
    wf = nn.StandardWorkflow(
        name="ga-dp",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 3}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=6, fail_iterations=50),
        grad_accumulation=2)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 2}))
    wf.run()
    assert wf.decision.best_metric < 0.06, wf.decision.epoch_metrics


def test_label_smoothing_trains_and_changes_loss():
    """EvaluatorSoftmax(label_smoothing=eps): CE against the eps-mixed
    target. Still converges; the loss genuinely differs from the hard-
    target CE (floor is the smoothed entropy, not 0); oracle agrees."""
    from veles_tpu import prng
    prng.seed_all(31)
    loader = BlobsLoader(None, minibatch_size=50, name="blobs-ls")
    wf = nn.StandardWorkflow(
        name="ls",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 3}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=8, fail_iterations=50),
        evaluator_config=dict(label_smoothing=0.1))
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    assert wf.evaluator.label_smoothing == 0.1
    wf.run()
    assert wf.decision.best_metric < 0.05, wf.decision.epoch_metrics
    # jax loss vs numpy oracle on a small batch
    import jax.numpy as jnp
    logits = numpy.random.RandomState(0).randn(6, 3).astype("float32")
    labels = numpy.array([0, 1, 2, 0, 1, 2], numpy.int32)
    mask = numpy.ones(6, numpy.float32)
    l_jax = float(wf.evaluator.loss(jnp.asarray(logits),
                                    jnp.asarray(labels),
                                    jnp.asarray(mask)))
    l_np = wf.evaluator.numpy_loss(logits, labels, mask)
    numpy.testing.assert_allclose(l_jax, l_np, rtol=1e-5)
    # and it differs from the unsmoothed loss
    wf.evaluator.label_smoothing = 0.0
    l_hard = float(wf.evaluator.loss(jnp.asarray(logits),
                                     jnp.asarray(labels),
                                     jnp.asarray(mask)))
    assert abs(l_jax - l_hard) > 1e-4
