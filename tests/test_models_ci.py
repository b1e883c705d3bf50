"""Model-zoo CI: the four BASELINE configs under convergence gates.

The reference CI ran Znicz model regression tests (SURVEY.md §4,
veles/tests/jenkins.xml); these tests are that net for the TPU build:
every model in models/ is imported, built through its public
build_workflow(), trained on a shrunken surrogate dataset, and held to a
convergence threshold — so a regression in any model (layer wiring, loss,
decision plumbing, loader contract) fails CI instead of shipping silently.

Thresholds are calibrated against the deterministic synthetic surrogates
(veles_tpu/datasets.py:_synthetic_images — class-template data that simple
models genuinely learn). They are intentionally loose: the gate is
"learns at all", not "matches the published anchor" (which needs the real
datasets, absent in-image; BASELINE.md documents the anchors).
"""
import os

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import datasets, prng
from veles_tpu.datasets import _synthetic_images

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


from conftest import import_model as _import_model  # noqa: E402


def _dev():
    return vt.XLADevice(mesh_axes={"data": 1})


def test_mnist_converges(monkeypatch):
    prng.seed_all(1234)
    """BASELINE config #1 (MNIST-784 FC). Real anchor: 1.48 % val error."""
    monkeypatch.setattr(
        datasets, "load_mnist",
        lambda flat=True: _synthetic_images((28, 28), 10, 3000, 600,
                                            flat, key="mnist"))
    mnist = _import_model("mnist")
    wf = mnist.build_workflow(epochs=4, minibatch_size=100)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["epochs"] == 4
    assert res["best_err"] < 0.12, res


def test_cifar_converges(monkeypatch):
    prng.seed_all(1234)
    """BASELINE config #4 (CIFAR conv net). Real anchor: 17.21 % val
    error. The surrogate shrinks to 16x16 so the conv stack stays CI-
    affordable on the CPU mesh; the gate is "clearly beats chance"
    (90 % error for 10 classes), which catches any wiring/loss/GD
    regression in the conv path."""
    monkeypatch.setattr(
        datasets, "load_cifar10",
        lambda n_train=50000, n_test=10000: _synthetic_images(
            (16, 16, 3), 10, 960, 120, flat=False, key="cifar10"))
    cifar = _import_model("cifar")
    wf = cifar.build_workflow(epochs=10, minibatch_size=60, lr=0.05)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["epochs"] == 10
    # chance is 0.9; a broken conv/gd path stays there (calibrated best
    # on this surrogate: ~0.62 at epoch 8)
    assert res["best_err"] < 0.7, res


def test_imagenet_ae_converges(monkeypatch):
    prng.seed_all(1234)
    """BASELINE config #3 (conv autoencoder). Real anchor: 0.5478 RMSE on
    the MNIST AE variant. Gate: reconstruction RMSE drops below the
    do-nothing bound (std of the surrogate pixels ~0.29) and improves
    across epochs."""
    monkeypatch.setattr(
        datasets, "load_cifar10",
        lambda n_train=50000, n_test=10000: _synthetic_images(
            (32, 32, 3), 10, 1000, 200, flat=False, key="cifar10"))
    ae = _import_model("imagenet_ae")
    wf = ae.build_workflow(epochs=3, minibatch_size=50, lr=0.02)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["epochs"] == 3
    assert res["best_rmse"] < 0.25, res


def test_genre_lstm_converges():
    prng.seed_all(1234)
    """BASELINE config #5 (LSTM genre recognition). The loader is already
    synthetic-by-design (frequency/phase signatures per genre)."""
    genre = _import_model("genre_recognition")
    wf = genre.build_workflow(epochs=3, minibatch_size=60, lr=0.05,
                              hidden=32)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["epochs"] == 3
    assert res["best_err"] < 0.35, res


def test_lines_converges():
    prng.seed_all(1234)
    """Lines demo (reference zoo member; generator-backed, so its
    accuracy is a REAL anchor, not a surrogate proxy). Exercises the
    per-layer adam solver in CI."""
    lines = _import_model("lines")
    wf = lines.build_workflow(epochs=5, minibatch_size=80,
                              n_train=960, n_valid=240)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["best_err"] < 0.1, res


def test_tiny_transformer_converges():
    prng.seed_all(1234)
    """Transformer zoo member (generated order-classification task —
    position-dependent, so pos_embedding + attention are load-bearing;
    a real anchor like lines)."""
    tt = _import_model("tiny_transformer")
    wf = tt.build_workflow(epochs=15, minibatch_size=64, n_blocks=2,
                           n_train=2048, n_valid=512)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    # chance is 0.5; calibrated best on this task: ~0.27 at epoch 14
    assert res["best_err"] < 0.35, res


def test_kanji_converges():
    prng.seed_all(1234)
    """Kanji zoo member (reference: "MSE NN with standard workflow",
    algorithms doc :29): the ONE model exercising loader-provided
    regression targets (target_mode='targets' / FullBatchLoaderMSE)
    through StandardWorkflow. Generator-backed — a real anchor.
    Do-nothing bound: predicting 0 gives RMSE ~0.5 on the stroke
    templates; calibrated best on 6 epochs: ~0.12."""
    kanji = _import_model("kanji")
    wf = kanji.build_workflow(epochs=6, minibatch_size=80,
                              n_train=960, n_valid=240)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["best_rmse"] < 0.3, res


def test_video_ae_converges():
    prng.seed_all(1234)
    """VideoAE zoo member (reference AE family, algorithms doc :70):
    the fully-connected bottleneck AE (imagenet_ae covers conv/deconv).
    Do-nothing bound: frame std ~0.22; calibrated best on 6 epochs:
    ~0.13."""
    vae = _import_model("video_ae")
    wf = vae.build_workflow(epochs=6, minibatch_size=64,
                            n_train=768, n_valid=192)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["best_rmse"] < 0.19, res


def test_kohonen_demo_organizes():
    prng.seed_all(1234)
    """DemoKohonen zoo member (algorithms doc :89): custom (non-GD)
    workflow loop around the batch-SOM trainer. The map must organize:
    final quantization error below the cluster noise radius (0.25)."""
    kd = _import_model("kohonen_demo")
    wf = kd.build_workflow(epochs=8, minibatch_size=100, n_train=600)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["epochs"] == 8
    assert res["final_qerr"] < 0.25, res
    # error actually fell as the map organized
    assert res["qerr_history"][-1] < res["qerr_history"][0]


def test_alexnet_converges():
    prng.seed_all(1234)
    """AlexNet zoo member (algorithms doc :49), authored via the
    mcdnnic_topology shorthand — this gate covers that authoring path
    end-to-end. Calibrated: 0 % by epoch 3 on the surrogate."""
    m = _import_model("alexnet")
    wf = m.build_workflow(epochs=5, minibatch_size=64,
                          n_train=960, n_valid=240)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["best_err"] < 0.2, res


def test_stl10_converges():
    prng.seed_all(1234)
    """STL-10 variant of the conv family (anchor: 35.10 % on real data,
    algorithms doc :51): same caffe-quick stack, STL geometry. CI
    shrinks to 32 px; the gate is "clearly beats chance"."""
    cifar = _import_model("cifar")
    wf = cifar.build_stl10_workflow(epochs=10, minibatch_size=60, lr=0.05,
                                    image_size=32, n_train=960,
                                    n_valid=120)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["best_err"] < 0.7, res


def test_bench_workflow_builds(monkeypatch):
    """The compute-bound ``build_bench_workflow`` must keep building
    and running a WHOLE epoch under its knobs
    (mixed_precision + bf16 dataset). One dispatch is not enough: the
    epoch's first dispatch is the VALID eval — an AMP regression in the
    conv/deconv TRAIN grad shipped invisibly behind a single-dispatch
    gate once (preferred_element_type f32 broke the conv transpose rule
    on bf16 operands)."""
    from veles_tpu.config import root
    root.common.engine.mixed_precision = True
    root.common.engine.dataset_dtype = "bfloat16"
    try:
        ae = _import_model("imagenet_ae")
        wf = ae.build_bench_workflow(image_size=16, minibatch_size=8,
                                     n_train=32, n_valid=8)
        wf.initialize(device=_dev())
        loader = wf.loader
        assert loader.total_samples == 40
        assert wf.train_step.mixed_precision
        # a full epoch: the valid-eval dispatch AND the train dispatch
        while True:
            loader.run()
            wf.train_step.run()
            if bool(loader.epoch_ended):
                break
        assert loader.samples_served == 40
        import jax
        jax.block_until_ready(wf.train_step.params)
    finally:
        root.common.engine.mixed_precision = False
        root.common.engine.dataset_dtype = None
    assert wf.train_step.params


def test_char_lm_converges():
    """Language-model zoo member (new capability: per-token CE via
    loss_function='softmax_seq'). The grammar's optimal per-token error
    is ~0.2-0.3 (stochastic branches); a broken LM path sits near
    1 - 1/16 = 0.94."""
    prng.seed_all(1234)
    lm = _import_model("char_lm")
    wf = lm.build_workflow(epochs=6, minibatch_size=64, n_blocks=1,
                           dim=32, n_train=768, n_valid=128)
    wf.initialize(device=_dev())
    wf.run()
    res = wf.gather_results()
    assert res["best_err"] < 0.45, res


def test_char_lm_generates_grammar():
    """Sampling: a briefly trained LM's GREEDY continuations follow the
    grammar's dominant transition (s -> s+1 mod 8 within 0..7)."""
    prng.seed_all(1234)
    lm = _import_model("char_lm")
    wf = lm.build_workflow(epochs=6, minibatch_size=64, n_blocks=1,
                           dim=32, n_train=768, n_valid=128)
    wf.initialize(device=_dev())
    wf.run()
    rng = numpy.random.RandomState(3)
    prompt = list(lm.make_corpus(rng, lm.SEQ_LEN))
    toks = lm.generate(wf, prompt, 64, temperature=0)
    seq = prompt[-1:] + toks
    follow = sum(1 for a, b in zip(seq, seq[1:])
                 if (a < 8 and b == (a + 1) % 8) or (a >= 8 and b == 0))
    # dominant transitions fire ~80-90% in the grammar; chance ~1/16
    assert follow / (len(seq) - 1) > 0.5, (follow, seq)


def test_genetic_example_solves():
    """GeneticExample zoo member (reference samples/GeneticExample —
    the GA engine used directly on plain objectives): the integer-gene
    knapsack must reach its known optimum; continuous Rosenbrock must
    get into the valley (random init scatters f across ~1e2-1e3)."""
    ge = _import_model("genetic_example")
    take, value = ge.solve_knapsack()
    assert value == 15.0, (take, value)
    _genes, f = ge.solve_rosenbrock(generations=60)
    assert f < 0.5, f


def test_lm_bench_workflow_builds():
    """The LM's ``build_bench_workflow`` (chip_smoke.py's model) must
    keep building and running one block dispatch."""
    lm = _import_model("char_lm")
    wf = lm.build_bench_workflow(seq_len=32, dim=32, n_blocks=2,
                                 ffn_hidden=64, n_heads=4, vocab=32,
                                 minibatch_size=8, n_train=32, n_valid=8,
                                 epochs_per_dispatch=2)
    wf.initialize(device=_dev())
    wf.loader.run()
    wf.train_step.run()
    assert wf.loader.block_length == 2
    assert wf.train_step.params
