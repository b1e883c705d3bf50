"""Convolutional autoencoder workflow — BASELINE config #3 (ImagenetAE).

TPU-native rebuild of the Znicz ImagenetAE sample (reference: conv+pool
encoder, deconv+depool decoder, MSE reconstruction; exercised the GEMM
path, SURVEY.md §2.8/§6). Trains to reconstruct its input
(target_mode="input"), reports RMSE like the reference's 0.5478 anchor
for the MNIST AE variant.

Run: python models/imagenet_ae.py [--epochs N] [--size N]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy  # noqa: E402

import veles_tpu as vt  # noqa: E402
from veles_tpu import nn, datasets  # noqa: E402
from veles_tpu.loader import FullBatchLoader  # noqa: E402


class AELoader(FullBatchLoader):
    hide_from_registry = True

    def __init__(self, workflow, image_size=32, n_train=4000, n_valid=800,
                 **kwargs):
        super().__init__(workflow, **kwargs)
        self.image_size = image_size
        self.n_train, self.n_valid = n_train, n_valid

    def load_data(self):
        tx, ty, vx, vy = datasets.load_cifar10(
            n_train=self.n_train, n_test=self.n_valid)
        data = numpy.concatenate([vx, tx])
        self.create_originals(data, None)
        self.class_lengths = [0, len(vx), len(tx)]


def build_workflow(epochs=20, minibatch_size=50, lr=0.01):
    loader = AELoader(None, minibatch_size=minibatch_size, name="ae")
    layers = [
        # encoder
        {"type": "conv_tanh", "n_kernels": 16, "kx": 5, "ky": 5,
         "padding": (2, 2, 2, 2), "learning_rate": lr},
        {"type": "avg_pooling", "kx": 2, "ky": 2},
        {"type": "conv_tanh", "n_kernels": 8, "kx": 3, "ky": 3,
         "padding": (1, 1, 1, 1), "learning_rate": lr},
        # decoder
        {"type": "depooling", "kx": 2, "ky": 2},
        {"type": "deconv", "n_channels": 3, "kx": 5, "ky": 5,
         "padding": (2, 2, 2, 2), "learning_rate": lr},
    ]
    wf = nn.StandardWorkflow(
        name="imagenet-ae",
        layers=layers, loader_unit=loader, loss_function="mse",
        decision_config=dict(max_epochs=epochs, fail_iterations=50),
    )
    return wf


class SyntheticImageLoader(FullBatchLoader):
    """Deterministic synthetic RGB images at an arbitrary size — the
    compute-bound bench surface (provenance 'synthetic' is stamped into the
    bench JSON; throughput/MFU do not depend on pixel content)."""

    hide_from_registry = True

    def __init__(self, workflow, image_size=128, n_train=1024, n_valid=128,
                 **kwargs):
        super().__init__(workflow, **kwargs)
        self.image_size = image_size
        self.n_train, self.n_valid = n_train, n_valid

    def load_data(self):
        rng = numpy.random.RandomState(123456)
        s = self.image_size
        data = rng.uniform(
            -1.0, 1.0, (self.n_valid + self.n_train, s, s, 3)
        ).astype(numpy.float32)
        self.create_originals(data, None)
        self.class_lengths = [0, self.n_valid, self.n_train]


def build_bench_workflow(image_size=128, minibatch_size=64, n_train=1024,
                         n_valid=128, lr=1e-4, remat=False):
    """MXU-weighted AE: most FLOPs sit in 64→128 and 128→128 3×3 convs
    (contraction dims ≥64 tile cleanly onto the 128×128 systolic array);
    only the unavoidable RGB stem is narrow. This is the compute-bound
    counterpart of :func:`build_workflow` — same layer vocabulary, sized so
    arithmetic dominates the dispatch latency."""
    loader = SyntheticImageLoader(
        None, image_size=image_size, n_train=n_train, n_valid=n_valid,
        minibatch_size=minibatch_size, name="ae-bench")
    layers = [
        # encoder
        {"type": "conv_relu", "n_kernels": 64, "kx": 5, "ky": 5,
         "padding": (2, 2, 2, 2), "learning_rate": lr},
        {"type": "avg_pooling", "kx": 2, "ky": 2},
        {"type": "conv_relu", "n_kernels": 128, "kx": 3, "ky": 3,
         "padding": (1, 1, 1, 1), "learning_rate": lr},
        {"type": "avg_pooling", "kx": 2, "ky": 2},
        {"type": "conv_relu", "n_kernels": 128, "kx": 3, "ky": 3,
         "padding": (1, 1, 1, 1), "learning_rate": lr},
        # decoder
        {"type": "depooling", "kx": 2, "ky": 2},
        {"type": "deconv", "n_channels": 64, "kx": 3, "ky": 3,
         "padding": (1, 1, 1, 1), "learning_rate": lr},
        {"type": "depooling", "kx": 2, "ky": 2},
        {"type": "deconv", "n_channels": 3, "kx": 5, "ky": 5,
         "padding": (2, 2, 2, 2), "learning_rate": lr},
    ]
    wf = nn.StandardWorkflow(
        name="imagenet-ae-bench",
        layers=layers, loader_unit=loader, loss_function="mse",
        decision_config=dict(max_epochs=10 ** 9, fail_iterations=10 ** 9),
        remat=remat,
    )
    return wf


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=20)
    p.add_argument("--mb", type=int, default=50)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--backend", default="auto")
    args = p.parse_args(argv)

    wf = build_workflow(args.epochs, args.mb, args.lr)
    wf.initialize(device=vt.Device_for(args.backend))
    t0 = time.time()
    wf.run()
    dt = time.time() - t0
    res = wf.gather_results()
    print("best validation rmse: %.4f (epoch %d)" %
          (res["best_rmse"], res["best_epoch"]))
    print("throughput: %.0f samples/sec" %
          (wf.loader.samples_served / dt))
    return res


if __name__ == "__main__":
    main()
