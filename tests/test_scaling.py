"""Data-parallel scaling (BASELINE config #4 structure: conv net over a
'data' mesh — real 8-chip hardware is unavailable, so the virtual
8-device mesh validates the sharded program; the driver's
dryrun_multichip covers the composed dp×tp×sp case)."""

import jax
import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn
from veles_tpu.loader import FullBatchLoader


class TinyImages(FullBatchLoader):
    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(0)
        x = rng.rand(512, 8, 8, 3).astype(numpy.float32)
        y = (x[:, :, :, 0].mean(axis=(1, 2)) >
             x[:, :, :, 1].mean(axis=(1, 2))).astype(numpy.int32)
        self.create_originals(x, y)
        self.class_lengths = [0, 128, 384]


def run_conv(dp, epochs=8, seed=7):
    vt.prng.seed_all(seed)
    wf = nn.StandardWorkflow(
        name="conv-dp%d" % dp,
        layers=[{"type": "conv_tanh", "n_kernels": 8, "kx": 3, "ky": 3,
                 "learning_rate": 0.05},
                {"type": "max_pooling", "kx": 2, "ky": 2},
                {"type": "all2all_tanh", "output_sample_shape": 32,
                 "learning_rate": 0.05},
                {"type": "softmax", "output_sample_shape": 2,
                 "learning_rate": 0.05}],
        loader_unit=TinyImages(None, minibatch_size=64),
        loss_function="softmax",
        decision_config=dict(max_epochs=epochs))
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": dp}))
    wf.run()
    return wf


def test_conv_dp8_trains_and_shards():
    wf = run_conv(8)
    res = wf.gather_results()
    assert res["epochs"] >= 8
    assert res["best_err"] < 0.45, res  # learns beyond chance
    # the minibatch plan is genuinely sharded over the 8 devices
    idx = wf.loader.minibatch_indices.devmem
    assert len(idx.sharding.device_set) == 8
    assert not idx.sharding.is_fully_replicated
    # params replicated across the data axis (pure DP)
    w = wf.train_step.params["conv_tanh0"]["weights"]
    assert w.sharding.is_fully_replicated


def test_dp1_vs_dp8_same_learning_trajectory():
    """Same seed, same data: an 8-way data-parallel run must follow the
    single-device trajectory (psum-of-shards == full-batch gradient up to
    reduction order)."""
    err1 = run_conv(1).gather_results()["err_history"]["train"]
    err8 = run_conv(8).gather_results()["err_history"]["train"]
    assert len(err1) == len(err8)
    numpy.testing.assert_allclose(err1, err8, atol=0.02)
