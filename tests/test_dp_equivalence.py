"""Data-parallel equivalence: the actual correctness claim of psum-DP.

The reference's master–slave plane (veles/server.py, veles/client.py)
averaged slave updates into one canonical model; the TPU build's claim is
that sharding the minibatch over the mesh 'data' axis and letting XLA
insert the gradient psum computes the SAME training run. This test proves
it: same seed, same data, same topology — a 1-device run and an 8-device
{"data": 8} run must produce matching per-epoch loss/error trajectories
(tolerance only for float reduction order).
"""
import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn, prng
from veles_tpu.loader import FullBatchLoader, TRAIN, VALID


class BlobsLoader(FullBatchLoader):
    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(7)
        n_per, d, k = 160, 10, 3
        centers = rng.randn(k, d) * 3
        data, labels = [], []
        for c in range(k):
            data.append(centers[c] + rng.randn(n_per, d))
            labels.append(numpy.full(n_per, c))
        data = numpy.concatenate(data).astype(numpy.float32)
        labels = numpy.concatenate(labels).astype(numpy.int32)
        perm = rng.permutation(len(data))
        self.create_originals(data[perm], labels[perm])
        self.class_lengths = [0, 120, 360]


def _run(n_devices=None, epochs=6, mesh_axes=None, n_classes=3,
         check_sharding=None, layers=None, watch_param="weights"):
    """One seeded blobs training run under the given mesh; the shared
    body of every equivalence test in this module. check_sharding, if
    given, receives the first layer's watched-param sharding BEFORE the
    run — tests must assert the axis actually engaged, or they pass
    vacuously when a mesh regression silently falls back to
    replication. ``layers`` overrides the 2-layer FC stack (EP uses a
    MoE layer); ``watch_param`` names the first layer's param to
    check/extract."""
    if mesh_axes is None:
        mesh_axes = {"data": n_devices}
    prng.seed_all(1234)
    loader = BlobsLoader(None, minibatch_size=40, name="blobs-eq")
    wf = nn.StandardWorkflow(
        name="eq-%s" % "x".join("%s%d" % kv for kv in
                                sorted(mesh_axes.items())),
        layers=layers or [
            {"type": "all2all_tanh", "output_sample_shape": 16},
            {"type": "softmax", "output_sample_shape": n_classes},
        ],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=epochs, fail_iterations=100),
    )
    wf.initialize(device=vt.XLADevice(mesh_axes=mesh_axes))
    if check_sharding is not None:
        check_sharding(
            wf.train_step.params[wf.forwards[0].name][watch_param]
            .sharding)
    wf.run()
    d = wf.decision
    import jax
    return {
        "train_err": numpy.asarray(d.epoch_metrics[TRAIN]),
        "valid_err": numpy.asarray(d.epoch_metrics[VALID]),
        "weights": numpy.asarray(
            jax.device_get(wf.train_step.params[wf.forwards[0].name]
                           [watch_param])),
    }


def test_dp_8dev_matches_1dev_trajectory():
    r1 = _run(1)
    r8 = _run(8)
    assert r1["train_err"].shape == r8["train_err"].shape == (6,)
    # reduction order differs (per-shard partial sums + psum); everything
    # else — shuffle order, init, schedule — is identical, so per-epoch
    # error fractions may differ by at most a couple of tie-break flips
    # (360 train / 120 valid samples → 1 flip = 0.0028 / 0.0083)
    numpy.testing.assert_allclose(r8["train_err"], r1["train_err"],
                                  atol=0.01)
    numpy.testing.assert_allclose(r8["valid_err"], r1["valid_err"],
                                  atol=0.02)
    # the strong claim: the trained parameters themselves match
    numpy.testing.assert_allclose(r8["weights"], r1["weights"],
                                  rtol=2e-3, atol=2e-4)


def test_sharded_dataset_matches_replicated():
    """shard_dataset=True: the device-resident dataset shards over the
    'data' axis (HBM/chip scales 1/n); GSPMD inserts the gather
    collectives. Must train identically to the replicated layout."""
    import jax

    def run(shard):
        prng.seed_all(1234)
        loader = BlobsLoader(None, minibatch_size=40,
                             shard_dataset=shard, name="blobs-sh")
        wf = nn.StandardWorkflow(
            name="ds-%s" % shard,
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 3},
            ],
            loader_unit=loader, loss_function="softmax",
            decision_config=dict(max_epochs=4, fail_iterations=100),
        )
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 8}))
        ds = wf.train_step._inputs()[0]
        if shard:
            assert not ds.sharding.is_fully_replicated
            assert ds.sharding.spec[0] == "data"
        else:
            assert ds.sharding.is_fully_replicated
        wf.run()
        return (numpy.asarray(wf.decision.epoch_metrics[TRAIN]),
                numpy.asarray(jax.device_get(
                    wf.train_step.params[wf.forwards[0].name]
                    ["weights"])))

    e_repl, w_repl = run(False)
    e_sh, w_sh = run(True)
    numpy.testing.assert_allclose(e_sh, e_repl, atol=0.01)
    numpy.testing.assert_allclose(w_sh, w_repl, rtol=2e-3, atol=2e-4)


class SeqLoader(FullBatchLoader):
    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(11)
        n, t, d = 256, 16, 8
        x = rng.randn(n, t, d).astype(numpy.float32)
        # order-sensitive rule so attention is load-bearing: does the
        # first half of the sequence carry more energy than the second
        y = (numpy.square(x[:, :t // 2]).sum(axis=(1, 2)) >
             numpy.square(x[:, t // 2:]).sum(axis=(1, 2)))
        self.create_originals(x, y.astype(numpy.int32))
        self.class_lengths = [0, 64, 192]


_SP_BASELINE = {}


def _run_sp(mesh_axes, epochs=4):
    """Attention model under the given mesh; sequence axis engages the
    ring-attention path inside MultiHeadAttention. The 1-device
    baseline is memoized — both equivalence tests compare against the
    same run."""
    key = (tuple(sorted(mesh_axes.items())), epochs)
    if mesh_axes == {"data": 1} and key in _SP_BASELINE:
        return _SP_BASELINE[key]
    prng.seed_all(777)
    loader = SeqLoader(None, minibatch_size=32, name="seq-eq")
    wf = nn.StandardWorkflow(
        name="sp-eq",
        layers=[
            {"type": "multi_head_attention", "n_heads": 2},
            {"type": "mean_pool"},
            {"type": "softmax", "output_sample_shape": 2},
        ],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=epochs, fail_iterations=100),
    )
    wf.initialize(device=vt.XLADevice(mesh_axes=mesh_axes))
    wf.run()
    import jax
    res = {
        "train_err": numpy.asarray(wf.decision.epoch_metrics[TRAIN]),
        "valid_err": numpy.asarray(wf.decision.epoch_metrics[VALID]),
        "wq": numpy.asarray(jax.device_get(
            wf.train_step.params[wf.forwards[0].name]["wq"])),
        "mesh_engaged": wf.forwards[0].mesh is not None,
    }
    if mesh_axes == {"data": 1}:
        _SP_BASELINE[key] = res
    return res


def test_sp_4dev_matches_1dev_trajectory():
    """Sequence-parallel equivalence — the SP analogue of the DP proof:
    ring attention over a {'sequence': 4} mesh is EXACT (K/V rotate via
    ppermute, softmax accumulated online), so the training run must
    match the single-device run up to reduction order."""
    r1 = _run_sp({"data": 1})
    r4 = _run_sp({"sequence": 4})
    assert not r1["mesh_engaged"] and r4["mesh_engaged"]
    numpy.testing.assert_allclose(r4["train_err"], r1["train_err"],
                                  atol=0.02)
    numpy.testing.assert_allclose(r4["valid_err"], r1["valid_err"],
                                  atol=0.03)
    numpy.testing.assert_allclose(r4["wq"], r1["wq"], rtol=5e-3,
                                  atol=5e-4)


def test_sp_composes_with_dp():
    """dp x sp: batch over 'data' AND sequence over 'sequence' in one
    mesh — the composed run still matches the single-device
    trajectory."""
    r1 = _run_sp({"data": 1})
    r24 = _run_sp({"data": 2, "sequence": 4})
    assert r24["mesh_engaged"]
    numpy.testing.assert_allclose(r24["train_err"], r1["train_err"],
                                  atol=0.02)
    numpy.testing.assert_allclose(r24["wq"], r1["wq"], rtol=5e-3,
                                  atol=5e-4)


def test_fsdp_matches_replicated():
    """ZeRO-3-style parameter sharding ({'fsdp': 8}: params sharded over
    their largest divisible axis, all-gathered at use by GSPMD) must
    train identically to the replicated layout — it changes placement,
    not math. Composed {'data': 2, 'fsdp': 4} likewise."""
    base = _run(1, epochs=4)

    def sharded(sh):
        assert not sh.is_fully_replicated, sh

    for axes in ({"fsdp": 8}, {"data": 2, "fsdp": 4}):
        r = _run(mesh_axes=axes, epochs=4, check_sharding=sharded)
        numpy.testing.assert_allclose(r["train_err"],
                                      base["train_err"], atol=0.01)
        numpy.testing.assert_allclose(r["weights"], base["weights"],
                                      rtol=2e-3, atol=2e-4)


def test_tensor_parallel_matches_replicated():
    """Megatron-style column sharding ({'tensor': 4}: output-feature
    axis split, activation collectives inserted by GSPMD) — same
    trajectory and weights as the replicated run; composed
    {'data': 2, 'tensor': 4} likewise."""
    base = _run(1, epochs=4, n_classes=4)

    def column_split(sh):
        assert sh.spec[-1] == "tensor", sh

    for axes in ({"tensor": 4}, {"data": 2, "tensor": 4}):
        r = _run(mesh_axes=axes, epochs=4, n_classes=4,
                 check_sharding=column_split)
        numpy.testing.assert_allclose(r["train_err"],
                                      base["train_err"], atol=0.01)
        numpy.testing.assert_allclose(r["weights"], base["weights"],
                                      rtol=2e-3, atol=2e-4)


def test_expert_parallel_matches_replicated():
    """{'expert': 4}: MoE expert-leading params shard over the axis and
    GSPMD partitions the expert einsums — placement, not math, so the
    run must match the replicated one exactly (completing the
    per-axis equivalence matrix: dp / tp / fsdp / sp / ep)."""
    moe = [{"type": "moe_ffn", "n_experts": 4, "hidden": 16},
           {"type": "softmax", "output_sample_shape": 3}]
    base = _run(1, epochs=4, layers=moe, watch_param="w1")

    def expert_sharded(sh):
        assert sh.spec[0] == "expert", sh

    for axes in ({"expert": 4}, {"data": 2, "expert": 4}):
        r = _run(mesh_axes=axes, epochs=4, layers=moe,
                 watch_param="w1", check_sharding=expert_sharded)
        numpy.testing.assert_allclose(r["train_err"],
                                      base["train_err"], atol=0.01)
        numpy.testing.assert_allclose(r["weights"], base["weights"],
                                      rtol=2e-3, atol=2e-4)


def test_sp_windowed_matches_1dev():
    """Sliding-window attention composes with the 'sequence' axis: a
    windowed TransformerBlock under {'sequence': 4} (ring path,
    shortened rotation scan) matches the 1-device windowed run."""
    def run(mesh_axes):
        prng.seed_all(555)
        loader = SeqLoader(None, minibatch_size=32, name="seq-win")
        wf = nn.StandardWorkflow(
            name="sp-win",
            layers=[
                {"type": "transformer_block", "n_heads": 2,
                 "ffn_hidden": 16, "causal": True, "window": 5},
                {"type": "mean_pool"},
                {"type": "softmax", "output_sample_shape": 2},
            ],
            loader_unit=loader, loss_function="softmax",
            decision_config=dict(max_epochs=3, fail_iterations=100),
        )
        wf.initialize(device=vt.XLADevice(mesh_axes=mesh_axes))
        wf.run()
        import jax
        return {
            "train_err": numpy.asarray(wf.decision.epoch_metrics[TRAIN]),
            "wq": numpy.asarray(jax.device_get(
                wf.train_step.params[wf.forwards[0].name]["wq"])),
            "mesh_engaged": wf.forwards[0].mesh is not None,
        }

    r1 = run({"data": 1})
    r4 = run({"sequence": 4})
    assert not r1["mesh_engaged"] and r4["mesh_engaged"]
    numpy.testing.assert_allclose(r4["train_err"], r1["train_err"],
                                  atol=0.02)
    numpy.testing.assert_allclose(r4["wq"], r1["wq"], rtol=5e-3,
                                  atol=5e-4)
