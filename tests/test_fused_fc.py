"""Whole-epoch fused FC SGD kernel (ops/fused_fc.py): kernel↔oracle
equivalence, TrainStep fast-path trajectory parity vs the general scan
path, and strict eligibility gating."""
import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn, prng
from veles_tpu.config import root
from veles_tpu.loader import FullBatchLoader, TRAIN, VALID


def _rand_net(rng, dims):
    import jax.numpy as jnp
    ws = [jnp.asarray(rng.randn(a, b) * 0.1, jnp.float32)
          for a, b in zip(dims, dims[1:])]
    bs = [jnp.asarray(rng.randn(b) * 0.01, jnp.float32)
          for b in dims[1:]]
    zw = [jnp.zeros_like(w) for w in ws]
    zb = [jnp.zeros_like(b) for b in bs]
    return ws, bs, zw, zb


def test_kernel_matches_oracle():
    """Kernel == jnp oracle across depth (2- and 3-layer chains),
    LeCun tanh scaling, momentum, coupled weight decay, and a bias-lr
    ratio — including the returned delta-recurrence state."""
    import jax.numpy as jnp
    from veles_tpu.ops.fused_fc import (fused_fc_oracle,
                                        fused_fc_sgd_epoch)
    rng = numpy.random.RandomState(0)
    n, mb, nout = 60, 10, 3
    ds = jnp.asarray(rng.rand(n, 20), jnp.float32)
    lb = jnp.asarray(rng.randint(0, nout, n), jnp.int32)
    plan = jnp.asarray(rng.permutation(n).reshape(-1, mb), jnp.int32)
    cases = (
        ((20, 12, 3), dict(act_a=1.0, act_b=1.0)),
        ((20, 12, 3), dict(act_a=1.7159, act_b=0.6666)),
        ((20, 12, 3), dict(momentum=0.9, wd=1e-3, wd_bias=1e-4,
                           lr_bias_ratio=0.5)),
        ((20, 16, 8, 3), dict(act_a=1.7159, act_b=0.6666,
                              momentum=0.5)),
    )
    for dims, kw in cases:
        ws, bs, zw, zb = _rand_net(rng, dims)
        out_k = fused_fc_sgd_epoch(ws, bs, zw, zb, ds, lb, plan, 0.05,
                                   interpret=True, **kw)
        out_o = fused_fc_oracle(ws, bs, zw, zb, ds, lb, plan, 0.05,
                                **kw)
        for name, kk, oo in zip(("w", "b", "vw", "vb"), out_k[:4],
                                out_o[:4]):
            for li, (k1, o1) in enumerate(zip(kk, oo)):
                numpy.testing.assert_allclose(
                    numpy.asarray(k1), numpy.asarray(o1), rtol=2e-5,
                    atol=2e-6, err_msg="%s[%d] %s %s" % (name, li,
                                                         dims, kw))
        for name, kk, oo in zip(("loss", "err"), out_k[4:], out_o[4:]):
            numpy.testing.assert_allclose(
                numpy.asarray(kk), numpy.asarray(oo), rtol=2e-5,
                atol=2e-6, err_msg=name)
        # a SECOND epoch continues from the returned state (the delta
        # recurrence survives the kernel boundary)
        k2 = fused_fc_sgd_epoch(out_k[0], out_k[1], out_k[2], out_k[3],
                                ds, lb, plan, 0.05, interpret=True,
                                **kw)
        o2 = fused_fc_oracle(out_o[0], out_o[1], out_o[2], out_o[3],
                             ds, lb, plan, 0.05, **kw)
        numpy.testing.assert_allclose(
            numpy.asarray(k2[0][0]), numpy.asarray(o2[0][0]),
            rtol=5e-5, atol=5e-6)


class Blobs(FullBatchLoader):
    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(9)
        n_per, d, k = 50, 16, 3
        centers = rng.randn(k, d) * 2.5
        x = numpy.concatenate(
            [centers[c] + rng.randn(n_per, d) for c in range(k)])
        y = numpy.concatenate([numpy.full(n_per, c) for c in range(k)])
        perm = rng.permutation(len(x))
        self.create_originals(x[perm].astype(numpy.float32),
                              y[perm].astype(numpy.int32))
        self.class_lengths = [0, 30, 120]


def _run(fused, epochs=4, solver="sgd", mb=20, **layer_extra):
    prev = root.common.engine.get("fused_fc_scan", False)
    root.common.engine.fused_fc_scan = "force" if fused else False
    try:
        prng.seed_all(777)
        wf = nn.StandardWorkflow(
            name="ffc-%s" % fused,
            layers=[{"type": "all2all_tanh", "output_sample_shape": 8,
                     "learning_rate": 0.05, "solver": solver,
                     **layer_extra},
                    {"type": "softmax", "output_sample_shape": 3,
                     "learning_rate": 0.05, "solver": solver,
                     **layer_extra}],
            loader_unit=Blobs(None, minibatch_size=mb, name="bl"),
            loss_function="softmax",
            decision_config=dict(max_epochs=epochs,
                                 fail_iterations=100),
            epochs_per_dispatch=2)
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        wf.run()
        return wf
    finally:
        root.common.engine.fused_fc_scan = prev


def test_workflow_trajectory_parity():
    """engine.fused_fc_scan must reproduce the general epoch-block
    path: identical per-epoch valid metrics and matching final
    weights (same seed → same shuffle plans → same SGD math)."""
    import jax
    wf_g = _run(False)
    wf_f = _run(True)
    assert wf_f.train_step._fused_fc is not None
    assert wf_f.train_step._fused_fc_active
    assert wf_g.train_step._fused_fc is None
    ev_g = numpy.asarray(wf_g.decision.epoch_metrics[VALID])
    ev_f = numpy.asarray(wf_f.decision.epoch_metrics[VALID])
    numpy.testing.assert_allclose(ev_f, ev_g, atol=1e-6)
    tr_g = numpy.asarray(wf_g.decision.epoch_metrics[TRAIN])
    tr_f = numpy.asarray(wf_f.decision.epoch_metrics[TRAIN])
    numpy.testing.assert_allclose(tr_f, tr_g, atol=1e-5)
    names = sorted(wf_g.train_step.params)
    assert names == sorted(wf_f.train_step.params) and len(names) == 2
    for name in names:
        wg = jax.device_get(wf_g.train_step.params[name]["weights"])
        wf_ = jax.device_get(wf_f.train_step.params[name]["weights"])
        numpy.testing.assert_allclose(wf_, wg, rtol=2e-4, atol=2e-5)


def test_workflow_trajectory_parity_momentum_decay():
    """The Znicz SGD recurrence (momentum + coupled L2) through the
    kernel: VALID metrics identical, weights AND the opt_state delta
    recurrence match the general path across dispatch boundaries."""
    import jax
    kw = dict(momentum=0.9, weights_decay=1e-3)
    wf_g = _run(False, **kw)
    wf_f = _run(True, **kw)
    assert wf_f.train_step._fused_fc_active
    ev_g = numpy.asarray(wf_g.decision.epoch_metrics[VALID])
    ev_f = numpy.asarray(wf_f.decision.epoch_metrics[VALID])
    numpy.testing.assert_allclose(ev_f, ev_g, atol=1e-6)
    for name in sorted(wf_g.train_step.params):
        for k in ("weights", "bias"):
            pg = jax.device_get(wf_g.train_step.params[name][k])
            pf = jax.device_get(wf_f.train_step.params[name][k])
            numpy.testing.assert_allclose(pf, pg, rtol=2e-4,
                                          atol=2e-5, err_msg=name)
            sg = jax.device_get(wf_g.train_step.opt_state[name][k])
            sf = jax.device_get(wf_f.train_step.opt_state[name][k])
            numpy.testing.assert_allclose(sf, sg, rtol=2e-3,
                                          atol=2e-6, err_msg=name)


def test_workflow_three_layer_chain():
    """Depth generality: tanh→tanh→softmax engages and learns."""
    prev = root.common.engine.get("fused_fc_scan", False)
    root.common.engine.fused_fc_scan = "force"
    try:
        prng.seed_all(5)
        wf = nn.StandardWorkflow(
            name="ffc3",
            layers=[{"type": "all2all_tanh", "output_sample_shape": 12,
                     "learning_rate": 0.05},
                    {"type": "all2all_tanh", "output_sample_shape": 8,
                     "learning_rate": 0.05},
                    {"type": "softmax", "output_sample_shape": 3,
                     "learning_rate": 0.05}],
            loader_unit=Blobs(None, minibatch_size=20, name="bl3"),
            loss_function="softmax",
            decision_config=dict(max_epochs=8, fail_iterations=100),
            epochs_per_dispatch=2)
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        wf.run()
        assert wf.train_step._fused_fc is not None
        assert wf.train_step._fused_fc_active
        assert len(wf.train_step._fused_fc["names"]) == 3
        assert wf.decision.best_metric < 0.15, \
            wf.decision.epoch_metrics
    finally:
        root.common.engine.fused_fc_scan = prev


def test_eligibility_rejects_adam():
    wf = _run(True, epochs=2, solver="adam")
    assert wf.train_step._fused_fc is None          # fell back loudly
    assert wf.decision.best_metric is not None


def test_eligibility_rejects_partial_batches():
    """mb that does not divide the train length leaves padded plan
    rows — the kernel path must yield to the masked general path."""
    wf = _run(True, epochs=2, mb=25)    # 120 % 25 != 0
    assert wf.train_step._fused_fc is not None
    assert not wf.train_step._fused_fc_active
    assert wf.decision.best_metric is not None


def test_eligibility_rejects_freeze_base():
    """Frozen layers must not be updated by the unconditional kernel."""
    prev = root.common.engine.get("fused_fc_scan", False)
    root.common.engine.fused_fc_scan = "force"
    try:
        prng.seed_all(3)
        wf = nn.StandardWorkflow(
            name="ffc-frozen",
            layers=[{"type": "all2all_tanh", "output_sample_shape": 8,
                     "learning_rate": 0.05, "freeze_base": True},
                    {"type": "softmax", "output_sample_shape": 3,
                     "learning_rate": 0.05}],
            loader_unit=Blobs(None, minibatch_size=20, name="bl2"),
            loss_function="softmax",
            decision_config=dict(max_epochs=1, fail_iterations=100),
            epochs_per_dispatch=2)
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.train_step._fused_fc is None
    finally:
        root.common.engine.fused_fc_scan = prev


def test_eligibility_rejects_vmem_oversized_chain():
    """A chain whose VMEM-resident state would blow the kernel budget
    must fall back to the general path instead of dying in Mosaic."""
    prev = root.common.engine.get("fused_fc_scan", False)
    root.common.engine.fused_fc_scan = "force"
    try:
        prng.seed_all(2)
        wf = nn.StandardWorkflow(
            name="ffc-big",
            layers=[{"type": "all2all_tanh",
                     "output_sample_shape": 2048,
                     "learning_rate": 0.05},
                    {"type": "all2all_tanh",
                     "output_sample_shape": 2048,
                     "learning_rate": 0.05},
                    {"type": "softmax", "output_sample_shape": 3,
                     "learning_rate": 0.05}],
            loader_unit=Blobs(None, minibatch_size=20, name="blb"),
            loss_function="softmax",
            decision_config=dict(max_epochs=1, fail_iterations=100),
            epochs_per_dispatch=2)
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.train_step._fused_fc is None
    finally:
        root.common.engine.fused_fc_scan = prev


def test_eligibility_rejects_per_layer_act_scales():
    """A per-instance (A, B) override on one tanh layer must fall back:
    the kernel bakes ONE scaling for the whole chain (ADVICE r4)."""
    from veles_tpu.nn.all2all import All2AllTanh
    prev = root.common.engine.get("fused_fc_scan", False)
    root.common.engine.fused_fc_scan = "force"
    try:
        prng.seed_all(7)
        wf = nn.StandardWorkflow(
            name="ffc-actscale",
            layers=[{"type": "all2all_tanh", "output_sample_shape": 12,
                     "learning_rate": 0.05},
                    {"type": "all2all_tanh", "output_sample_shape": 8,
                     "learning_rate": 0.05},
                    {"type": "softmax", "output_sample_shape": 3,
                     "learning_rate": 0.05}],
            loader_unit=Blobs(None, minibatch_size=20, name="blact"),
            loss_function="softmax",
            decision_config=dict(max_epochs=1, fail_iterations=100),
            epochs_per_dispatch=2)
        tanhs = [f for f in wf.forwards if isinstance(f, All2AllTanh)]
        tanhs[1].A = 1.0            # instance override shadows class A
        wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert wf.train_step._fused_fc is None
    finally:
        root.common.engine.fused_fc_scan = prev
