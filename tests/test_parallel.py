"""Parallelism: ring attention vs exact oracle, sharding rules, tensor/
fsdp-parallel training, watchdog/fault hooks."""
import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn, parallel
from veles_tpu.memory import Array
from veles_tpu.parallel.ring_attention import (ring_attention,
                                               attention_reference)


def seq_mesh(n=8):
    return vt.make_mesh(__import__("jax").devices(), {"sequence": n})


def test_ring_attention_matches_reference():
    import jax.numpy as jnp
    rng = numpy.random.RandomState(0)
    b, t, h, d = 2, 32, 4, 8
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    mesh = seq_mesh(8)
    out = ring_attention(q, k, v, mesh)
    ref = attention_reference(q, k, v)
    numpy.testing.assert_allclose(numpy.asarray(out), numpy.asarray(ref),
                                  rtol=2e-4, atol=2e-5)


def test_ring_attention_causal():
    import jax.numpy as jnp
    rng = numpy.random.RandomState(1)
    b, t, h, d = 1, 16, 2, 4
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    mesh = seq_mesh(4)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    numpy.testing.assert_allclose(numpy.asarray(out), numpy.asarray(ref),
                                  rtol=2e-4, atol=2e-5)


def test_ring_attention_jittable_and_differentiable():
    import jax
    import jax.numpy as jnp
    mesh = seq_mesh(4)
    rng = numpy.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 8, 2, 4).astype(numpy.float32))

    @jax.jit
    def loss(q):
        o = ring_attention(q, q, q, mesh, causal=True)
        return (o ** 2).sum()
    g = jax.grad(loss)(q)
    assert g.shape == q.shape
    assert numpy.isfinite(numpy.asarray(g)).all()


def test_mha_oracle():
    wf = vt.Workflow(name="t")
    u = nn.MultiHeadAttention(wf, n_heads=2)
    x = numpy.random.RandomState(3).randn(2, 6, 8).astype(numpy.float32)
    u.input = Array(x, name="x")
    u.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    u.xla_run()
    y_dev = numpy.asarray(u.output.map_read())
    y_np = u.numpy_apply(u.params_np(), x)
    numpy.testing.assert_allclose(y_dev, y_np, rtol=2e-4, atol=2e-5)


def test_mha_causal_oracle():
    wf = vt.Workflow(name="t")
    u = nn.MultiHeadAttention(wf, n_heads=2, causal=True)
    x = numpy.random.RandomState(4).randn(1, 5, 4).astype(numpy.float32)
    u.input = Array(x, name="x")
    u.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    u.xla_run()
    numpy.testing.assert_allclose(
        numpy.asarray(u.output.map_read()),
        u.numpy_apply(u.params_np(), x), rtol=2e-4, atol=2e-5)


def test_sharding_rules():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    mesh = vt.make_mesh(__import__("jax").devices(),
                        {"fsdp": 2, "tensor": 2, "data": 2})
    params = {"fc": {"weights": jnp.zeros((64, 32)),
                     "bias": jnp.zeros((32,))}}
    sh = parallel.param_shardings(params, mesh)
    assert sh["fc"]["weights"].spec == P("fsdp", "tensor")
    assert sh["fc"]["bias"].spec == P(None)   # biases stay replicated


def test_tensor_parallel_training_converges():
    """2-way data x 4-way tensor mesh: fused step still converges."""
    from test_train_e2e import BlobsLoader
    loader = BlobsLoader(None, minibatch_size=48, name="blobs")
    wf = nn.StandardWorkflow(
        name="tp-train",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 4}],
        loader_unit=loader, loss_function="softmax",
        decision_config=dict(max_epochs=8, fail_iterations=50))
    dev = vt.XLADevice(mesh_axes={"data": 2, "tensor": 4})
    wf.initialize(device=dev)
    w = wf.train_step.params["all2all_tanh0"]["weights"]
    assert not w.sharding.is_fully_replicated     # actually tensor-sharded
    wf.run()
    assert wf.decision.best_metric < 0.1


def test_step_watchdog_records():
    hist = []
    for _ in range(10):
        with parallel.distributed.step_watchdog("s", history=hist):
            pass
    assert len(hist) == 10


def test_fault_injection_zero_probability_noop():
    parallel.distributed.fault_injection(0.0)   # must not exit


def test_restore_latest_no_snapshots(tmp_path):
    wf = vt.Workflow(name="w")
    assert parallel.distributed.restore_latest(wf, str(tmp_path)) is False


def test_ulysses_attention_matches_reference():
    import jax.numpy as jnp
    from veles_tpu.parallel.ulysses import ulysses_attention
    rng = numpy.random.RandomState(2)
    b, t, h, d = 2, 32, 8, 4
    q, k, v = [jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
               for _ in range(3)]
    mesh = seq_mesh(4)
    for causal in (False, True):
        out = ulysses_attention(q, k, v, mesh, causal=causal)
        ref = attention_reference(q, k, v, causal=causal)
        numpy.testing.assert_allclose(numpy.asarray(out),
                                      numpy.asarray(ref),
                                      rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    import jax.numpy as jnp
    from veles_tpu.parallel.ulysses import ulysses_attention
    q = jnp.zeros((1, 16, 3, 4))
    with pytest.raises(ValueError):
        ulysses_attention(q, q, q, seq_mesh(4))


def test_mha_routes_by_sequence_parallel_config():
    """With sequence_parallel='ulysses' and divisible heads, the unit
    output still matches the numpy oracle on a dp×sp mesh."""
    import veles_tpu as vt
    from veles_tpu import nn
    from veles_tpu.memory import Array
    prev_dtype = vt.root.common.engine.compute_dtype
    prev_scheme = vt.root.common.engine.sequence_parallel
    vt.root.common.engine.compute_dtype = "float32"
    vt.root.common.engine.sequence_parallel = "ulysses"
    try:
        wf = vt.Workflow(name="t")
        u = nn.MultiHeadAttention(wf, n_heads=4, causal=True)
        x = numpy.random.RandomState(0).randn(2, 16, 8).astype(
            numpy.float32)
        u.input = Array(x)
        u.initialize(device=vt.XLADevice(
            mesh_axes={"data": 2, "sequence": 4}))
        assert u.mesh is not None
        u.xla_run()
        y = numpy.asarray(u.output.map_read())
        y_np = u.numpy_apply(u.params_np(), x)
        numpy.testing.assert_allclose(y, y_np, rtol=1e-3, atol=1e-4)
    finally:
        vt.root.common.engine.compute_dtype = prev_dtype
        vt.root.common.engine.sequence_parallel = prev_scheme


def test_ulysses_flash_inner_matches_reference():
    """After the all-to-all each device holds the full sequence, so the
    pallas flash kernel can take the inner attention (forced into
    interpret mode here); result must match the exact reference."""
    import jax.numpy as jnp
    from veles_tpu.parallel.ulysses import ulysses_attention
    rng = numpy.random.RandomState(5)
    b, t, h, d = 1, 128, 4, 16          # t divisible by flash blocks
    q, k, v = [jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
               for _ in range(3)]
    mesh = seq_mesh(4)
    from veles_tpu.ops import flash_attention as fa
    calls = []
    real_flash = fa.flash_attention
    prev = vt.root.common.engine.flash_attention
    vt.root.common.engine.flash_attention = "force"
    fa.flash_attention = lambda *a, **k2: (calls.append(1),
                                           real_flash(*a, **k2))[1]
    try:
        out = ulysses_attention(q, k, v, mesh, causal=True)
    finally:
        vt.root.common.engine.flash_attention = prev
        fa.flash_attention = real_flash
    assert calls, "flash path never taken — test would compare " \
                  "reference against itself"
    ref = attention_reference(q, k, v, causal=True)
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref),
                                  rtol=2e-4, atol=2e-5)


def test_ring_attention_windowed_matches_reference():
    """Ring + sliding window: masked AND ring-shortened (the scan runs
    ceil((W-1+Tl)/Tl) rotations, not n) vs the windowed reference.
    Windows chosen to need 1, 2, and all ring hops at Tl = 8."""
    import jax.numpy as jnp
    rng = numpy.random.RandomState(7)
    b, t, h, d = 1, 32, 2, 4
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    mesh = seq_mesh(4)
    for win in (4, 8, 13, 31):
        out = ring_attention(q, k, v, mesh, causal=True, window=win)
        ref = attention_reference(q, k, v, causal=True, window=win)
        numpy.testing.assert_allclose(
            numpy.asarray(out), numpy.asarray(ref), rtol=2e-4,
            atol=2e-5, err_msg="window=%d" % win)


def test_ring_attention_windowed_differentiable():
    import jax
    import jax.numpy as jnp
    rng = numpy.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 16, 2, 4).astype(numpy.float32))
    mesh = seq_mesh(4)

    def loss_ring(q):
        return (ring_attention(q, q, q, mesh, causal=True,
                               window=6) ** 2).sum()

    def loss_ref(q):
        from veles_tpu.parallel.ring_attention import attention_reference
        return (attention_reference(q, q, q, causal=True,
                                    window=6) ** 2).sum()

    g1 = jax.grad(loss_ring)(q)
    g2 = jax.grad(loss_ref)(q)
    numpy.testing.assert_allclose(numpy.asarray(g1), numpy.asarray(g2),
                                  rtol=5e-4, atol=5e-4)


def test_ulysses_windowed_matches_reference():
    import jax.numpy as jnp
    from veles_tpu.parallel.ulysses import ulysses_attention
    rng = numpy.random.RandomState(9)
    b, t, h, d = 1, 32, 4, 8
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    mesh = seq_mesh(4)
    out = ulysses_attention(q, k, v, mesh, causal=True, window=9)
    ref = attention_reference(q, k, v, causal=True, window=9)
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref), rtol=2e-4,
                                  atol=2e-5)


def test_ring_window_requires_causal():
    import jax.numpy as jnp
    import pytest as _pytest
    q = jnp.zeros((1, 16, 2, 4), jnp.float32)
    with _pytest.raises(ValueError, match="causal"):
        ring_attention(q, q, q, seq_mesh(4), causal=False, window=4)


@pytest.fixture()
def flash_forced():
    """engine.flash_attention="force": the kernel interprets off-TPU
    (its own default is to compile, which the CPU backend refuses)."""
    prev = vt.root.common.engine.flash_attention
    vt.root.common.engine.flash_attention = "force"
    yield
    vt.root.common.engine.flash_attention = prev


def test_ring_attention_flash_engine_matches_reference(flash_forced):
    """Flash-in-ring (Pallas inner engine, peeled diagonal + lse
    merge): forward must match the exact reference for causal AND full
    attention. CPU runs the kernel in interpret mode (flash_forced;
    use_flash=True overrides the length gate)."""
    import jax.numpy as jnp
    rng = numpy.random.RandomState(11)
    b, t, h, d = 1, 256, 2, 8
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    mesh = seq_mesh(2)
    for causal in (False, True):
        out = ring_attention(q, k, v, mesh, causal=causal,
                             use_flash=True)
        ref = attention_reference(q, k, v, causal=causal)
        numpy.testing.assert_allclose(
            numpy.asarray(out), numpy.asarray(ref), rtol=2e-4,
            atol=2e-5)


def test_ring_attention_flash_engine_gradients(flash_forced):
    """The blockwise ring backward (global-lse recompute) under the
    flash forward: grads of a scalar loss wrt q, k, v match the
    autodiff of the exact reference."""
    import jax
    import jax.numpy as jnp
    rng = numpy.random.RandomState(12)
    b, t, h, d = 1, 256, 2, 8
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    mesh = seq_mesh(2)

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, mesh, causal=True, use_flash=True)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=True)
        return (o.astype(jnp.float32) ** 2).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        numpy.testing.assert_allclose(
            numpy.asarray(gr), numpy.asarray(gf), rtol=2e-3, atol=2e-4)


def test_ring_attention_flash_refuses_window():
    import jax.numpy as jnp
    x = jnp.zeros((1, 256, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="window"):
        ring_attention(x, x, x, seq_mesh(2), causal=True, window=32,
                       use_flash=True)


def test_ring_attention_einsum_bwd_window_matches_reference():
    """Window rings stay on the einsum engine; the custom blockwise
    backward must reproduce reference gradients through the
    window-shortened scan (incl. the accumulator fast-forward home)."""
    import jax
    import jax.numpy as jnp
    rng = numpy.random.RandomState(13)
    b, t, h, d = 1, 32, 2, 4
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    mesh = seq_mesh(8)          # tl=4; window=6 -> steps=3 of 8

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, mesh, causal=True, window=6)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=True, window=6)
        return (o.astype(jnp.float32) ** 2).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        numpy.testing.assert_allclose(
            numpy.asarray(gr), numpy.asarray(gf), rtol=2e-3, atol=2e-4)
