"""Pallas flash-attention kernel vs the exact reference attention
(forward + custom-VJP backward), and its wiring into MultiHeadAttention.
Runs in pallas interpret mode on the CPU test harness (asked for
explicitly: the kernel's own default is to compile); the same kernel
compiles via Mosaic on TPU (chip_smoke.py, scripts/chip_experiments.py
--sections pallas_compile)."""
import functools

import jax
import jax.numpy as jnp
import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn
from veles_tpu.memory import Array
from veles_tpu.ops import flash_attention as fa
from veles_tpu.ops.flash_attention import supported
from veles_tpu.parallel.ring_attention import attention_reference

flash_attention = functools.partial(fa.flash_attention, interpret=True)


def qkv(b=2, t=256, h=2, d=64, seed=0):
    rng = numpy.random.RandomState(seed)
    return [jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = qkv()
    o = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    numpy.testing.assert_allclose(numpy.asarray(o), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = qkv(b=1, t=128, h=2, d=32)

    def loss(f):
        return lambda q, k, v: (f(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        scale = float(jnp.abs(b).max())
        numpy.testing.assert_allclose(numpy.asarray(a) / scale,
                                      numpy.asarray(b) / scale,
                                      rtol=1e-4, atol=1e-5)


def test_head_dim_padding():
    """D=32 < 128 lanes: zero padding must not change the result."""
    q, k, v = qkv(t=128, d=32, seed=3)
    o = flash_attention(q, k, v)
    ref = attention_reference(q, k, v)
    numpy.testing.assert_allclose(numpy.asarray(o), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


def test_multi_lane_head_dim():
    """D=256 > one 128-lane group: runs with multi-lane blocks."""
    q, k, v = qkv(b=1, t=128, h=1, d=256, seed=5)
    o = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    numpy.testing.assert_allclose(numpy.asarray(o), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


def test_supported_predicate():
    assert supported(256, 64)
    assert not supported(200, 64)       # T not divisible by block
    assert supported(256, 256)          # multi-lane head dim
    assert not supported(256, 1024)     # beyond the VMEM budget bound


def test_mha_unit_routes_through_flash():
    prev = vt.root.common.engine.compute_dtype
    prev_flash = vt.root.common.engine.flash_attention
    vt.root.common.engine.compute_dtype = "float32"
    # CPU harness: production gating skips flash off-TPU; force interpret
    vt.root.common.engine.flash_attention = "force"
    try:
        wf = vt.Workflow(name="t")
        u = nn.MultiHeadAttention(wf, n_heads=2, causal=True)
        x = numpy.random.RandomState(0).randn(2, 128, 64).astype(
            numpy.float32)
        u.input = Array(x)
        u.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
        assert u.mesh is None           # single chip → flash eligible
        u.xla_run()
        y_flash = numpy.asarray(u.output.map_read())
        vt.root.common.engine.flash_attention = False
        u._jit_cache.clear()
        u.xla_run()
        y_ref = numpy.asarray(u.output.map_read())
        numpy.testing.assert_allclose(y_flash, y_ref, rtol=1e-4,
                                      atol=1e-5)
        y_np = u.numpy_apply(u.params_np(), x)
        numpy.testing.assert_allclose(y_flash, y_np, rtol=1e-3, atol=1e-4)
    finally:
        vt.root.common.engine.flash_attention = prev_flash
        vt.root.common.engine.compute_dtype = prev


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_bwd_matches_jnp_bwd(causal):
    """The Pallas backward twins the jnp blockwise oracle exactly
    (same math, same f32 accumulation) and the config switch selects
    between them."""
    from veles_tpu.config import root
    rng = numpy.random.RandomState(9)
    q, k, v = (jnp.asarray(rng.randn(2, 256, 2, 64), jnp.float32)
               for _ in range(3))

    def loss_fn(qq, kk, vv):
        return (flash_attention(qq, kk, vv, causal=causal)
                .astype(jnp.float32) ** 2).sum()

    def g(qq, kk, vv):
        return jax.grad(loss_fn, argnums=(0, 1, 2))(qq, kk, vv)

    assert root.common.engine.get("flash_attention_pallas_bwd",
                                  True) is True
    g_pallas = g(q, k, v)
    root.common.engine.flash_attention_pallas_bwd = False
    try:
        jax.clear_caches()      # the switch lives outside the trace
        g_jnp = g(q, k, v)
    finally:
        root.common.engine.flash_attention_pallas_bwd = True
        jax.clear_caches()
    for a, b in zip(g_pallas, g_jnp):
        numpy.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [128, 200, 256])
def test_windowed_forward_matches_reference(window):
    """Sliding window: flash (with dead-block skipping) vs the windowed
    reference mask. Windows chosen to hit block-aligned (128), block-
    straddling (200), and multi-block (256) horizons at bq=bk=128."""
    q, k, v = qkv(t=512, seed=3)
    o = flash_attention(q, k, v, causal=True, window=window)
    ref = attention_reference(q, k, v, causal=True, window=window)
    numpy.testing.assert_allclose(numpy.asarray(o), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pallas_bwd", [True, False])
def test_windowed_grads_match_reference(pallas_bwd):
    """Window masking through BOTH backwards (pallas kernels and the
    jnp blockwise fallback) vs autodiff of the windowed reference."""
    prev = vt.root.common.engine.get("flash_attention_pallas_bwd", True)
    vt.root.common.engine.flash_attention_pallas_bwd = pallas_bwd
    try:
        q, k, v = qkv(b=1, t=256, h=2, d=32, seed=4)
        win = 160

        def loss_fl(q, k, v):
            return (flash_attention(q, k, v, causal=True,
                                    window=win) ** 2).sum()

        def loss_ref(q, k, v):
            return (attention_reference(q, k, v, causal=True,
                                        window=win) ** 2).sum()

        g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            numpy.testing.assert_allclose(numpy.asarray(a),
                                          numpy.asarray(b),
                                          rtol=2e-4, atol=2e-4)
    finally:
        vt.root.common.engine.flash_attention_pallas_bwd = prev


def test_window_requires_causal():
    q, k, v = qkv(t=256)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)


def test_window_covering_everything_equals_full():
    """window >= T degenerates to full causal attention exactly."""
    q, k, v = qkv(t=256, seed=5)
    o_w = flash_attention(q, k, v, causal=True, window=4096)
    o_f = flash_attention(q, k, v, causal=True)
    numpy.testing.assert_allclose(numpy.asarray(o_w),
                                  numpy.asarray(o_f), rtol=1e-6)


def gqa_qkv(b=2, t=256, h=4, kv=2, d=64, seed=11):
    rng = numpy.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, t, h, d).astype(numpy.float32))
    k = jnp.asarray(rng.randn(b, t, kv, d).astype(numpy.float32))
    v = jnp.asarray(rng.randn(b, t, kv, d).astype(numpy.float32))
    return q, k, v


def _expand(x, h):
    b, t, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (b, t, kv, h // kv, d)).reshape(b, t, h, d)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_forward_matches_expanded(causal):
    """GQA-native kernel (grouped k/v consumed via index-map head
    remapping, never expanded into operands) vs the same attention on
    pre-expanded K/V."""
    q, k, v = gqa_qkv()
    o = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, _expand(k, 4), _expand(v, 4),
                              causal=causal)
    numpy.testing.assert_allclose(numpy.asarray(o), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pallas_bwd", [True, False])
def test_grouped_grads_match_expanded(pallas_bwd):
    """GQA grads through BOTH backwards. The pallas dkv grid folds
    (query-head-in-group, q-block) into its sequential dim so each kv
    head accumulates all its query heads' contributions; dk/dv must
    equal the group-summed expanded gradients."""
    prev = vt.root.common.engine.get("flash_attention_pallas_bwd", True)
    vt.root.common.engine.flash_attention_pallas_bwd = pallas_bwd
    try:
        q, k, v = gqa_qkv(b=1, t=128, h=4, kv=2, d=32, seed=12)

        def loss_fl(q, k, v):
            return (flash_attention(q, k, v, causal=True) ** 2).sum()

        def loss_ref(q, k, v):
            return (attention_reference(
                q, _expand(k, 4), _expand(v, 4), causal=True) ** 2).sum()

        g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            assert a.shape == b.shape
            numpy.testing.assert_allclose(numpy.asarray(a),
                                          numpy.asarray(b),
                                          rtol=2e-4, atol=2e-4)
    finally:
        vt.root.common.engine.flash_attention_pallas_bwd = prev


def test_grouped_windowed_forward():
    """GQA x sliding window in one kernel call."""
    q, k, v = gqa_qkv(t=512, seed=13)
    o = flash_attention(q, k, v, causal=True, window=200)
    ref = attention_reference(q, _expand(k, 4), _expand(v, 4),
                              causal=True, window=200)
    numpy.testing.assert_allclose(numpy.asarray(o), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


def test_mqa_extreme_grouping():
    """kv=1 (multi-query): every query head reads the single KV head."""
    q, k, v = gqa_qkv(h=8, kv=1, seed=14)
    o = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, _expand(k, 8), _expand(v, 8),
                              causal=True)
    numpy.testing.assert_allclose(numpy.asarray(o), numpy.asarray(ref),
                                  rtol=1e-4, atol=1e-5)


def test_mismatched_kv_heads_refused():
    q, k, v = gqa_qkv(h=4, kv=2)
    with pytest.raises(ValueError, match="head counts"):
        flash_attention(q, k, v[:, :, :1], causal=True)
    q2 = jnp.zeros((1, 256, 3, 64), jnp.float32)
    with pytest.raises(ValueError, match="head counts"):
        flash_attention(q2, jnp.zeros((1, 256, 2, 64), jnp.float32),
                        jnp.zeros((1, 256, 2, 64), jnp.float32),
                        causal=True)


def test_flash_runs_in_a_shard_map_on_a_multi_device_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic custom call, so under a mesh the
    model path wraps the kernel in a shard_map — batch over 'data',
    heads over 'tensor' — and still matches the reference, forward and
    backward. (The refusal itself only shows on real chips: interpret
    mode lowers to plain HLO.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from veles_tpu.nn.attention import attention_core
    mesh = Mesh(numpy.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "tensor"))
    q, k, v = (jax.device_put(x, NamedSharding(mesh, P("data")))
               for x in qkv(b=2, t=128, h=4, d=64))
    specs = []
    real = jax.shard_map

    def spy(fn, **kw):
        specs.append(kw["in_specs"][0])
        return real(fn, **kw)

    monkeypatch.setattr(jax, "shard_map", spy)
    monkeypatch.setattr(vt.root.common.engine, "flash_attention", "force",
                        raising=False)

    def loss(core):
        return lambda q, k, v: (core(q, k, v) ** 2).sum()

    def sharded(q, k, v):
        return attention_core(q, k, v, causal=True, mesh=mesh, n_heads=4)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=True)

    o = jax.jit(sharded)(q, k, v)
    assert specs == [P("data", None, "tensor", None)]
    numpy.testing.assert_allclose(numpy.asarray(o),
                                  numpy.asarray(ref(q, k, v)),
                                  rtol=1e-4, atol=1e-5)
    g = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        numpy.testing.assert_allclose(numpy.asarray(a), numpy.asarray(b),
                                      rtol=2e-3, atol=2e-4)
    # a pipeline stage already runs inside the schedule's shard_map
    pp = Mesh(numpy.asarray(jax.devices()[:2]), ("pipeline",))
    del specs[:]
    attention_core(*qkv(b=2, t=128, h=4, d=64), causal=True, mesh=pp,
                   n_heads=4)
    assert specs == []


# -- the tiles committed for head size 128 (PR 27) --

def _v5e_row(key):
    import json
    from veles_tpu.ops import autotune
    with open(autotune.SHIPPED) as f:
        return json.load(f)["TPU v5 lite"][key]


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("key", ["flash_t4096_d128_causal",
                                 "flash_t2048_d128_causal"])
def test_committed_d128_tiles_match_reference(key, dtype, tol, tmp_path,
                                              monkeypatch):
    """Forward and gradients at the tiles a v5e row commits, in the
    cell's shape class (GQA 2 on 1, head size 128, float32 and bfloat16
    operands), T two of the largest tile a side: the causal skip, the
    diagonal tile and the last tile's ``_finish`` are all walked. The
    row is planted at that T in a DB of its own and resolved as the
    model path resolves it (None blocks), so a row's backward tiles run
    the backward."""
    from veles_tpu.ops import autotune
    row = _v5e_row(key)
    tiles = {k: row[k] for k in ("block_q", "block_k", "bwd_block_q",
                                 "bwd_block_k") if k in row}
    t = 2 * max(tiles.values())
    monkeypatch.setattr(autotune, "SHIPPED", str(tmp_path / "db.json"))
    monkeypatch.setattr(autotune, "current_device_kind", lambda: "fake")
    autotune.clear_memo()
    autotune.record(autotune.flash_key(t, 128, True), tiles)
    walked = {}
    fwd, bwd = fa._fwd_pallas, fa._bwd_pallas_core
    monkeypatch.setattr(fa, "_fwd_pallas", lambda *a, **kw: (
        walked.setdefault("fwd", a[5:7]), fwd(*a, **kw))[1])
    monkeypatch.setattr(fa, "_bwd_pallas_core", lambda *a, **kw: (
        walked.setdefault("bwd", a[8:10]), bwd(*a, **kw))[1])
    rng = numpy.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, t, heads, 128), dtype)
               for heads in (2, 1, 1))

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32)
                                ** 2).sum()

    def ref(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        return attention_reference(q, jnp.repeat(k, 2, axis=2),
                                   jnp.repeat(v, 2, axis=2), causal=True)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    try:
        got = (flash(q, k, v),) + jax.grad(
            loss(flash), argnums=(0, 1, 2))(q, k, v)
    finally:
        autotune.clear_memo()
    want = (ref(q, k, v),) + jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    assert walked == {
        "fwd": (tiles["block_q"], tiles["block_k"]),
        "bwd": (tiles.get("bwd_block_q", tiles["block_q"]),
                tiles.get("bwd_block_k", tiles["block_k"]))}
    for a, b in zip(got, want):
        scale = float(jnp.abs(b).max())
        numpy.testing.assert_allclose(
            numpy.asarray(a, numpy.float32) / scale,
            numpy.asarray(b, numpy.float32) / scale, rtol=0, atol=tol)


def test_vmem_limit_is_counted_from_the_tiles():
    """Small tiles ask for Mosaic's default, so they lower as they
    always have; the limit grows with the tile and the itemsize, and
    stops at the cap."""
    small = fa._vmem_limit(128, 128, 128, 4, q_tiles=2, k_tiles=4,
                           f32_elems=2 * 128 * 128, scores=fa.BWD_SCORES)
    assert small == fa.VMEM_DEFAULT
    f32 = fa._vmem_limit(1024, 1024, 128, 4, q_tiles=2, k_tiles=4,
                         f32_elems=2 * 1024 * 128, scores=fa.BWD_SCORES)
    bf16 = fa._vmem_limit(1024, 1024, 128, 2, q_tiles=2, k_tiles=4,
                          f32_elems=2 * 1024 * 128, scores=fa.BWD_SCORES)
    # four float32 score tiles of 4 MiB and six double-buffered operand
    # tiles: more than the default, and more at four bytes than at two
    assert fa.VMEM_DEFAULT < bf16 < f32 < fa.VMEM_CAP
    assert f32 >= 4 * 4 * 1024 * 1024 + 2 * 6 * 1024 * 128 * 4
    huge = fa._vmem_limit(4096, 4096, 512, 4, q_tiles=2, k_tiles=4,
                          f32_elems=2 * 4096 * 512, scores=fa.BWD_SCORES)
    assert huge == fa.VMEM_CAP
