"""The paged decode-attention kernel (ops/paged_attention.py) against a
plain float32 einsum over the gathered view, in Pallas interpret mode
on the CPU: the pool's positions before the row's, read through its
page-table row, and the row's own new key beside them. The pages a row
cannot see hold NaN here, so a kernel that read one, or weighed one's
value by nought, would show it. The same kernel compiles for a
described v5e in tests/test_chip_compile.py."""
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import paged_attention as pa

PAGE = 4
WIDTH = 8                   # pages a table row: 32 positions
POOL = 72


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # blocks of two pages, so that a row's read crosses blocks and the
    # double buffer turns over
    monkeypatch.setattr(pa, "BLOCK_POSITIONS", 2 * PAGE)


def _case(slots, heads, kv, hd, pos, seed, dtype, window=None, vd=None):
    """Pools, tables, queries and new rows for rows at ``pos``: the
    pages that hold a row's positions before ``pos`` are distinct pages
    of the pool; its table's tail points at the sink page 0 (a row at 0
    has an all-sink table); every position a row cannot see in its own
    pages, and the whole sink page, holds NaN. Values are ``vd`` wide
    (None: ``hd``)."""
    rng = numpy.random.RandomState(seed)
    vd = hd if vd is None else vd
    k_pool = rng.randn(POOL, PAGE, kv, hd).astype(numpy.float32)
    v_pool = rng.randn(POOL, PAGE, kv, vd).astype(numpy.float32)
    k_pool[0] = v_pool[0] = numpy.nan
    ids = rng.permutation(numpy.arange(1, POOL))
    tables = numpy.zeros((slots, WIDTH), numpy.int32)
    for s, p in enumerate(pos):
        live = -(-p // PAGE)            # pages that hold positions < p
        tables[s, :live] = ids[s * WIDTH:s * WIDTH + live]
        for t in range(p, live * PAGE):
            pg = tables[s, t // PAGE]
            k_pool[pg, t % PAGE] = v_pool[pg, t % PAGE] = numpy.nan
        if window:
            for t in range(0, max(0, p - window + 1)):
                pg = tables[s, t // PAGE]
                k_pool[pg, t % PAGE] = v_pool[pg, t % PAGE] = numpy.nan
    q = rng.randn(slots, heads, hd).astype(numpy.float32)
    k_new = rng.randn(slots, kv, hd).astype(numpy.float32)
    v_new = rng.randn(slots, kv, vd).astype(numpy.float32)
    cast = (lambda a: jnp.asarray(a, dtype))
    return (jnp.asarray(q), cast(k_pool), cast(v_pool), jnp.asarray(tables),
            cast(k_new), cast(v_new))


def _reference(q, k_pool, v_pool, tables, pos, k_new, v_new, window=None,
               scale=None):
    """Float32 attention over each row's gathered view with its new row
    written at ``pos``: positions <= pos (the last ``window``), scores
    times ``scale`` (None: ``1 / sqrt(hd)``)."""
    q = numpy.asarray(q, numpy.float64)
    slots, heads, hd = q.shape
    kv, vd = k_pool.shape[2], v_pool.shape[-1]
    scale = 1 / numpy.sqrt(hd) if scale is None else scale
    view_k = numpy.asarray(k_pool, numpy.float64)[numpy.asarray(tables)]
    view_v = numpy.asarray(v_pool, numpy.float64)[numpy.asarray(tables)]
    view_k = view_k.reshape(slots, -1, kv, hd).copy()
    view_v = view_v.reshape(slots, -1, kv, vd).copy()
    out = numpy.zeros((slots, heads, vd))
    for s, p in enumerate(pos):
        view_k[s, p] = numpy.asarray(k_new[s], numpy.float64)
        view_v[s, p] = numpy.asarray(v_new[s], numpy.float64)
        lo = max(0, p - window + 1) if window else 0
        for h in range(heads):
            g = h // (heads // kv)
            sc = view_k[s, lo:p + 1, g] @ q[s, h] * scale
            w = numpy.exp(sc - sc.max())
            out[s, h] = (w / w.sum()) @ view_v[s, lo:p + 1, g]
    return out


def _run(q, k_pool, v_pool, tables, pos, k_new, v_new, window=None,
         operand_dtype=None, scale=None):
    pos = jnp.asarray(pos, jnp.int32)
    start = (jnp.maximum(pos - window + 1, 0) if window
             else jnp.zeros_like(pos))
    return pa.paged_decode_attention(
        q, k_pool, v_pool, tables, start, pos, k_new[:, None],
        v_new[:, None], jnp.ones((q.shape[0], 1), bool), scale=scale,
        operand_dtype=operand_dtype, interpret=True)


def _tolerance(dtype):
    return 2e-5 if dtype == jnp.float32 else 1e-2


@pytest.mark.parametrize("group", [1, 4, 16])
@pytest.mark.parametrize("pos", [0, 1, 15, 16, 17, WIDTH * PAGE - 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_one_position(dtype, pos, group):
    """A row at ``pos`` against the reference, both pool dtypes, at the
    first position, across a page's edge and at the table's last."""
    kv = 2
    case = _case(2, kv * group, kv, 16, [pos, pos], seed=pos, dtype=dtype)
    got = _run(*case[:4], [pos, pos], *case[4:])
    want = _reference(*case[:4], [pos, pos], *case[4:])
    assert numpy.isfinite(numpy.asarray(got)).all()
    numpy.testing.assert_allclose(numpy.asarray(got), want,
                                  atol=_tolerance(dtype), rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_ragged_rows_with_masked_ones(dtype):
    """Eight slots at ragged positions; those with nothing in the pool
    (a masked-out slot stands at 0 with an all-sink table) see their
    own row alone."""
    pos = [0, 31, 5, 0, 12, 23, 8, 3]
    case = _case(8, 8, 2, 16, pos, seed=11, dtype=dtype)
    got = numpy.asarray(_run(*case[:4], pos, *case[4:]))
    want = _reference(*case[:4], pos, *case[4:])
    numpy.testing.assert_allclose(got, want, atol=_tolerance(dtype), rtol=0)
    v_new = numpy.asarray(case[5], numpy.float32)
    for s in (0, 3):
        numpy.testing.assert_allclose(
            got[s].reshape(2, 4, 16),
            numpy.broadcast_to(v_new[s][:, None], (2, 4, 16)),
            atol=_tolerance(dtype))


@pytest.mark.parametrize("window,pos", [(6, 13), (9, 29)],
                         ids=["starts_mid_page", "drops_whole_pages"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_window(dtype, window, pos):
    """A sliding window that starts inside a page, and one that leaves
    whole pages behind: the positions before it hold NaN and are never
    read."""
    case = _case(2, 8, 2, 16, [pos, pos - 3], seed=window, dtype=dtype,
                 window=window)
    got = _run(*case[:4], [pos, pos - 3], *case[4:], window=window)
    want = _reference(*case[:4], [pos, pos - 3], *case[4:], window=window)
    numpy.testing.assert_allclose(numpy.asarray(got), want,
                                  atol=_tolerance(dtype), rtol=0)


def test_pages_past_the_row_are_not_read():
    """The kernel's own state: ``l`` sums the weights of exactly the
    positions in ``[start, end)``; a row with an empty range gives
    ``l`` 0 and nothing else (its pages, all the sink's, hold NaN)."""
    pos = [0, 9, 32]
    q, k_pool, v_pool, tables, _, _ = _case(3, 4, 2, 16, pos, seed=5,
                                            dtype=jnp.float32)
    ends = jnp.asarray(pos, jnp.int32)
    acc, m, l = pa.paged_attention(q, k_pool, v_pool, tables,
                                   jnp.zeros_like(ends), ends,
                                   interpret=True)
    acc, m, l = map(numpy.asarray, (acc, m, l))
    assert (l[0] == 0).all() and (acc[0] == 0).all()
    assert numpy.isfinite(acc[1:]).all() and (l[1:] >= 1 - 1e-6).all()


def test_bfloat16_operands_stay_near_float32():
    """The chip's products (bfloat16 operands, float32 accumulation)
    against the float32 ones, interpreted: the rounding of the operands
    and no more."""
    pos = [30, 7]
    case = _case(2, 8, 2, 16, pos, seed=3, dtype=jnp.float32)
    f32 = numpy.asarray(_run(*case[:4], pos, *case[4:]))
    bf16 = numpy.asarray(_run(*case[:4], pos, *case[4:],
                              operand_dtype=jnp.bfloat16))
    assert 0 < numpy.abs(bf16 - f32).max() < 3e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kv,group,hd,true_hd,vd", [
    (2, 4, 32, 24, 16), (4, 16, 256, 192, 128)],
    ids=["narrow", "mimo_widths"])
def test_values_narrower_than_keys_and_the_callers_scale(dtype, kv, group,
                                                         hd, true_hd, vd):
    """Keys stored wider than they are (noughts past ``true_hd`` in the
    pool, the new row and q) and values of another width, scored at the
    caller's ``1 / sqrt(true_hd)``: the reference at the true width. At
    mimo's widths the pages reach the kernel as they lie (4 KV heads of
    256 lanes: ``_rows_of_pages``)."""
    pos = [13, 30]
    q, k_pool, v_pool, tables, k_new, v_new = _case(
        2, kv * group, kv, hd, pos, seed=hd, dtype=dtype, vd=vd)
    pad = numpy.arange(hd) >= true_hd
    q = jnp.where(pad, 0.0, q)
    k_pool = jnp.where(pad, jnp.zeros((), k_pool.dtype), k_pool)
    k_new = jnp.where(pad, jnp.zeros((), k_new.dtype), k_new)
    scale = 1 / numpy.sqrt(true_hd)
    got = numpy.asarray(_run(q, k_pool, v_pool, tables, pos, k_new, v_new,
                             scale=scale))
    assert got.shape == (2, kv * group, vd)
    want = _reference(q[..., :true_hd], k_pool[..., :true_hd], v_pool,
                      tables, pos, k_new[..., :true_hd], v_new)
    numpy.testing.assert_allclose(got, want, atol=_tolerance(dtype), rtol=0)
    assert pa._rows_of_pages(kv, hd, vd) is (hd <= pa.LANE)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_query_heads_padded_to_the_stored_kv_heads(dtype):
    """Six heads on six KV heads stored as eight (as 30 are stored as
    32): q padded with nought heads, the pools with nought heads; the six
    heads are the reference's over the six, and the padded ones read
    nothing but noughts."""
    pos = [9, 27]
    q, k_pool, v_pool, tables, k_new, v_new = _case(
        2, 8, 8, 16, pos, seed=30, dtype=dtype)
    heads = numpy.arange(8)[:, None] >= 6
    q = jnp.where(heads, 0.0, q)
    k_new = jnp.where(heads, jnp.zeros((), k_new.dtype), k_new)
    v_new = jnp.where(heads, jnp.zeros((), v_new.dtype), v_new)
    k_pool = jnp.where(heads, jnp.zeros((), k_pool.dtype), k_pool)
    v_pool = jnp.where(heads, jnp.zeros((), v_pool.dtype), v_pool)
    # the unseen positions' NaN stays in the true heads' rows
    got = numpy.asarray(_run(q, k_pool, v_pool, tables, pos, k_new, v_new))
    want = _reference(q[:, :6], k_pool[:, :, :6], v_pool[:, :, :6], tables,
                      pos, k_new[:, :6], v_new[:, :6])
    numpy.testing.assert_allclose(got[:, :6], want, atol=_tolerance(dtype),
                                  rtol=0)
    assert (got[:, 6:] == 0).all()


@pytest.mark.parametrize("head_dim,page,kv,dtype,v_dim,ok", [
    (128, 16, 8, jnp.float32, None, True),
    (128, 16, 8, jnp.bfloat16, None, True),
    (256, 16, 4, jnp.bfloat16, None, True),
    (256, 16, 4, jnp.bfloat16, 128, True),
    (128, 16, 32, jnp.bfloat16, 128, True),
    (128, 16, 8, jnp.float32, 64, False),
    (64, 16, 8, jnp.float32, None, False),
    (128, 8, 8, jnp.float32, None, False),
    (128, 16, 6, jnp.float32, None, False),
    (128, 16, 8, jnp.int8, None, False),
])
def test_supported(head_dim, page, kv, dtype, v_dim, ok):
    assert pa.supported(head_dim, page, kv, dtype, v_dim) is ok
