"""Flash attention: Pallas TPU kernel for the attention core.

The one place in the op set where XLA fusion is genuinely insufficient
(SURVEY.md §7 "Pallas only where XLA fusion is insufficient"): naive
attention materializes the (B, H, T, T) score matrix in HBM, so for long
sequences the op is HBM-bandwidth-bound. This kernel streams K/V blocks
through VMEM with an online softmax (running max/sum rescaling), keeping
the working set at (block_q × block_k) — the standard flash-attention
recipe expressed in Pallas (guide: /opt/skills/guides/pallas_guide.md;
same tiling discipline as the public jax.experimental.pallas TPU ops).

The backward pass recomputes scores blockwise from the saved
log-sum-exp (``lse``) under ``jax.custom_vjp`` — O(T·block) memory, no
(T, T) materialization. Two Pallas kernels (dk/dv accumulating over Q
blocks; dq accumulating over K/V blocks) keep the recompute working set
VMEM-resident like the forward; ``_bwd_blockwise`` (plain jnp) is kept
as the oracle and the fallback
(``root.common.engine.flash_attention_pallas_bwd = False``).

Layout contract: (B, T, H, D) like the rest of the attention stack; heads
are folded into the grid's leading dimension. D is zero-padded to the
128-lane width (zero features change neither scores nor outputs).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

LANE = 128
NEG_INF = -1e30

#: Mosaic's own scoped-VMEM budget: what a ``pallas_call`` gets when it
#: asks for nothing, so small tiles compile exactly as they always have
VMEM_DEFAULT = 16 << 20
#: the most a kernel here asks for: three quarters of the 128 MiB a
#: v5e core has (the smallest VMEM of the TPUs this build runs on)
VMEM_CAP = 96 << 20
#: (block_q, block_k) float32 temporaries a kernel's step holds: s and
#: p in the forward; s, p, dp and ds in the backward pair
FWD_SCORES, BWD_SCORES = 2, 4


def _vmem_limit(block_q: int, block_k: int, d: int, itemsize: int,
                q_tiles: int, k_tiles: int, f32_elems: int,
                scores: int) -> int:
    """``vmem_limit_bytes`` of one kernel, counted from its tiles: the
    pipelined (block, D) operand and result tiles, two buffers each
    (``q_tiles`` of block_q rows, ``k_tiles`` of block_k rows, at the
    widest operand's ``itemsize``); ``f32_elems`` of float32 scratch
    (accumulators, and the forward's two lane-wide running rows); four
    sublane-padded (8, block_q) float32 rows, two buffers each; and
    ``scores`` float32 temporaries of (block_q, block_k). An upper
    bound, not a fit: this Mosaic (libtpu 0.0.34) chains the
    elementwise steps through registers and lowered the float32
    backward at 1024x1024, D 128, in 9 MiB where this counts 23, and
    at 2048x2048 in 35 where this counts 78 (compiled for a described
    v5e, PR 27). Never under Mosaic's default, so tiles that lowered
    without a limit still do; never over ``VMEM_CAP`` (a tile pair
    that needs more fails to lower, and the sweep records that)."""
    tiles = 2 * itemsize * d * (q_tiles * block_q + k_tiles * block_k)
    rows = 2 * 4 * 8 * block_q * 4
    need = tiles + rows + 4 * f32_elems + 4 * scores * block_q * block_k
    return max(VMEM_DEFAULT, min(VMEM_CAP, need))


def _itemsize(*arrays_or_dtypes) -> int:
    return max(jnp.dtype(getattr(x, "dtype", x)).itemsize
               for x in arrays_or_dtypes)


def _mask_scores(s, q_start, k_start, block_q: int, block_k: int,
                 causal: bool, window: int):
    """The one copy of the score mask all three kernels share:
    causal (k <= q) and, when ``window`` > 0, sliding-window
    (q - k < window: each query attends to itself plus window-1
    predecessors — the Mistral convention)."""
    if not causal and not window:
        return s
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = None
    if causal:
        keep = q_pos >= k_pos
    if window:
        in_win = q_pos - k_pos < window
        keep = in_win if keep is None else keep & in_win
    return jnp.where(keep, s, NEG_INF)


def _block_live(q_start, k_start, block_q: int, block_k: int,
                causal: bool, window: int):
    """Whether a (q-block, k-block) pair holds ANY unmasked score —
    the block-skip predicate paired with _mask_scores. Causal kills
    blocks strictly above the diagonal; a window kills blocks entirely
    behind every query row's horizon."""
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window:
        live = jnp.logical_and(
            live, k_start + block_k - 1 >
            q_start - window)  # newest k in block within oldest q's win
    return live


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            window: int):
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _step():
        q = q_ref[0]                    # (bq, D)
        k = k_ref[0]                    # (bk, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        s = _mask_scores(s, q_start, k_start, block_q, block_k,
                         causal, window)
        m_prev = m_scr[:, :1]                       # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)             # (bq, 1)
        p = jnp.exp(s - m_cur)                      # (bq, bk)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + p.sum(axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window:
        # skip K/V blocks with no unmasked scores (above the causal
        # diagonal / behind the window horizon)
        pl.when(_block_live(q_start, k_start, block_q, block_k,
                            causal, window))(_step)
    else:
        _step()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        # (8, bq) sublane-padded: TPU block shapes need ≥(8, 128) tiles
        lse_ref[0] = jnp.broadcast_to(
            (m_scr[:, :1] + jnp.log(l))[:, 0][None, :], lse_ref.shape[1:])


def _kv_fold_of(h: int, kv: int):
    """Map a folded (batch*h) q-grid index to the folded (batch*kv)
    K/V row its query head reads — the GQA head-group mapping expressed
    as a BlockSpec index transform, so grouped K/V are NEVER expanded
    in the kernel operands (query head qh reads kv head qh // (h//kv))."""
    group = h // kv

    def kv_of(g):
        return (g // h) * kv + (g % h) // group
    return kv_of


def _fwd_pallas(q, k, v, causal: bool, scale: float, block_q: int,
                block_k: int, interpret: bool, window: int = 0,
                h: int = 1, kv: int = 1):
    """q: (B*h, T, D); k/v: (B*kv, T, D) with D == LANE (kv == h is
    MHA) → (o (B*h, T, D), lse (B*h, 8, T) sublane-padded — callers
    use ``lse[:, 0, :]``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    g, t, d = q.shape
    kv_of = _kv_fold_of(h, kv)
    grid = (g, t // block_q, t // block_k)
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (kv_of(b), j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (kv_of(b), j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, d), q.dtype),
            jax.ShapeDtypeStruct((g, 8, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANE), jnp.float32),
            pltpu.VMEM((block_q, LANE), jnp.float32),
        ],
        # scheduling hint, not semantics: head and Q-block grid dims
        # carry no state between steps, so Mosaic may parallelize /
        # pipeline them; only the K/V dim accumulates in scratch and
        # must stay sequential ("arbitrary")
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                block_q, block_k, d, _itemsize(q, k, v), q_tiles=2,
                k_tiles=2, f32_elems=block_q * (d + 2 * LANE),
                scores=FWD_SCORES)),
        interpret=interpret,
        # the name is the device operation's in a profiler capture
        # (veles_flash_fwd.N); chipbench/metrics/flash_step_ms.py and
        # `veles_tpu trace self-time` find the kernels by it
        name="veles_flash_fwd",
    )(q, k, v)


def _bwd_blockwise(causal, scale, block_k, window, res, do):
    """Blockwise recompute backward (no (T, T) materialization).
    Grouped (GQA) k/v with fewer rows than q are expanded per block for
    the recompute and the dk/dv contributions summed back per group."""
    q, k, v, o, lse = res
    g, t, d = q.shape
    gk = k.shape[0]
    if gk != g:
        group = g // gk
        kx = jnp.broadcast_to(k[:, None], (gk, group, t, d)
                              ).reshape(g, t, d)
        vx = jnp.broadcast_to(v[:, None], (gk, group, t, d)
                              ).reshape(g, t, d)
        dq, dk, dv = _bwd_blockwise(causal, scale, block_k, window,
                                    (q, kx, vx, o, lse), do)
        dk = dk.reshape(gk, group, t, d).sum(1).astype(k.dtype)
        dv = dv.reshape(gk, group, t, d).sum(1).astype(v.dtype)
        return dq, dk, dv
    nk = t // block_k
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)
             ).sum(-1)                                      # (G, T)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    q_pos = jnp.arange(t)

    def body(dq, j):
        ks = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k, 1)
        ksf = ks.astype(jnp.float32)
        s = jnp.einsum("gqd,gkd->gqk", qf, ksf) * scale
        if causal or window:
            k_pos = j * block_k + jnp.arange(block_k)
            rel = q_pos[None, :, None] - k_pos[None, None, :]
            keep = rel >= 0 if causal else True
            if window:
                in_win = rel < window
                keep = in_win if keep is True else keep & in_win
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                     # (G, T, bk)
        dv = jnp.einsum("gqk,gqd->gkd", p, dof)
        dp = jnp.einsum("gqd,gkd->gqk", dof, vs.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("gqk,gkd->gqd", ds, ksf)
        dk = jnp.einsum("gqk,gqd->gkd", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 1).reshape(g, t, d)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(g, t, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def _bwd_dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, block_q: int,
                    block_k: int, window: int, n_q_blocks: int = 0):
    from jax.experimental import pallas as pl

    ki, j = pl.program_id(1), pl.program_id(2)
    # grouped (GQA) grids fold (query-head-in-group, q-block) into the
    # sequential dim: j = qh * n_q_blocks + qi. n_q_blocks=0 → MHA (j
    # IS the q-block index).
    qi = j % n_q_blocks if n_q_blocks else j

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k

    def _step():
        q = q_ref[0]                       # (bq, D)
        do = do_ref[0]                     # (bq, D)
        k = k_ref[0]                       # (bk, D)
        v = v_ref[0]
        lse = lse_ref[0][:1].T             # (bq, 1) from (8, bq) row 0
        delta = delta_ref[0][:1].T         # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        s = _mask_scores(s, q_start, k_start, block_q, block_k,
                         causal, window)
        p = jnp.exp(s - lse)               # (bq, bk) f32
        # dv_j += p^T do_i    (contract the bq axis)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bq, bk)
        ds = p * (dp - delta) * scale
        # dk_j += ds^T q_i
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window:
        # same liveness predicate as the forward, from the k block's
        # perspective (q/k roles swap in the grid, the set of live
        # (q, k) pairs does not)
        pl.when(_block_live(q_start, k_start, block_q, block_k,
                            causal, window))(_step)
    else:
        _step()

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale: float, causal: bool,
                   block_q: int, block_k: int, window: int):
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    k_start = ki * block_k

    def _step():
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0][:1].T
        delta = delta_ref[0][:1].T
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, q_start, k_start, block_q, block_k,
                         causal, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dq_i += ds k_j
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window:
        pl.when(_block_live(q_start, k_start, block_q, block_k,
                            causal, window))(_step)
    else:
        _step()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, causal: bool, scale: float,
                block_q: int, block_k: int, interpret: bool,
                window: int = 0, h: int = 1, kv: int = 1):
    """Pallas twin of ``_bwd_blockwise``: same math, VMEM-resident
    blockwise recompute. delta = rowsum(do*o) is O(T·D) and computed
    outside the kernels."""
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    return _bwd_pallas_core(q, k, v, lse, delta, do, causal, scale,
                            block_q, block_k, interpret, window, h, kv)


def _bwd_pallas_core(q, k, v, lse, delta, do, causal: bool,
                     scale: float, block_q: int, block_k: int,
                     interpret: bool, window: int = 0, h: int = 1,
                     kv: int = 1, out_dtype=None):
    """The kernel pair behind the backward, against a CALLER-SUPPLIED
    normalizer: ``p = exp(s − lse)`` with ``lse``/``delta`` (G, T)
    computed over whatever attention the caller ran (the full T here;
    the GLOBAL ring softmax in parallel/ring_attention.py — that is
    what makes these kernels reusable per ring step). lse/delta ride
    in the forward's (G, 8, T) sublane-padded layout. GQA (kv < h):
    k/v stay grouped (B*kv rows); the dq grid remaps K/V reads per
    query head, and the dk/dv grid runs over the GROUPED rows with
    (query-head-in-group, q-block) folded into its sequential
    dimension — each kv head's gradient accumulates the contributions
    of all h/kv query heads with no expanded operands and no racy
    parallel writes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    g, t, d = q.shape
    gk = k.shape[0]
    group = h // kv
    nq, nk = t // block_q, t // block_k
    kv_of = _kv_fold_of(h, kv)

    def q_of(b, j):
        # dkv grid: b indexes grouped K/V rows; j = qh * nq + qi
        return (b // kv) * h + (b % kv) * group + j // nq

    itemsize = _itemsize(q, k, v, do, *([out_dtype] if out_dtype else []))
    pad8 = jnp.broadcast_to(delta[:, None, :], (g, 8, t))
    lse8 = jnp.broadcast_to(lse[:, None, :], (g, 8, t))
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, window=window)
    qspec = pl.BlockSpec((1, block_q, d),
                         lambda b, i, j: (q_of(b, j), j % nq, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    row_q = pl.BlockSpec((1, 8, block_q),
                         lambda b, i, j: (q_of(b, j), 0, j % nq),
                         memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q_blocks=nq, **common),
        grid=(gk, nk, nq * group),
        in_specs=[qspec, qspec, kspec, kspec, row_q, row_q],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((gk, t, d), out_dtype or k.dtype),
            jax.ShapeDtypeStruct((gk, t, d), out_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                block_q, block_k, d, itemsize, q_tiles=2, k_tiles=4,
                f32_elems=2 * block_k * d, scores=BWD_SCORES)),
        interpret=interpret,
        name="veles_flash_bwd_dkv",
    )(q, do, k, v, lse8, pad8)
    dq, = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (kv_of(b), j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (kv_of(b), j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((g, t, d), out_dtype or q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                block_q, block_k, d, itemsize, q_tiles=3, k_tiles=2,
                f32_elems=block_q * d, scores=BWD_SCORES)),
        interpret=interpret,
        name="veles_flash_bwd_dq",
    )(q, do, k, v, lse8, pad8)
    return dq, dk, dv


def _use_pallas_bwd() -> bool:
    from ..config import root
    return bool(root.common.engine.get("flash_attention_pallas_bwd",
                                       True))


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, causal, scale, blocks, bwd_blocks, interpret,
           window, h, kv, d_logical):
    o, _ = _fwd_pallas(q, k, v, causal, scale, *blocks,
                       interpret, window, h, kv)
    return o


def _flash_fwd(q, k, v, causal, scale, blocks, bwd_blocks, interpret,
               window, h, kv, d_logical):
    o, lse = _fwd_pallas(q, k, v, causal, scale, *blocks,
                         interpret, window, h, kv)
    # residuals keep the GROUPED k/v — the GQA memory saving holds
    # through the backward
    return o, (q, k, v, o, lse[:, 0, :])


def _flash_bwd(causal, scale, blocks, bwd_blocks, interpret, window,
               h, kv, d_logical, res, do):
    block_q, block_k = bwd_blocks
    q = res[0]
    # trace-time analytic note for the backward pair (standard 2.5×
    # the forward: blockwise recompute + 4 gradient matmuls), billed
    # at the LOGICAL head dim (``d_logical`` rides the nondiff args:
    # the folded residual is lane-padded, and model FLOPs count the
    # useful dim, matching the forward note)
    from ..telemetry.cost import note_kernel_cost
    note_kernel_cost(analytic_cost(
        q.shape[0] // h, q.shape[1], h, d_logical, causal,
        window).scaled(2.5))
    if _use_pallas_bwd():
        q, k, v, o, lse = res
        return _bwd_pallas(q, k, v, o, lse, do, causal, scale,
                           block_q, block_k, interpret, window, h, kv)
    return _bwd_blockwise(causal, scale, block_k, window, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


#: VMEM budget bound. A kernel's working set grows with its tiles, not
#: with D alone, and ``_vmem_limit`` counts it per call: the backward
#: at the largest committed tiles (1024x1024), float32, D 512 counts
#: 44 MiB of ``VMEM_CAP``'s 96; at the 128-row default tiles it is
#: under 4 MiB. Past 512 lanes nothing has been compiled or measured
MAX_D = 512


def supported(t: int, d: int, block_q: int = 128,
              block_k: int = 128) -> bool:
    """Head dims beyond one lane group run with D zero-padded to the next
    128 multiple (zero features change neither scores nor outputs);
    above MAX_D the padded working set would pressure VMEM — callers
    fall back to the fused XLA reference."""
    return t % block_q == 0 and t % block_k == 0 and d <= MAX_D


def choose_flash(t: int, d: int) -> bool:
    """THE policy predicate for picking this kernel over the fused XLA
    reference — one definition shared by every call site
    (nn/attention.attention_core, parallel/ulysses) so the crossover
    cannot silently diverge between paths. True when the config enables
    flash, the shapes qualify, and T is past the measured crossover
    (engine.flash_attention_min_t, docs/perf.md); "force" overrides the
    backend/length gates (pallas interpret mode — tests only)."""
    import jax
    from ..config import root
    cfg = root.common.engine.flash_attention
    if not cfg:
        return False
    if not supported(t, d):
        return False
    if cfg == "force":
        return True
    if jax.default_backend() != "tpu":
        return False          # before any DB read — off-TPU never flash
    # per-device measured crossover (seeded by the chip attn sweep;
    # 4096 for a device or head size not swept yet); one resolver
    # shared with the bench gate
    from .autotune import resolved_min_t
    return t >= resolved_min_t(d)


def analytic_cost(b: int, t: int, h: int, d: int, causal: bool = False,
                  window: int = 0, train: bool = False,
                  dtype_bytes: int = 2):
    """Telemetry fallback cost of one flash-attention call
    (veles_tpu/telemetry/cost.py): the Pallas custom call is opaque to
    XLA's HLO cost model, so the kernel's owner publishes the standard
    analytic model instead. FLOPs: 2·T·T_ctx·D per head for QK^T plus
    the same for PV (T_ctx = T/2 causal, min(T, W) windowed); training
    adds the blockwise backward at the standard 2.5× forward
    (recompute + 4 gradient matmuls). Bytes: the HBM traffic floor —
    q/k/v read + o written (+lse), ×3 round trips under training."""
    from ..telemetry.cost import Cost
    t_ctx = float(t)
    if window:
        t_ctx = min(t_ctx, float(window))
    elif causal:
        t_ctx = t / 2.0
    fwd = 4.0 * b * h * t * t_ctx * d
    flops = fwd * 3.5 if train else fwd
    io = b * h * t * d * dtype_bytes
    lse = b * h * t * 4
    bytes_accessed = (4 * io + lse) * (3 if train else 1)
    # VMEM working set: ~5 f32 (block, D_padded) tiles per grid step
    d_pad = ((d + LANE - 1) // LANE) * LANE
    peak = 5.0 * 128 * d_pad * 4
    return Cost(flops, bytes_accessed, peak, source="analytic")


def _prepare(q, k, v, scale, block_q, block_k, interpret, caller,
             causal=False, window=0):
    """Shared prologue for the public entry points: validation, scale
    default, interpret default, block resolution (``None`` blocks go
    through the per-device autotune DB — ``ops/autotune.py``, the
    build's port of the reference's measured-per-device block sizes,
    `veles/backends.py:623-731`), and the head-fold + lane-pad of the
    operands. Returns (q3, k3, v3, scale, interpret, b, t, h, kv, d,
    blocks, bwd_blocks): the backward pair's blocks are the forward's
    unless the DB's row carries its own."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    bwd_q, bwd_k = block_q, block_k
    if block_q is None or block_k is None:
        from .autotune import flash_blocks_fwd_bwd
        fwd, bwd = flash_blocks_fwd_bwd(
            t, d, causal=causal, window=window,
            dtype=jnp.result_type(q, k, v))
        if block_q is None:
            block_q, bwd_q = fwd[0], bwd[0]
        if block_k is None:
            block_k, bwd_k = fwd[1], bwd[1]
    if v.shape[2] != kv or h % kv:
        raise ValueError(
            "k/v head counts must match and divide q heads: q has %d, "
            "k %d, v %d" % (h, kv, v.shape[2]))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    for bq, bk in {(block_q, block_k), (bwd_q, bwd_k)}:
        if not supported(t, d, bq, bk):
            raise ValueError("%s: T=%d D=%d not supported with blocks "
                             "(%d, %d)" % (caller, t, d, bq, bk))
    if interpret is None:
        # compiled, or an error: a kernel reached off-TPU must not run
        # interpreted without a word. The one exception is the test
        # harness's engine.flash_attention="force" (tests may also
        # pass interpret=True themselves)
        from ..config import root
        interpret = (root.common.engine.flash_attention == "force"
                     and jax.default_backend() != "tpu")
    d_pad = ((d + LANE - 1) // LANE) * LANE

    def fold(x):
        heads = x.shape[2]
        xt = jnp.moveaxis(x, 2, 1).reshape(b * heads, t, d)
        if d < d_pad:
            xt = jnp.pad(xt, ((0, 0), (0, 0), (0, d_pad - d)))
        return xt

    return (fold(q), fold(k), fold(v), float(scale), interpret,
            b, t, h, kv, d, (block_q, block_k), (bwd_q, bwd_k))


def flash_attention_fwd_lse(q, k, v, causal: bool = False,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """FORWARD-ONLY flash returning ``(o, lse)`` with lse ``(B, T, H)``
    (log-sum-exp of the scaled scores per query row). No custom VJP —
    the caller owns differentiation: ring attention merges per-block
    partials by lse and defines the blockwise ring backward itself
    (parallel/ring_attention.py). Same folding/padding/support rules
    as :func:`flash_attention`."""
    q3, k3, v3, scale, interpret, b, t, h, kv, d, blocks, _ = \
        _prepare(q, k, v, scale, block_q, block_k, interpret,
                 "flash_attention_fwd_lse", causal=causal)
    o, lse = _fwd_pallas(q3, k3, v3, causal, scale, *blocks,
                         interpret, 0, h, kv)
    o = jnp.moveaxis(o[..., :d].reshape(b, h, t, d), 1, 2)
    lse = jnp.moveaxis(lse[:, 0, :].reshape(b, h, t), 1, 2)  # (B,T,H)
    return o, lse


def flash_attention_bwd_lse(q, k, v, lse, delta, do,
                            causal: bool = False,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Blockwise flash BACKWARD against an external (global) softmax
    normalizer: ``(dq, dk, dv)`` contributions of this K/V block set,
    with ``p = exp(s − lse)``. ``lse``/``delta = rowsum(do·o)`` are
    (B, T, H), computed by the caller over the FULL attention — ring
    attention's per-step backward engine (the global lse makes each
    block's probabilities exact regardless of which blocks this call
    sees). VMEM-resident kernels; no (T, T) materialization."""
    q3, k3, v3, scale, interpret, b, t, h, kv, d, _, blocks = \
        _prepare(q, k, v, scale, block_q, block_k, interpret,
                 "flash_attention_bwd_lse", causal=causal)

    def fold_g(x):      # (B, T, H) → (B*H, T)
        return jnp.moveaxis(x, -1, 1).reshape(b * h, t)

    d_pad = q3.shape[-1]
    do3 = jnp.moveaxis(do, 2, 1).reshape(b * h, t, d)
    if d < d_pad:
        do3 = jnp.pad(do3, ((0, 0), (0, 0), (0, d_pad - d)))
    # f32 outputs: these are PARTIAL contributions the ring sums across
    # steps — rounding each partial to bf16 before the f32 accumulation
    # would grow error O(ring size) over the einsum engine
    dq, dk, dv = _bwd_pallas_core(
        q3, k3, v3, fold_g(lse).astype(jnp.float32),
        fold_g(delta).astype(jnp.float32), do3, causal, scale,
        *blocks, interpret, 0, h, kv, out_dtype=jnp.float32)

    def unfold(x, heads):
        return jnp.moveaxis(x[..., :d].reshape(b, heads, t, d), 1, 2)

    return unfold(dq, h), unfold(dk, kv), unfold(dv, kv)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """(B, T, H, D) × 3 → (B, T, H, D), differentiable.

    Falls back is the caller's job — check ``supported(T, D)`` first.
    ``interpret=None`` compiles the kernel (Mosaic; an error off-TPU)
    unless ``engine.flash_attention == "force"`` runs it off-TPU, where
    it interprets; tests exercising the kernel on the CPU backend pass
    ``interpret=True``. ``window=W`` restricts each query to
    itself plus W-1 predecessors (sliding-window / Mistral convention;
    requires ``causal``): compute AND the blockwise backward drop the
    dead blocks, so long-T cost scales O(T·W) instead of O(T²).
    """
    window = int(window or 0)
    if window < 0:
        raise ValueError("window must be >= 1 (or None)")
    if window and not causal:
        raise ValueError("sliding-window attention requires causal=True")
    if window >= q.shape[1]:
        window = 0          # a window covering everything is no window
    q3, k3, v3, scale, interpret, b, t, h, kv, d, blocks, bwd_blocks = \
        _prepare(q, k, v, scale, block_q, block_k, interpret,
                 "flash_attention", causal=causal, window=window)
    # trace-time events (run once per trace, not per execution): the
    # counter records that a program containing this kernel was
    # (re)built — recompile churn shows up here first — and the
    # analytic forward cost lands in any active kernel-cost collector
    # (AcceleratedUnit.program_cost: the custom call is opaque to
    # XLA's cost model, so the kernel reports itself)
    from ..telemetry.counters import inc
    from ..telemetry.cost import note_kernel_cost
    inc("veles_flash_attention_traces_total")
    if interpret:
        inc("veles_flash_attention_interpret_traces_total")
    note_kernel_cost(analytic_cost(b, t, h, d, causal, window))
    o = _flash(q3, k3, v3, causal, scale,
               blocks, bwd_blocks, interpret, window, h, kv, d)
    o = o[..., :d].reshape(b, h, t, d)
    return jnp.moveaxis(o, 1, 2)
