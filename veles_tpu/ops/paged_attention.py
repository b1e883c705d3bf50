"""Paged decode attention: one Pallas TPU call that reads K and V
straight from the serving engine's page pool through each slot's
page-table row, live pages only.

The serving engine keeps a layer's cache as a pool of pages
``(pages, page_size, kv, width)`` and each slot's page-table row names
the pages that hold its positions (serving/pages.py). The gather path
copies a slot's whole row into a dense view and then reads the view a
second time; this kernel reads the pages in place instead. For each
slot it walks only the pages that hold a position in ``[start, end)``,
copies them to VMEM a block at a time with double-buffered async
copies, and keeps an online softmax in float32 (guides:
/opt/skills/guides/pallas_guide.md, boom_attention_tricks.md §8-11;
blueprints: jax's ``paged_attention`` and ``ragged_paged_attention``
TPU kernels, written here for this repo's pool layout). Keys and values
may differ in width (``v_pool``'s last axis), and the scores' scale is
the caller's where the keys come padded beyond their true width.

A page's rows are ``(page_size, kv, width)``: read as ``(page_size * kv,
width)``, one copy brings every KV head of the page, and a block's scores
are one product of all the slot's query heads against all its rows,
``(H, rows)``, with the columns of other heads' rows masked out. A KV
head's group of query heads so shares one read of each page; the masked
columns cost products the MXU has to spare. That holds for a group of
one too (multi-head attention): read head by head instead, a strided
load down the page rows for each KV head, the block's loop is 2,898
bundles against this read's 1,708 at ``olmo-hybrid-7b``'s full layers
(32 KV heads of 128, bfloat16, compiled for a described v5e): each
strided vector is put together from eight loads.

The kernel returns the softmax's unnormalised state, ``(acc, m, l)``;
:func:`paged_decode_attention` merges it with a few more keys (the
rows the step has just made, which are not in the pool yet) and
normalises. Products take the precision the configuration states:
``Precision.DEFAULT`` rounds the operands to bfloat16 on the chip, as
XLA does for the gather path's einsums; interpreted off the chip the
product keeps float32 operands, as XLA's CPU backend does for those
einsums. Scores and the softmax stay in float32 either way.

The call's device operation is named after the kernel (:data:`KERNEL`)
and not after the named scopes around it, so a profile files its time
under that name alone; a caller whose read belongs to a scope of its
own passes that scope's name instead (``name=``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import LANE, NEG_INF, VMEM_CAP, VMEM_DEFAULT

#: positions a block of pages holds: what one double-buffered copy
#: brings and one product scores (8 pages of 16 at the served page size)
BLOCK_POSITIONS = 128
#: the kernel's own name: its device operation's in a profile and in the
#: ledger's breakdown, where the caller names it no otherwise
KERNEL = "veles_paged_attn"


def supported(head_dim: int, page_size: int, kv_heads: int,
              dtype, v_dim: Optional[int] = None) -> bool:
    """Whether the kernel compiles for this pool: float32 or bfloat16
    rows, key and value widths (``v_dim``, None: the key's) of whole lane
    groups, pages of 16 positions, and a power of two of KV heads whose
    page rows fill whole sublane tiles."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    sublanes = 8 * 4 // dtype.itemsize
    v_dim = head_dim if v_dim is None else v_dim
    return (head_dim % LANE == 0 and v_dim % LANE == 0 and page_size == 16
            and kv_heads & (kv_heads - 1) == 0
            and page_size * kv_heads % sublanes == 0)


def analytic_cost(slots: int, heads: int, kv_heads: int, head_dim: int,
                  positions: int, dtype_bytes: int,
                  v_dim: Optional[int] = None):
    """Telemetry cost of one call (the custom call is opaque to XLA's
    cost model; veles_tpu/telemetry/cost.py): the bound at ``positions``
    a slot, the table's length. FLOPs: 2·positions·hd per query head
    for the scores and 2·positions·v_dim for the values. Bytes: every
    position's K and V rows read once, the queries read and the state
    written."""
    from ..telemetry.cost import Cost
    v_dim = head_dim if v_dim is None else v_dim
    flops = 2.0 * slots * heads * positions * (head_dim + v_dim)
    rows = 1.0 * slots * positions * kv_heads * (head_dim + v_dim) \
        * dtype_bytes
    io = slots * heads * (head_dim * dtype_bytes + v_dim * 4 + 2 * LANE * 4)
    rows_per_block = BLOCK_POSITIONS * kv_heads
    peak = 2.0 * rows_per_block * (head_dim + v_dim) * dtype_bytes
    return Cost(flops, rows + io, peak, source="analytic")


def _div(x, n: int):
    """``x // n`` for non-negative ``x``, a shift where ``n`` is a
    power of two (what the vector unit does without a divide)."""
    if n & (n - 1) == 0:
        return jax.lax.shift_right_logical(x, n.bit_length() - 1)
    return x // n


def _rem(x, n: int):
    if n & (n - 1) == 0:
        return jnp.bitwise_and(x, n - 1)
    return x % n


def _rows_of_pages(kv: int, *widths) -> bool:
    """Whether the pools' pages go to the kernel as ``(page_size * kv,
    width)`` rows, or else as they lie, ``(page_size, kv, width)``, their
    rows put together in VMEM. XLA tiles a pool's ``(kv, width)`` by 8
    rows where ``kv`` is a multiple of 8, else by ``kv`` rows; a page of
    fewer heads whose rows span more than one tile of lanes then lies
    otherwise than its rows would, and handing the kernel rows would copy
    the whole pool every call (``mimo-v2.5``'s full layers, 4 KV heads of
    256, compiled for a described v5e)."""
    return kv % 8 == 0 or max(widths) <= LANE


def _kernel(tables_ref, starts_ref, ends_ref, q_ref, k_hbm, v_hbm,
            acc_ref, m_ref, l_ref, k_buf, v_buf, sems, *,
            page_size: int, pages_per_block: int, table_width: int,
            kv: int, root: float, operand_dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slot = pl.program_id(0)
    start, end = starts_ref[slot], ends_ref[slot]
    first = jax.lax.div(start, page_size)
    last = jax.lax.div(jnp.maximum(end - 1, 0), page_size)
    n_blocks = jnp.where(
        end > start, jax.lax.div(last - first, pages_per_block) + 1, 0)
    heads, hd = q_ref.shape
    vd = acc_ref.shape[-1]
    group = heads // kv
    rows = pages_per_block * page_size * kv

    def pages_of(block, buf, start_or_wait):
        """Start (or wait for) the copies of ``block``'s pages into the
        buffer ``buf``, K's and V's; a page past the row's last is never
        read."""
        def page(i, carry):
            at = first + block * pages_per_block + i
            pid = tables_ref[slot * table_width
                             + jnp.minimum(at, table_width - 1)]

            @pl.when(at <= last)
            def _copy():
                for hbm, vmem, sem in ((k_hbm, k_buf, sems.at[0, buf]),
                                       (v_hbm, v_buf, sems.at[1, buf])):
                    dma = pltpu.make_async_copy(hbm.at[pid],
                                                vmem.at[buf, i], sem)
                    getattr(dma, start_or_wait)()
            return carry
        jax.lax.fori_loop(0, pages_per_block, page, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_blocks > 0)
    def _first():
        pages_of(0, 0, "start")

    q = q_ref[...].astype(operand_dtype)
    root = jnp.float32(root)

    def body(block, carry):
        buf = jax.lax.rem(block, 2)

        @pl.when(block + 1 < n_blocks)
        def _next():
            pages_of(block + 1, 1 - buf, "start")

        pages_of(block, buf, "wait")
        base = (first + block * pages_per_block) * page_size
        k = k_buf[buf].reshape(rows, hd).astype(operand_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / root     # (H, rows)
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 0)
        pos = base + _div(col, kv)
        keep = ((_rem(col, kv) == _div(row, group))
                & (pos >= start) & (pos < end))
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(keep, jnp.exp(s - m_cur), 0.0)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + p.sum(axis=1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        # rows outside [start, end) weigh nought; zeroed too, so that
        # what a page past the row's end left in the buffer never
        # meets a zero weight as a NaN
        vpos = base + _div(jax.lax.broadcasted_iota(
            jnp.int32, (rows, vd), 0), kv)
        v = jnp.where((vpos >= start) & (vpos < end),
                      v_buf[buf].reshape(rows, vd).astype(jnp.float32),
                      0.0)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(operand_dtype), v.astype(operand_dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)


def paged_attention(q, k_pool, v_pool, tables, starts, ends, *,
                    scale: Optional[float] = None, operand_dtype=None,
                    name: str = KERNEL, interpret: Optional[bool] = None):
    """Each slot's queries against the pool positions ``[start, end)``
    of its page-table row: ``q`` (S, H, hd); ``k_pool`` (pages,
    page_size, kv, hd), ``v_pool`` (pages, page_size, kv, vd); ``tables``
    (S, P) page ids; ``starts`` and ``ends`` (S,) positions, ``end <= P *
    page_size``. Returns the online softmax's state ``(acc (S, H, vd), m
    (S, H), l (S, H))``, float32: ``acc / l`` is the attention where ``l
    > 0``; a slot with an empty range gives ``l == 0``.

    ``scale``: the scores' factor (None: ``1 / sqrt(hd)``; the caller's
    where q and the keys come padded beyond their true width).
    ``operand_dtype``: the products' operands (None: bfloat16 on the
    chip, the pool's float32 or wider interpreted; see the module's
    docstring). ``name``: the device operation's (see the module's
    docstring). ``interpret``: None runs interpreted off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if operand_dtype is None:
        operand_dtype = jnp.float32 if interpret else jnp.bfloat16
    slots, heads, hd = q.shape
    _, page_size, kv, _ = k_pool.shape
    if heads % kv:
        raise ValueError("%d query heads on %d KV heads" % (heads, kv))
    width = tables.shape[1]
    from ..telemetry.cost import note_kernel_cost
    note_kernel_cost(analytic_cost(slots, heads, kv, hd, width * page_size,
                                   jnp.dtype(k_pool.dtype).itemsize,
                                   v_pool.shape[-1]))
    acc, m, l = _call(
        q, k_pool, v_pool, tables.astype(jnp.int32), starts.astype(jnp.int32),
        ends.astype(jnp.int32),
        per_block=max(1, min(width, BLOCK_POSITIONS // page_size)),
        root=math.sqrt(hd) if scale is None else 1.0 / scale,
        operand_dtype=jnp.dtype(operand_dtype), name=name,
        interpret=bool(interpret))
    return acc, m[..., 0], l[..., 0]


@functools.partial(jax.jit, static_argnames=("per_block", "root",
                                             "operand_dtype", "name",
                                             "interpret"))
def _call(q, k_pool, v_pool, tables, starts, ends, *, per_block: int,
          root: float, operand_dtype, name: str, interpret: bool):
    """The pallas_call, traced once a program for all the layers that
    share its shapes (a step calls it once a layer)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    slots, heads, hd = q.shape
    pages, page_size, kv, _ = k_pool.shape
    vd = v_pool.shape[-1]
    width = tables.shape[1]
    rows = per_block * page_size * kv
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    # the two double buffers, and some six (H, rows) float32 temporaries
    need = 2 * rows * (hd + vd) * itemsize + 6 * heads * rows * 4
    page = ((page_size * kv,) if _rows_of_pages(kv, hd, vd)
            else (page_size, kv))
    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_block=per_block,
        table_width=width, kv=kv, root=root, operand_dtype=operand_dtype)
    query = pl.BlockSpec((None, heads, hd), lambda s, *_: (s, 0, 0))
    state = pl.BlockSpec((None, heads, vd), lambda s, *_: (s, 0, 0))
    lanes = pl.BlockSpec((None, heads, LANE), lambda s, *_: (s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[query,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[state, lanes, lanes],
            scratch_shapes=[
                pltpu.VMEM((2, per_block) + page + (hd,), k_pool.dtype),
                pltpu.VMEM((2, per_block) + page + (vd,), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((slots, heads, vd), jnp.float32),
            jax.ShapeDtypeStruct((slots, heads, LANE), jnp.float32),
            jax.ShapeDtypeStruct((slots, heads, LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(VMEM_DEFAULT, min(VMEM_CAP, 2 * need))),
        interpret=interpret,
        name=name,
    )(tables.reshape(-1), starts, ends, q,
      k_pool.reshape((pages,) + page + (hd,)),
      v_pool.reshape((pages,) + page + (vd,)))


def paged_decode_attention(q, k_pool, v_pool, tables, starts, ends,
                           rows_k, rows_v, rows_ok, *, scale=None,
                           operand_dtype=None, name: str = KERNEL,
                           interpret: Optional[bool] = None):
    """The decode step's attention read: the pool positions ``[start,
    end)`` of each slot's table (:func:`paged_attention`) and ``C``
    further keys a slot, ``rows_k`` (S, C, kv, hd) and ``rows_v`` (S, C,
    kv, vd) where ``rows_ok`` (S, C) — the rows the step has made and
    not written back yet, the row's own among them. ``scale`` and
    ``name`` as :func:`paged_attention`'s. Returns (S, H, vd) float32.
    The further keys are scored and merged here, in float32, with the
    einsums' own precision (the gather path's for the same rows)."""
    slots, heads, hd = q.shape
    kv, vd = rows_k.shape[2], rows_v.shape[-1]
    group = heads // kv
    acc, m, l = paged_attention(q, k_pool, v_pool, tables, starts, ends,
                                scale=scale, operand_dtype=operand_dtype,
                                name=name, interpret=interpret)
    q5 = q.reshape(slots, kv, group, hd).astype(jnp.float32)
    s = jnp.einsum("skgd,sckd->skgc", q5, rows_k.astype(jnp.float32)) / (
        math.sqrt(hd) if scale is None else 1.0 / scale)
    ok = rows_ok[:, None, None, :]
    s = jnp.where(ok, s, NEG_INF)
    m5 = m.reshape(slots, kv, group, 1)
    top = jnp.maximum(m5, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m5 - top)
    p = jnp.where(ok, jnp.exp(s - top), 0.0)
    total = l.reshape(slots, kv, group, 1) * alpha + p.sum(
        axis=-1, keepdims=True)
    o = (acc.reshape(slots, kv, group, vd) * alpha
         + jnp.einsum("skgc,sckd->skgd", p, rows_v.astype(jnp.float32)))
    return (o / total).reshape(slots, heads, vd)
