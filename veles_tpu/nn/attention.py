"""Multi-head attention forward unit.

New capability vs the reference (its sequence models were Znicz RNN/LSTM
only, SURVEY.md §5.7); required for long-context parity goals. The unit is
a standard ForwardBase: pure ``apply``, numpy oracle, matched GD unit.
When the attached mesh has a 'sequence' axis larger than 1, the attention
core routes through parallel.ring_attention (exact, sequence-sharded,
K/V rotating over ICI); otherwise a single fused softmax(QK^T)V — the
Pallas flash kernel past the measured crossover, inside a shard_map
whenever the step is partitioned over more than one device.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import numpy

from ..config import root
from ..memory import Array
from .. import prng
from .nn_units import ForwardBase, GradientDescentBase, matches


def expand_kv(np_mod, x, n_heads: int):
    """(B, T, KV, Dh) → (B, T, H, Dh): share each KV head across
    H/KV query-head groups (GQA). Expressed as broadcast+reshape, NOT
    repeat, so XLA lowers a broadcast it can fuse into the consuming
    dot on the reference path. Honest caveat for the flash path: the
    Pallas kernel takes concrete folded operands, so there (and in its
    custom-vjp residuals) the expansion IS materialized — GQA's
    training-memory saving needs a group-aware kernel, which this
    kernel does not have; the *serving* cache saving is real
    (sampling._block_step reads the unrepeated cache)."""
    if np_mod is None:
        import jax.numpy as np_mod
    b, t, kv, hd = x.shape
    g = n_heads // kv
    if g == 1:
        return x
    return np_mod.broadcast_to(
        x[:, :, :, None, :], (b, t, kv, g, hd)).reshape(
        b, t, n_heads, hd)


def device_mesh(device):
    """What an attention unit keeps of its device: the mesh when it
    spans more than one device, else None."""
    mesh = getattr(device, "mesh", None)
    return mesh if mesh is not None and mesh.devices.size > 1 else None


def _flash_sharded(flash, q, k, v, mesh):
    """The flash kernel under a multi-device jit. GSPMD cannot partition
    a Mosaic custom call ("Mosaic kernels cannot be automatically
    partitioned" — first seen on the four-chip host, never on the CPU,
    where interpret mode lowers to plain HLO), so the kernel runs in a
    shard_map: batch split over 'data' and heads over 'tensor' where
    those axes exist and divide — the split GSPMD already gives the
    surrounding matmuls — and replicated over any other axis. Attention
    is independent per (batch, head): no collective is needed."""
    import jax
    from jax.sharding import PartitionSpec as P
    sizes = dict(mesh.shape)

    def axis(name, *dims):
        n = sizes.get(name, 1)
        return name if n > 1 and all(d % n == 0 for d in dims) else None

    spec = P(axis("data", q.shape[0]), None,
             axis("tensor", q.shape[2], k.shape[2]), None)
    return jax.shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention_core(q, k, v, *, causal=False, mesh=None, n_heads=1,
                   window=None):
    """The per-shape attention chooser, shared by MultiHeadAttention and
    TransformerBlock. q: (B, T, H, Dh); k/v may carry FEWER heads (GQA
    — H must divide by their count) → (B, T, H, Dh).
    sequence-mesh → ring/Ulysses; long T on TPU → Pallas flash; else the
    fused XLA reference (crossover: engine.flash_attention_min_t,
    docs/perf.md). ``window``: sliding-window span (causal only). The
    flash path skips dead blocks (O(T·window) compute) and consumes
    GROUPED k/v natively (index-map head remapping — no expanded
    operands or residuals); the other paths expand via broadcast. The
    ring path additionally SHORTENS the rotation scan to the blocks
    the window can reach; Ulysses passes the window to its inner
    attention. ``mesh``: the unit's multi-device mesh (``device_mesh``),
    None on one device or when the caller already runs inside a
    shard_map (the serving engine)."""
    from ..ops import flash_attention as fa
    from ..parallel.ring_attention import (ring_attention,
                                           attention_reference)
    t, hd = q.shape[1], q.shape[-1]
    h = q.shape[2]
    sizes = dict(mesh.shape) if mesh is not None else {}
    if sizes.get("sequence", 1) > 1:
        k, v = expand_kv(None, k, h), expand_kv(None, v, h)
        scheme = root.common.engine.sequence_parallel
        n_seq = sizes["sequence"]
        if scheme == "ulysses" and n_heads % n_seq == 0:
            from ..parallel.ulysses import ulysses_attention
            return ulysses_attention(q, k, v, mesh, causal=causal,
                                     window=window)
        return ring_attention(q, k, v, mesh, causal=causal,
                              window=window)
    if fa.choose_flash(t, hd):
        flash = functools.partial(fa.flash_attention, causal=causal,
                                  window=window)
        if mesh is None or sizes.get("pipeline", 1) > 1:
            # one device — or a pipeline stage, which already runs
            # inside the schedule's own shard_map (parallel/pipeline.py)
            return flash(q, k, v)
        return _flash_sharded(flash, q, k, v, mesh)
    return attention_reference(q, expand_kv(None, k, h),
                               expand_kv(None, v, h), causal=causal,
                               window=window)


def masked_attention(q, k, v, mask, sink=None, scale=None):
    """softmax(q k^T / sqrt(Dk) under ``mask``) v where the key and the
    value widths may differ and the keys carry fewer heads (read through
    a (kv, group) view of the query heads, nothing expanded). ``scale``
    in place of ``1 / sqrt(Dk)`` where q and k come padded with noughts
    beyond their width.
    q (B, Tq, H, Dk), k (B, Tk, KV, Dk), v (B, Tk, KV, Dv), ``mask``
    (B or 1, Tq, Tk) bool -> (B, Tq, H, Dv) in v's type. Scores, softmax
    and the sink in float32, the two products on the operands' own type
    with float32 accumulation. ``sink`` (H,): one learned score a head
    that joins the softmax's denominator and no value's weight
    (``p_ij = exp(s_ij) / (sum_j exp(s_ij) + exp(sink_h))``)."""
    import jax.numpy as jnp
    f32 = jnp.float32
    b, tq, h, dk = q.shape
    kv = k.shape[2]
    g = h // kv
    s = jnp.einsum("bqkgd,btkd->bkgqt", q.reshape(b, tq, kv, g, dk), k,
                   preferred_element_type=f32) * (
                       1.0 / numpy.sqrt(dk) if scale is None else scale)
    s = jnp.where(mask[:, None, None], s, -1e30)
    m = s.max(axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(f32).reshape(1, kv, g, 1, 1)
        m = jnp.maximum(m, sk)
    w = jnp.exp(s - m)
    den = w.sum(axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - m)
    w = (w / den).astype(v.dtype)
    o = jnp.einsum("bkgqt,btkd->bqkgd", w, v, preferred_element_type=f32)
    return o.astype(v.dtype).reshape(b, tq, h, v.shape[-1])


def blocked_causal_attention(q, k, v, *, window=None, sink=None,
                             block=256):
    """Causal :func:`masked_attention` of a whole sequence onto itself,
    by blocks of queries, so that no (H, T, T) score tensor exists: a
    full layer's block of ``block`` queries reads the keys up to its own
    end; a window layer's blocks of ``window`` queries each read their
    own keys and the block's before, all blocks in one batched product.
    A query sees the keys j with i - window < j <= i. Plain XLA, any
    pair of key and value widths."""
    import jax.numpy as jnp
    b, t = q.shape[:2]
    if window and t > 2 * window:
        w = int(window)
        n = -(-t // w)
        pad = n * w - t

        def blocks(x, lead):
            x = jnp.pad(x, ((0, 0), (lead * w, pad), (0, 0), (0, 0)))
            return x.reshape((b, n + lead, w) + x.shape[2:])

        qb = blocks(q, 0)
        kb, vb = blocks(k, 1), blocks(v, 1)
        # block n's keys: the block before it (zeros before the first,
        # masked) and its own
        k2 = jnp.concatenate([kb[:, :-1], kb[:, 1:]], axis=2)
        v2 = jnp.concatenate([vb[:, :-1], vb[:, 1:]], axis=2)
        i = numpy.arange(w)[:, None]
        j = numpy.arange(2 * w)[None, :] - w      # relative to the block
        seen = (j <= i) & (j > i - w)
        first = seen & (j >= 0)
        mask = numpy.broadcast_to(seen, (n, w, 2 * w)).copy()
        mask[0] = first

        def fold(x):
            return x.reshape((b * n,) + x.shape[2:])
        o = masked_attention(fold(qb), fold(k2), fold(v2),
                             jnp.asarray(numpy.tile(mask, (b, 1, 1))),
                             sink)
        return o.reshape((b, n * w) + o.shape[2:])[:, :t]
    outs = []
    for start in range(0, t, block):
        end = min(start + block, t)
        lo = max(0, start - window + 1) if window else 0
        i = numpy.arange(start, end)[:, None]
        j = numpy.arange(lo, end)[None, :]
        mask = j <= i
        if window:
            mask = mask & (j > i - window)
        outs.append(masked_attention(q[:, start:end], k[:, lo:end],
                                     v[:, lo:end], jnp.asarray(mask[None]),
                                     sink))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


class MultiHeadAttention(ForwardBase):
    """(B, T, D) → (B, T, D); params wq/wk/wv/wo each (D, D)."""

    MAPPING = "multi_head_attention"
    PARAMETERIZED = True
    hide_from_registry = False

    def __init__(self, workflow, n_heads=4, causal=False,
                 n_kv_heads=None, **kwargs):
        super().__init__(workflow, **kwargs)
        self.n_heads = int(n_heads)
        #: grouped-query attention (n_kv_heads < n_heads): K/V heads
        #: shared across query-head groups; None = classic MHA
        self.n_kv_heads = int(n_kv_heads) if n_kv_heads else self.n_heads
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads %d not divisible by n_kv_heads %d"
                             % (self.n_heads, self.n_kv_heads))
        self.causal = causal
        self.mesh = None          # set at initialize from the device
        self.weights_stddev = kwargs.get("weights_stddev", None)

    PARAM_NAMES = ("wq", "wk", "wv", "wo")

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def create_params(self, rng: prng.RandomGenerator) -> Dict[str, Array]:
        d = self.input.shape[-1]
        if d % self.n_heads:
            raise ValueError("model dim %d not divisible by %d heads" %
                             (d, self.n_heads))
        stddev = self.weights_stddev or (1.0 / numpy.sqrt(d))
        dtype = root.common.engine.precision_type
        kv_d = (d // self.n_heads) * self.n_kv_heads
        params = {}
        for k, cols in (("wq", d), ("wk", kv_d), ("wv", kv_d),
                        ("wo", d)):
            w = numpy.zeros((d, cols), dtype=dtype)
            prng.get("%s.%s" % (self.name, k)).fill_normal(w, stddev)
            params[k] = Array(w, name="%s.%s" % (self.name, k))
        return params

    def initialize(self, device=None, **kwargs):
        res = super().initialize(device=device, **kwargs)
        if res:
            return res
        self.mesh = device_mesh(device)
        return None

    def apply(self, params, x, *, train=False, rng=None):
        import jax.numpy as jnp
        from ..ops import matmul_precision
        prec = matmul_precision()
        b, t, d = x.shape
        h = self.n_heads
        kv = getattr(self, "n_kv_heads", h)   # absent in old snapshots
        hd = d // h
        q = jnp.dot(x, params["wq"], precision=prec).reshape(b, t, h, hd)
        k = jnp.dot(x, params["wk"],
                    precision=prec).reshape(b, t, kv, hd)
        v = jnp.dot(x, params["wv"],
                    precision=prec).reshape(b, t, kv, hd)
        o = attention_core(q, k, v, causal=self.causal, mesh=self.mesh,
                           n_heads=h)
        o = o.reshape(b, t, d)
        return jnp.dot(o, params["wo"], precision=prec)

    def numpy_apply(self, params, x):
        b, t, d = x.shape
        h = self.n_heads
        kv = getattr(self, "n_kv_heads", h)
        hd = d // h

        q = (x @ params["wq"]).reshape(b, t, h, hd)
        k = (x @ params["wk"]).reshape(b, t, kv, hd)
        v = (x @ params["wv"]).reshape(b, t, kv, hd)
        k = expand_kv(numpy, k, h)
        v = expand_kv(numpy, v, h)
        s = numpy.einsum("bqhd,bkhd->bhqk", q, k) / numpy.sqrt(hd)
        if self.causal:
            mask = numpy.tril(numpy.ones((t, t), bool))
            s = numpy.where(mask[None, None], s, -1e30)
        s = s - s.max(axis=-1, keepdims=True)
        p = numpy.exp(s)
        p /= p.sum(axis=-1, keepdims=True)
        o = numpy.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, d)
        return (o @ params["wo"]).astype(numpy.float32)


@matches(MultiHeadAttention)
class GDMultiHeadAttention(GradientDescentBase):
    MAPPING = "gd_multi_head_attention"
