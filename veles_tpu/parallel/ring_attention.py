"""Ring attention: exact attention over a sequence-sharded axis.

Long-context capability (green-field vs the reference, SURVEY.md §5.7):
sequences sharded over the mesh 'sequence' axis, each device holding a
T/n block of Q, K, V. K/V blocks rotate around the ring via
``lax.ppermute`` over ICI while each device accumulates its Q block's
attention with the online-softmax (running max / denominator) recurrence —
memory O(T/n) per device, compute overlapped with neighbor transfers by
XLA. This is the blockwise ring attention construction (Liu et al.) built
from shard_map + XLA collectives.

Two inner engines for the per-step block attention:

- **flash** (Pallas, ``ops/flash_attention.py``): when the local block
  qualifies (``choose_flash``; causal/full only — no window) each ring
  step runs the VMEM-resident kernel: the diagonal step (own K/V)
  causally masked, every later step unmasked — a block strictly behind
  the queries needs no mask, a wrapped future block is killed by
  weighting its contribution with ``exp(-inf)`` in the lse merge. The
  per-step partials ``(o_i, lse_i)`` fold into the running softmax by
  log-sum-exp.
- **einsum** (fused XLA): the reference engine, and the only one for
  sliding-window rings (the in-block window cut needs element masks at
  traced block offsets, which the kernel does not take).

Differentiation is a hand-written blockwise ring backward under
``jax.custom_vjp`` — NOT autodiff through the forward scan: the
backward recomputes each block's probabilities from the saved global
``lse`` (flash-attention style) while dk/dv accumulators rotate with
their K/V blocks, so residual memory stays O(T/n · D) per device
instead of the O(steps · Tl²) score blocks autodiff-of-scan would save.
"""

from __future__ import annotations

from functools import partial
from typing import Optional


def _ring_perm(n):
    return [(j, (j + 1) % n) for j in range(n)]


def ring_attention(q, k, v, mesh, axis: str = "sequence",
                   causal: bool = False, scale: Optional[float] = None,
                   window: Optional[int] = None,
                   use_flash: Optional[bool] = None):
    """q, k, v: (B, T, H, D) GLOBAL arrays (or already sharded); returns
    (B, T, H, D) attention output, sequence axis sharded over ``axis``.

    ``window=W`` (causal only): each query sees itself plus W-1
    predecessors. Beyond the mask, the ring itself shortens — a device
    only ever needs K/V blocks reaching W-1 positions behind its
    oldest query, so the rotation scan runs ``min(n, ceil((W-1+Tl)/Tl))``
    steps instead of ``n``: fewer ppermutes over ICI and fewer masked
    einsums, the point of windowed attention at ring scale.

    ``use_flash``: None = auto (``ops.flash_attention.choose_flash`` on
    the LOCAL block length, windowless, equal q/kv heads); True forces
    the Pallas engine past the length gate, False forces the einsum
    engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    window = int(window or 0)
    if window < 0:
        raise ValueError("window must be >= 1 (or None)")
    if window and not causal:
        raise ValueError("sliding-window attention requires causal=True")
    n = mesh.shape[axis]
    tl = q.shape[1] // n
    d = q.shape[-1]
    if use_flash is None:
        from ..ops.flash_attention import choose_flash
        use_flash = (not window and q.shape[2] == k.shape[2]
                     and choose_flash(tl, d))
    if use_flash and window:
        raise ValueError("use_flash composes with causal/full rings "
                         "only; window rings use the einsum engine")
    if use_flash:
        if q.shape[2] != k.shape[2]:
            # the flash FORWARD would accept grouped k/v, but the ring
            # backward's einsums assume equal head counts — refuse at
            # the API instead of exploding inside the custom VJP
            raise ValueError(
                "use_flash ring requires equal q/kv head counts "
                "(expand grouped K/V first — nn/attention.expand_kv)")
        from ..ops.flash_attention import supported
        if not supported(tl, d):
            raise ValueError(
                "use_flash: local block T/n=%d D=%d not kernel-"
                "compatible" % (tl, d))
    # carry the batch sharding through: without 'data' in the specs a
    # dp x sp mesh would all-gather the batch and compute it redundantly
    batch_axis = "data" if "data" in mesh.axis_names else None

    local = partial(_ring_local, axis=axis, n=n, causal=causal,
                    scale=float(scale), window=window,
                    use_flash=bool(use_flash))
    spec = P(batch_axis, axis, None, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def _steps_for(n: int, window: int, tl: int) -> int:
    """Rotation count: full ring, or window-shortened (uniform across
    devices — the bound comes from each block's oldest query row)."""
    return n if not window else min(n, (window + tl - 2) // tl + 1)


def _ring_local(q_blk, k_blk, v_blk, *, axis, n, causal, scale,
                window, use_flash):
    """Per-shard ring attention with a custom blockwise backward."""
    import jax

    @jax.custom_vjp
    def ring(q_blk, k_blk, v_blk):
        o, _ = _ring_fwd_impl(q_blk, k_blk, v_blk, axis=axis, n=n,
                              causal=causal, scale=scale, window=window,
                              use_flash=use_flash)
        return o

    def fwd(q_blk, k_blk, v_blk):
        o, lse = _ring_fwd_impl(q_blk, k_blk, v_blk, axis=axis, n=n,
                                causal=causal, scale=scale,
                                window=window, use_flash=use_flash)
        return o, (q_blk, k_blk, v_blk, o, lse)

    def bwd(res, do):
        return _ring_bwd_impl(res, do, axis=axis, n=n, causal=causal,
                              scale=scale, window=window,
                              use_flash=use_flash)

    ring.defvjp(fwd, bwd)
    return ring(q_blk, k_blk, v_blk)


def _ring_fwd_impl(q_blk, k_blk, v_blk, *, axis, n, causal, scale,
                   window, use_flash):
    """Returns (o (B,Tl,H,D), lse (B,H,Tl) — global log-sum-exp of the
    scaled, masked scores per query row: the backward's residual)."""
    import jax
    import jax.numpy as jnp

    my = jax.lax.axis_index(axis)
    b, tl, h, d = q_blk.shape
    q_pos = my * tl + jnp.arange(tl)
    steps = _steps_for(n, window, tl)
    perm = _ring_perm(n)

    if use_flash:
        from ..ops.flash_attention import flash_attention_fwd_lse

        # diagonal step peeled out of the scan: it is the only one
        # whose mask (causal within the block) is static
        o0, lse0 = flash_attention_fwd_lse(q_blk, k_blk, v_blk,
                                           causal=causal, scale=scale)
        o_acc = o0.astype(jnp.float32)
        m = jnp.moveaxis(lse0, -1, 1)              # (B, H, Tl)
        l = jnp.ones_like(m)
        kb = jax.lax.ppermute(k_blk, axis, perm)
        vb = jax.lax.ppermute(v_blk, axis, perm)

        def body(carry, i):
            o_acc, m, l, kb, vb = carry
            src = (my - i) % n
            # a block strictly behind every query needs no mask; a
            # wrapped "future" block (src > my under causal) is dead —
            # its whole contribution is annulled in the merge weight
            oi, lsei = flash_attention_fwd_lse(q_blk, kb, vb,
                                               causal=False, scale=scale)
            mi = jnp.moveaxis(lsei, -1, 1)         # (B, H, Tl)
            if causal:
                live = src < my                    # traced scalar bool
                mi = jnp.where(live, mi, -jnp.inf)
            m_new = jnp.maximum(m, mi)
            # guard the all-dead row: exp(-inf - -inf) would be NaN
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            alpha = jnp.exp(m - m_safe)            # (B, H, Tl)
            beta = jnp.exp(mi - m_safe)
            w_a = alpha.transpose(0, 2, 1)[..., None]
            w_b = beta.transpose(0, 2, 1)[..., None]
            o_new = o_acc * w_a + oi.astype(jnp.float32) * w_b
            l_new = l * alpha + beta
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return (o_new, m_new, l_new, kb, vb), None

        if steps > 1:
            (o_acc, m, l, _, _), _ = jax.lax.scan(
                body, (o_acc, m, l, kb, vb), jnp.arange(1, steps))
        denom = l.transpose(0, 2, 1)[..., None]
        o = (o_acc / jnp.maximum(denom, 1e-30)).astype(q_blk.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return o, lse

    def body(carry, i):
        o, m, l, kb, vb = carry
        src = (my - i) % n          # who produced this K/V block
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kb) * scale
        s = s.astype(jnp.float32)
        if causal:
            k_pos = src * tl + jnp.arange(tl)
            rel = q_pos[:, None] - k_pos[None, :]
            mask = rel >= 0
            if window:
                mask = mask & (rel < window)
            s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q_blk.dtype), vb)
        o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
        # rotate K/V to the next device on the ring
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return (o_new, m_new, l_new, kb, vb), None

    o0 = jnp.zeros((b, tl, h, d), dtype=jnp.float32)
    m0 = jnp.full((b, h, tl), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, tl), dtype=jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, k_blk, v_blk), jnp.arange(steps))
    denom = l.transpose(0, 2, 1)[..., None]
    out = (o / jnp.maximum(denom, 1e-30)).astype(q_blk.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, lse


def _ring_bwd_impl(res, do, *, axis, n, causal, scale, window,
                   use_flash=False):
    """Blockwise ring backward (flash-attention bwd math at ring
    scale): p recomputed per step from the global lse; dq accumulates
    locally; dk/dv accumulators rotate WITH their K/V blocks and are
    fast-forwarded home after the (possibly window-shortened) scan.
    ``use_flash`` runs each step's recompute through the Pallas bwd
    kernel pair (``flash_attention_bwd_lse`` — VMEM-resident, no
    (Tl, Tl) score materialization), same peeled-diagonal structure as
    the forward."""
    import jax
    import jax.numpy as jnp

    q_blk, k_blk, v_blk, o, lse = res     # lse (B, H, Tl) global
    my = jax.lax.axis_index(axis)
    b, tl, h, d = q_blk.shape
    q_pos = my * tl + jnp.arange(tl)
    steps = _steps_for(n, window, tl)
    perm = _ring_perm(n)

    qf = q_blk.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = (dof * o.astype(jnp.float32)).sum(-1)        # (B, Tl, H)
    delta_bh = delta.transpose(0, 2, 1)                  # (B, H, Tl)

    if use_flash:
        from ..ops.flash_attention import flash_attention_bwd_lse
        lse_bth = jnp.moveaxis(lse, 1, -1)               # (B, Tl, H)

        def step_grads(kb, vb, diag, src):
            # diagonal step: static causal mask in the kernel; behind
            # blocks unmasked; a wrapped future block's contribution
            # is zeroed by the liveness weight (like the forward). The
            # kernels emit f32 partials — see flash_attention_bwd_lse.
            dqi, dki, dvi = flash_attention_bwd_lse(
                q_blk, kb, vb, lse_bth, delta, do,
                causal=bool(causal) and diag, scale=scale)
            if causal and not diag:
                live = src < my
                dqi = jnp.where(live, dqi, 0)
                dki = jnp.where(live, dki, 0)
                dvi = jnp.where(live, dvi, 0)
            return dqi, dki, dvi
    else:
        def step_grads(kb, vb, diag, src):
            s = jnp.einsum("bqhd,bkhd->bhqk", qf,
                           kb.astype(jnp.float32)) * scale
            if causal:
                k_pos = src * tl + jnp.arange(tl)
                rel = q_pos[:, None] - k_pos[None, :]
                mask = rel >= 0
                if window:
                    mask = mask & (rel < window)
                s = jnp.where(mask[None, None], s, -jnp.inf)
            # probabilities against the GLOBAL normalizer; fully masked
            # rows/blocks (incl. wrapped future ones) give exp(-inf)=0
            p = jnp.exp(s - lse[..., :, None])
            dvi = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
            dp = jnp.einsum("bqhd,bkhd->bhqk", dof,
                            vb.astype(jnp.float32))
            ds = p * (dp - delta_bh[..., None]) * scale
            dqi = jnp.einsum("bhqk,bkhd->bqhd", ds,
                             kb.astype(jnp.float32))
            dki = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
            return dqi, dki, dvi

    def rotate(*xs):
        return tuple(jax.lax.ppermute(x, axis, perm) for x in xs)

    # step 0 peeled (the flash engine needs its causal mask static);
    # accumulators then rotate WITH their K/V blocks each step
    dq, dkb, dvb = step_grads(k_blk, v_blk, True, my)
    kb, vb, dkb, dvb = rotate(k_blk, v_blk, dkb, dvb)

    def body(carry, i):
        dq, kb, vb, dkb, dvb = carry
        src = (my - i) % n
        dqi, dki, dvi = step_grads(kb, vb, False, src)
        dq, dkb, dvb = dq + dqi, dkb + dki, dvb + dvi
        kb, vb, dkb, dvb = rotate(kb, vb, dkb, dvb)
        return (dq, kb, vb, dkb, dvb), None

    if steps > 1:
        (dq, _, _, dkb, dvb), _ = jax.lax.scan(
            body, (dq, kb, vb, dkb, dvb), jnp.arange(1, steps))
    # after `steps` hops the accumulators sit `steps` devices ahead of
    # home; one shifted ppermute completes the (window-shortened) ring
    # in a single collective (dead far blocks contributed exact zeros)
    home = (n - steps) % n
    if home:
        shift = [(j, (j + home) % n) for j in range(n)]
        dkb = jax.lax.ppermute(dkb, axis, shift)
        dvb = jax.lax.ppermute(dvb, axis, shift)
    return (dq.astype(q_blk.dtype), dkb.astype(k_blk.dtype),
            dvb.astype(v_blk.dtype))


def attention_reference(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Single-device exact attention — the oracle for ring_attention
    and the flash kernel. ``window=W``: each query sees itself plus
    W-1 predecessors (requires causal)."""
    import jax.numpy as jnp
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if window is not None and int(window) < 0:
        raise ValueError("window must be >= 1 (or None)")
    if window and not causal:
        raise ValueError("sliding-window attention requires causal=True")
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        rel = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        mask = rel >= 0
        if window:
            mask = mask & (rel < window)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
