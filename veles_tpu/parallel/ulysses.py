"""Ulysses-style all-to-all sequence parallelism.

The second of the two standard long-context schemes (new capability vs
the reference — SURVEY.md §5.7 names this the green-field requirement;
the public DeepSpeed-Ulysses recipe is the pattern): instead of rotating
K/V blocks around a ring (parallel/ring_attention.py), ONE all-to-all
re-shards the activations from sequence-sharded to **head-sharded**, the
exact attention runs locally per head group over the full sequence, and
a second all-to-all restores sequence sharding.

Trade-off vs ring: 2 collectives total instead of n-1 permutes (better
for moderate T and enough heads), but requires ``heads % n == 0`` and
holds full-T activations per head group (memory grows with T). Ring
stays memory-flat in T. `nn.MultiHeadAttention` picks via
``root.common.engine.sequence_parallel`` ("ring" | "ulysses"), falling
back to ring when the head count does not divide.
"""

from __future__ import annotations

from typing import Optional


def ulysses_attention(q, k, v, mesh, axis: str = "sequence",
                      causal: bool = False,
                      scale: Optional[float] = None,
                      window: Optional[int] = None):
    """q, k, v: (B, T, H, D) global arrays; returns (B, T, H, D) with the
    sequence axis sharded over ``axis``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from .ring_attention import attention_reference

    n = mesh.shape[axis]
    heads = q.shape[2]
    if heads % n:
        raise ValueError("ulysses needs heads %% devices == 0 "
                         "(%d heads over %d devices)" % (heads, n))
    batch_axis = "data" if "data" in mesh.axis_names else None

    def local(q_blk, k_blk, v_blk):
        # (B, T/n, H, D) → all-to-all → (B, T, H/n, D)
        def spread(x):
            return jax.lax.all_to_all(x, axis, split_axis=2,
                                      concat_axis=1, tiled=True)

        qh, kh, vh = spread(q_blk), spread(k_blk), spread(v_blk)
        # after the re-shard each device holds the FULL sequence for
        # its head group — exactly the single-chip attention problem,
        # so the per-shape chooser applies: the Pallas flash kernel
        # takes the long-T regime Ulysses exists for, the fused XLA
        # reference the short one (same crossover as attention_core)
        t, hd = qh.shape[1], qh.shape[-1]
        from ..ops import flash_attention as fa
        if fa.choose_flash(t, hd):
            o = fa.flash_attention(qh, kh, vh, causal=causal,
                                   scale=scale, window=window)
        else:
            o = attention_reference(qh, kh, vh, causal=causal,
                                    scale=scale, window=window)
        # (B, T, H/n, D) → all-to-all back → (B, T/n, H, D)
        return jax.lax.all_to_all(o, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = P(batch_axis, axis, None, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
