"""Flash-attention perf regression bench (real TPU).

VERDICT r1 item 6: prove the Pallas kernel beats the fused-XLA naive
attention at long sequence lengths (where naive materializes the (T, T)
score matrix in HBM). Prints one JSON line per config with achieved
TFLOP/s for both paths and the speedup; exits non-zero if flash loses at
any T >= 2048 (the kernel's reason to exist).

Run: python scripts/bench_attention.py          # on the TPU chip
Recorded results: docs/perf.md.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy  # noqa: E402

from veles_tpu.ops.flash_attention import flash_attention  # noqa: E402
from veles_tpu.parallel.ring_attention import (  # noqa: E402
    attention_reference)


def sync(x):
    numpy.asarray(jax.tree_util.tree_leaves(x)[0].ravel()[0:1])


def time_fn(fn, *args, iters=8):
    fn(*args)          # compile
    sync(fn(*args))
    t0 = time.time()
    out = None
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.time() - t0) / iters


def bench(t, b=1, h=8, d=64, causal=True, dtype=jnp.bfloat16,
          train=False):
    """train=True times value+grad (exercises the blockwise custom-VJP
    backward — the path a training step actually runs)."""
    rng = numpy.random.RandomState(0)
    shape = (b, t, h, d)
    q, k, v = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))

    def wrap(core):
        if not train:
            return jax.jit(lambda q, k, v: core(q, k, v, causal=causal))
        return jax.jit(jax.grad(
            lambda q, k, v: core(q, k, v,
                                 causal=causal).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    t_flash = time_fn(wrap(flash_attention), q, k, v)
    t_naive = time_fn(wrap(attention_reference), q, k, v)
    # attention core FLOPs: 2 matmuls of 2*B*H*T^2*D, halved when causal.
    # Training: the backward re-walks both matmuls twice (3x); the flash
    # custom-VJP additionally RECOMPUTES the forward blockwise (3.5x) —
    # the naive VJP reuses stored scores, so each path gets its own
    # numerator (speedup stays a pure time ratio either way).
    base = 2 * 2 * b * h * t * t * d * (0.5 if causal else 1.0)
    flash_flops = base * (3.5 if train else 1.0)
    naive_flops = base * (3.0 if train else 1.0)
    return {
        "T": t, "B": b, "H": h, "D": d, "causal": causal,
        "mode": "train" if train else "fwd",
        "dtype": str(dtype.__name__ if hasattr(dtype, "__name__")
                     else dtype),
        "flash_ms": round(t_flash * 1e3, 3),
        "naive_ms": round(t_naive * 1e3, 3),
        "flash_tflops": round(flash_flops / t_flash / 1e12, 2),
        "naive_tflops": round(naive_flops / t_naive / 1e12, 2),
        "speedup": round(t_naive / t_flash, 3),
    }


def main():
    backend = jax.default_backend()
    results = []
    # batch scaled so the short-T config is compute-bound, not
    # dispatch-latency-bound (a per-call-chain floor)
    for t, b in ((2048, 16), (8192, 1)):
        for train in (False, True):
            r = bench(t, b=b, train=train)
            r["backend"] = backend
            results.append(r)
            print(json.dumps(r))
    if backend == "tpu":
        from veles_tpu.ops.autotune import resolved_min_t
        min_t = resolved_min_t(64)
        # the regression gate applies where the framework actually
        # CHOOSES flash (T >= min_t); below the crossover the fused XLA
        # reference is the chosen path and flash merely must stay sane
        losers = [r for r in results
                  if r["T"] >= min_t and r["speedup"] < 1.0]
        if losers:
            print("FAIL: flash slower than naive at T=%s"
                  % [r["T"] for r in losers], file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
