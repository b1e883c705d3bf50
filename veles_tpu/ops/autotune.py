"""Per-device kernel block-shape database — measure → commit → reuse.

Reference parity: the reference benchmarks GEMM block sizes per device
and persists them keyed by device name (`veles/backends.py:623-731`
``_find_optimal_bs_vo`` → ``devices/device_infos.json``), so every run
starts tuned. Here XLA owns GEMM tuning, but the build's OWN Pallas
kernel — ``ops/flash_attention.py`` — has ``block_q``/``block_k`` knobs
the compiler does not pick.

One DB: ``veles_tpu/devices/kernel_tuning.json``, committed. The model
path only ever READS it (a memoized dict lookup, safe at trace time),
so one commit compiles the same kernels on every machine and in every
process of an SPMD job. Measuring is an explicit command on a chip —
``scripts/chip_experiments.py --sections attn_2048,...,attn_d256``
— whose ``record()`` rewrites that file for the next commit; a trace
never sweeps.

``fused_fc`` deliberately has no entry here: its only tunable is
epochs-per-dispatch ``h`` (whole minibatches ARE its blocks), measured
by the chip batch's h-sweep, not a per-call shape knob.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional, Tuple

DEFAULT_BLOCKS = (128, 128)
#: bounded candidate census the chip attn sweep tries (the reference
#: swept a fixed census too, veles/backends.py:692), filtered per length
#: to divisors of T. The 1024-wide pairs exist because 512×512 won every
#: r5 sweep length — the knee hadn't been reached
CANDIDATES = ((128, 128), (256, 128), (512, 128), (256, 256),
              (512, 512), (1024, 512), (1024, 1024))
#: the census of the head-size-128 sweep (PR 27): every pair of 256 ...
#: 2048, block_q != block_k included, and the fallback to measure it by
CANDIDATES_WIDE = (DEFAULT_BLOCKS,) + tuple(
    (bq, bk) for bq in (256, 512, 1024, 2048)
    for bk in (256, 512, 1024, 2048))
SHIPPED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "devices", "kernel_tuning.json")

#: per-process memo: key → blocks / crossover / compile verdict
_memo: dict = {}

#: (device_kind, key) pairs whose staleness was already warned about —
#: one log line per entry per process, however many traces look it up
_stale_warned: set = set()

#: (device_kind, key) pairs already named as running on DEFAULT_BLOCKS
_default_warned: set = set()


def _jax_version() -> str:
    try:
        import jax
        return str(jax.__version__)
    except Exception:            # noqa: BLE001 — backend-less tooling
        return "unknown"


def _check_stale(key: str, kind: str, entry: dict) -> None:
    """Provenance check on a DB hit: an entry measured under a
    different jax (or none recorded — the pre-stamp DB format) may
    rank block shapes the current Mosaic lowers differently, so the
    hit is USED but flagged — warned once per (kind, key) and counted
    ``veles_autotune_stale_total`` every lookup, the signal a
    re-sweep (or chip measurement batch) clears."""
    stamped = entry.get("jax")
    current = _jax_version()
    if stamped == current:
        return
    from ..telemetry.counters import inc
    inc("veles_autotune_stale_total")
    if (kind, key) in _stale_warned:
        return
    _stale_warned.add((kind, key))
    import logging
    logging.getLogger("veles_tpu.ops.autotune").warning(
        "kernel_tuning entry %s (%s) was measured under jax %s, "
        "running %s — reusing it, but the ranking may be stale; "
        "re-sweep to refresh", key, kind,
        stamped if stamped is not None else "an unstamped build",
        current)


def _read(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _device_db(device_kind: str) -> dict:
    return _read(SHIPPED).get(device_kind, {})


def current_device_kind() -> str:
    import jax
    try:
        return str(jax.devices()[0].device_kind)
    except Exception:            # noqa: BLE001 — backend init failure
        return "unknown"


def flash_key(t: int, d: int, causal: bool, window: int = 0) -> str:
    mode = "causal" if causal else "full"
    if window:
        mode += "_win"
    return "flash_t%d_d%d_%s" % (t, d, mode)


def lookup(key: str, device_kind: Optional[str] = None) -> Optional[dict]:
    kind = device_kind or current_device_kind()
    hit = _device_db(kind).get(key)
    if hit is not None:
        _check_stale(key, kind, hit)
    return hit


def record(key: str, entry: dict,
           device_kind: Optional[str] = None) -> None:
    """Write ``entry`` under (device_kind, key) into the committed DB —
    explicit chip measurement commands only, so the repo ships what
    was actually measured. The read→merge→write is serialized through
    an flock'd sidecar so concurrent sweeps cannot drop each other's
    entries."""
    import fcntl
    kind = device_kind or current_device_kind()
    # provenance stamp: which toolchain + chip measured this entry —
    # lookup() flags (veles_autotune_stale_total) hits whose jax
    # differs from the running one
    entry = dict(entry, ts=time.strftime("%Y-%m-%d %H:%M:%S"),
                 jax=_jax_version(), device_kind=kind)
    with open(SHIPPED + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        db = _read(SHIPPED)
        db.setdefault(kind, {})[key] = entry
        tmp = SHIPPED + ".tmp"
        with open(tmp, "w") as f:
            json.dump(db, f, indent=1, sort_keys=True)
            f.write("\n")       # POSIX text file: end with newline
        os.replace(tmp, SHIPPED)
    # the next lookup reads the new row
    _memo.pop((kind, key), None)
    _memo.pop((kind, key, "min_t"), None)


#: sentinel "flash never won a swept length on this device" — keeps
#: the fused-XLA reference in charge without disabling the config knob
NEVER = 1 << 30


def min_t_key(d: int) -> str:
    return "flash_min_t_d%d" % d


def flash_min_t(d: int, device_kind: Optional[str] = None,
                default: int = 4096) -> int:
    """The measured flash-vs-fused crossover length for this
    device_kind and head size (seeded by the chip attn sweep — the
    reference persisted measured per-device decisions the same way,
    `veles/backends.py:623-731`); ``default`` for a device_kind or
    head size no sweep has covered: long enough that the fused
    reference's (T, T) scores still fit, a guess and no measurement
    (both of the v5e's swept head sizes cross over at 2048 with tuned
    tiles, devices/kernel_tuning.json). Memoized: this runs per
    attention layer per trace."""
    kind = device_kind or current_device_kind()
    key = min_t_key(d)
    memo_key = (kind, key, "min_t")
    if memo_key in _memo:
        return _memo[memo_key]
    hit = lookup(key, kind)
    val = default if hit is None else int(hit["min_t"])
    _memo[memo_key] = val
    return val


def resolved_min_t(d: int, device_kind: Optional[str] = None) -> int:
    """The ONE resolution of ``engine.flash_attention_min_t`` shared by
    the production gate (``choose_flash``) and the bench gate
    (scripts/bench_attention.py): ``"auto"``/None → the measured
    per-device crossover, an int pins it."""
    from ..config import root
    cfg = root.common.engine.get("flash_attention_min_t", "auto")
    if cfg in (None, "auto"):
        return flash_min_t(d, device_kind)
    return int(cfg or 0)


def _bwd_compiles(t: int, d: int, causal: bool,
                  blocks: Tuple[int, int], dtype=None) -> bool:
    """Whether the custom-VJP backward pair LOWERS at these blocks with
    operands of ``dtype`` (the dtype of the call being decided for;
    bfloat16 when the caller has none): a tile pair that lowers at two
    bytes an element can be a Mosaic VMEM failure at four. One head —
    the tiles, not the head count, set a kernel's working set.
    Compile-only: no timing."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import flash_attention
    x = jax.ShapeDtypeStruct((1, t, 1, d), jnp.dtype(dtype or jnp.bfloat16))
    try:
        jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block_q=blocks[0],
                block_k=blocks[1],
                interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))).lower(x, x, x).compile()
        return True
    except Exception:            # noqa: BLE001 — lowering/VMEM failure
        return False


def _nearest_blocks(t: int, d: int, causal: bool,
                    kind: str) -> Optional[Tuple[int, int]]:
    """Measured winner from the nearest tuned length of the same
    (d, mode) class whose blocks divide this ``t``. Rationale
    (measured on the v5e, devices/kernel_tuning.json): the per-device
    block preference is set by how many grid steps a call makes and
    how well each fills the MXU pipeline, which transfers across
    lengths — the committed d64 winners are 1024x1024 at both 2048 and
    8192, while the 128x128 DEFAULT_BLOCKS lost to fused XLA at 2048
    and ran the d128 training cell at a twenty-fourth of its roofline
    (PERF.md, PR 27). Without this, an untuned T between swept lengths
    would pair the measured ``flash_min_t`` gate with the unmeasured
    default blocks — the exact combination the sweep showed
    regressing.

    By head size, on purpose: a d128 call inherits nothing from the
    d64 rows, although both pad to one 128-lane width. The v5e's
    winners do not agree well enough to share: d64 (bfloat16, MHA) is
    1024x1024 at 2048 and 8192; d128 (float32, GQA 16 on 8) is
    1024x1024 at 4096 but 2048x2048 forward, 1024x1024 backward at
    2048 (PR 27's sweep), and the two were measured at different
    operand dtypes. A head size no sweep has covered resolves to
    DEFAULT_BLOCKS, which ``_note_default_blocks`` counts and logs."""
    pref = "flash_t"
    suf = "_d%d_%s" % (d, "causal" if causal else "full")
    from .flash_attention import supported
    best = None
    for key, entry in _device_db(kind).items():
        if not (key.startswith(pref) and key.endswith(suf)):
            continue
        try:
            t_entry = int(key[len(pref):-len(suf)])
        except ValueError:
            continue
        try:
            bq, bk = int(entry["block_q"]), int(entry["block_k"])
        except (KeyError, TypeError, ValueError):
            continue
        if not supported(t, d, bq, bk):
            continue
        dist = abs(t_entry - t)
        if best is None or dist < best[0]:
            best = (dist, (bq, bk))
    return best[1] if best else None


def _check_inherited(t: int, d: int, causal: bool,
                     blocks: Tuple[int, int], kind: str,
                     dtype=None) -> Tuple[int, int]:
    """First use of a length-INHERITED winner at this ``t``: confirm
    the custom-VJP pair actually LOWERS at the call's operand dtype
    (the sweep only compiled it at the swept lengths and dtype) and
    use DEFAULT_BLOCKS otherwise. TPU-only: off-TPU there is no Mosaic
    lowering to fail (and tests drive inheritance with fake device
    kinds). The verdict is memoized per (kind, t, blocks, dtype) so
    the compile probe costs once, not per trace. A committed entry for
    ``t`` itself is NOT probed here: the chip batch
    (``--sections pallas_compile``) compiles every committed pair."""
    if blocks == DEFAULT_BLOCKS:
        return blocks
    import jax
    if jax.default_backend() != "tpu":
        return blocks
    memo_key = (kind, "inherit_ok", t, d, causal, blocks, str(dtype))
    ok = _memo.get(memo_key)
    if ok is None:
        ok = _memo[memo_key] = _bwd_compiles(t, d, causal, blocks, dtype)
    return blocks if ok else DEFAULT_BLOCKS


def _note_default_blocks(kind: str, key: str) -> None:
    """A call that was sent to the kernel resolved to DEFAULT_BLOCKS on
    a TPU: counted at every trace
    (``veles_flash_default_blocks_traces_total``), named in the log
    once per (kind, key). The 128x128 fallback is a grid of
    (T/128)^2 steps a head, each about half a microsecond before it
    does any work on a v5e; the d128 training cell ran on it unseen for
    five rounds."""
    if not kind.startswith("TPU"):
        return
    from ..telemetry.counters import inc
    inc("veles_flash_default_blocks_traces_total")
    if (kind, key) in _default_warned:
        return
    _default_warned.add((kind, key))
    import logging
    logging.getLogger("veles_tpu.ops.autotune").warning(
        "kernel_tuning.json has no row %s for %s and no tuned length "
        "of its class to inherit from: the flash kernels run on the "
        "%dx%d fallback tiles. Measure it on the chip: give "
        "scripts/chip_experiments.py an attn section for this shape "
        "(sec_attn_d128 is the pattern), run `python "
        "scripts/chip_experiments.py --sections <section>` and commit "
        "veles_tpu/devices/kernel_tuning.json",
        key, kind, *DEFAULT_BLOCKS)


def flash_blocks_fwd_bwd(t: int, d: int, causal: bool = True,
                         window: int = 0,
                         device_kind: Optional[str] = None, dtype=None
                         ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """THE policy lookup ``flash_attention`` resolves its default
    blocks through, (forward's, backward pair's): the committed entry
    for this shape class, else (for a windowed shape) the causal
    entry's ranking, else the nearest tuned length's winner (probed
    once at the call's operand ``dtype``), else DEFAULT_BLOCKS —
    counted and logged on a TPU. The backward pair's differ from the
    forward's only where the committed row carries
    ``bwd_block_q``/``bwd_block_k``. Read-only — the same commit
    resolves the same blocks on every machine. Hits are memoized;
    misses are not, so a ``record()`` later in the process (a sweep
    command) changes the answer."""
    kind = device_kind or current_device_kind()
    key = flash_key(t, d, causal, window)
    memo_key = (kind, key)
    if memo_key in _memo:
        return _memo[memo_key]
    hit = lookup(key, kind)
    if hit is None and window:
        hit = lookup(flash_key(t, d, causal), kind)
    if hit is not None:
        fwd = (int(hit["block_q"]), int(hit["block_k"]))
        bwd = (int(hit.get("bwd_block_q", fwd[0])),
               int(hit.get("bwd_block_k", fwd[1])))
        _memo[memo_key] = (fwd, bwd)
        return fwd, bwd
    inherited = _nearest_blocks(t, d, causal, kind)
    blocks = (DEFAULT_BLOCKS if inherited is None else
              _check_inherited(t, d, causal, inherited, kind, dtype))
    if blocks == DEFAULT_BLOCKS:
        _note_default_blocks(kind, key)
    return blocks, blocks


def flash_blocks(t: int, d: int, causal: bool = True, window: int = 0,
                 device_kind: Optional[str] = None,
                 dtype=None) -> Tuple[int, int]:
    """The forward's blocks of ``flash_blocks_fwd_bwd``."""
    return flash_blocks_fwd_bwd(t, d, causal, window, device_kind,
                                dtype)[0]


def clear_memo() -> None:
    _memo.clear()
    _stale_warned.clear()
    _default_warned.clear()
