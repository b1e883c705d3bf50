"""Per-device kernel block DB (ops/autotune.py) — measure → commit →
reuse, proven on CPU with a fake device_kind and a DB file in tmp (the
reference proved its GEMM equivalent against real GPUs and shipped the
result, veles/backends.py:623-731 + devices/device_infos.json; the
capability under test is the same: an explicit measurement records,
every model-path use is a read-only lookup)."""
import json
import os

import pytest

from veles_tpu.config import root
from veles_tpu.ops import autotune


@pytest.fixture()
def tuned_env(tmp_path, monkeypatch):
    """Redirect the DB into tmp, clear the memo, and pin a fake
    device_kind."""
    monkeypatch.setattr(autotune, "SHIPPED",
                        str(tmp_path / "kernel_tuning.json"))
    monkeypatch.setattr(autotune, "current_device_kind",
                        lambda: "faketpu-v0")
    autotune.clear_memo()
    yield tmp_path
    autotune.clear_memo()


def test_record_persists_and_reuses(tuned_env):
    autotune.record(autotune.flash_key(2048, 64, True),
                    {"block_q": 256, "block_k": 128, "ms": 0.25})
    db = json.load(open(autotune.SHIPPED))
    entry = db["faketpu-v0"]["flash_t2048_d64_causal"]
    assert (entry["block_q"], entry["block_k"]) == (256, 128)
    assert "ts" in entry
    assert autotune.flash_blocks(2048, 64, causal=True) == (256, 128)
    # ... even in a "fresh process" (memo cleared → file read)
    autotune.clear_memo()
    assert autotune.flash_blocks(2048, 64, causal=True) == (256, 128)


def test_miss_returns_defaults_and_never_measures(tuned_env, monkeypatch):
    """A trace never sweeps: a miss on a (pretended) TPU backend
    resolves to the defaults without compiling or timing anything, and
    writes nothing — so one commit compiles the same kernels on every
    machine."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def boom(*a, **k):
        raise AssertionError("a model-path lookup compiled a kernel")

    monkeypatch.setattr(autotune, "_bwd_compiles", boom)
    assert autotune.flash_blocks(4096, 64) == autotune.DEFAULT_BLOCKS
    assert not os.path.exists(autotune.SHIPPED)


def test_reads_nothing_outside_the_checkout(monkeypatch):
    """The model path resolves blocks from the committed DB only: no
    user layer under root.common.dirs.cache / $HOME."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert autotune.SHIPPED == os.path.join(
        repo, "veles_tpu", "devices", "kernel_tuning.json")
    opened = []
    real_read = autotune._read
    monkeypatch.setattr(autotune, "_read",
                        lambda path: opened.append(path) or real_read(path))
    autotune.clear_memo()
    autotune.flash_blocks(2048, 64, device_kind="TPU v5 lite")
    autotune.flash_min_t(64, device_kind="TPU v5 lite")
    autotune.clear_memo()
    assert opened and set(opened) == {autotune.SHIPPED}


def test_nearest_length_fallback(tuned_env):
    """An untuned T inherits the measured winner from the nearest
    tuned length of the same (d, mode) class — the v5e sweep showed
    the block preference transfers across lengths while the 128×128
    default LOSES to fused XLA near the crossover."""
    autotune.record(autotune.flash_key(2048, 64, True),
                    {"block_q": 512, "block_k": 512, "ms": 0.5})
    autotune.record(autotune.flash_key(8192, 64, True),
                    {"block_q": 256, "block_k": 256, "ms": 0.4})
    autotune.clear_memo()
    # 3072 is nearer 2048 → 512×512; 6144 is nearer 8192 → 256×256
    assert autotune.flash_blocks(3072, 64) == (512, 512)
    assert autotune.flash_blocks(6144, 64) == (256, 256)
    # different mode (full) has no entries → defaults
    assert autotune.flash_blocks(3072, 64,
                                 causal=False) == autotune.DEFAULT_BLOCKS


def test_nearest_length_fallback_respects_divisibility(tuned_env):
    # nearest entry's blocks must divide the new T; otherwise defaults
    autotune.record(autotune.flash_key(2048, 64, True),
                    {"block_q": 512, "block_k": 512, "ms": 0.5})
    autotune.clear_memo()
    assert autotune.flash_blocks(1280, 64) == autotune.DEFAULT_BLOCKS


def test_windowed_reuses_causal_entry(tuned_env):
    autotune.record(autotune.flash_key(2048, 64, True),
                    {"block_q": 512, "block_k": 128, "ms": 0.5})
    assert autotune.flash_blocks(2048, 64, causal=True,
                                 window=256) == (512, 128)


def test_flash_attention_resolves_db_blocks(tuned_env, monkeypatch):
    """End to end: flash_attention with default (None) blocks must run
    with the DB's winner — proven by planting blocks that only divide T
    for the planted entry, then checking numerics still match (the
    kernel itself asserts divisibility via `supported`)."""
    import numpy
    import jax.numpy as jnp
    from veles_tpu.ops.flash_attention import flash_attention
    from veles_tpu.parallel.ring_attention import attention_reference

    autotune.record(autotune.flash_key(256, 64, True),
                    {"block_q": 256, "block_k": 128, "ms": 0.1})
    seen = {}
    import veles_tpu.ops.flash_attention as fa
    orig = fa._fwd_pallas

    def spy(q, k, v, causal, scale, block_q, block_k, *a, **kw):
        seen["blocks"] = (block_q, block_k)
        return orig(q, k, v, causal, scale, block_q, block_k, *a, **kw)

    monkeypatch.setattr(fa, "_fwd_pallas", spy)
    rng = numpy.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
               for _ in range(3))
    o = flash_attention(q, k, v, causal=True, interpret=True)
    assert seen["blocks"] == (256, 128)
    ref = attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(o - ref))) < 2e-3


def test_flash_min_t_lookup(tuned_env):
    assert autotune.flash_min_t(64) == 4096      # default until swept
    autotune.record(autotune.min_t_key(64), {"min_t": 2048})
    assert autotune.flash_min_t(64) == 2048


def test_choose_flash_auto_reads_measured_crossover(tuned_env,
                                                    monkeypatch):
    import jax
    from veles_tpu.ops import flash_attention as fa
    monkeypatch.setattr(root.common.engine, "flash_attention", True,
                        raising=False)
    monkeypatch.setattr(root.common.engine, "flash_attention_min_t",
                        "auto", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    autotune.record(autotune.min_t_key(64), {"min_t": 1024})
    assert fa.choose_flash(1024, 64)
    assert not fa.choose_flash(512, 64)
    # an explicit int still pins the gate over the DB
    monkeypatch.setattr(root.common.engine, "flash_attention_min_t",
                        256, raising=False)
    assert fa.choose_flash(512, 64)


def _load_chip_experiments():
    """scripts/ is not a package; the seeding tests import the chip
    batch module by path (one copy of the boilerplate)."""
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "ce", os.path.join(repo, "scripts", "chip_experiments.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    return ce


def test_attn_seed_derives_blocks_and_min_t(tuned_env):
    """The chip attn sweep's seeding: block winners per T (train mode
    preferred) AND the measured flash-vs-fused crossover land in the
    DB so production gates update by measurement."""
    ce = _load_chip_experiments()
    results = [
        # t=2048: tuned flash (2.0) LOSES to fused (1.0) in train mode
        {"t": 2048, "b": 16, "train": True, "variants": {
            "fused_xla": {"ms": 1.0}, "flash_128x128": {"ms": 3.0},
            "flash_256x128": {"ms": 2.0}}},
        # t=8192: tuned flash (7.0) WINS vs fused (10.0)
        {"t": 8192, "b": 1, "train": True, "variants": {
            "fused_xla": {"ms": 10.0}, "flash_512x512": {"ms": 7.0}}},
    ]

    ce._attn_seed(results)
    assert autotune.flash_blocks(2048, 64) == (256, 128)
    assert autotune.flash_blocks(8192, 64) == (512, 512)
    assert autotune.flash_min_t(64) == 8192
    entry = autotune.lookup(autotune.min_t_key(64))
    assert entry["swept"] == {"2048": False, "8192": True}


def test_attn_seed_min_t_respects_losses_above_wins(tuned_env):
    """A win at a SMALL T below a measured loss at a larger T must not
    open the `t >= min_t` gate over the loss: min_t only opens above
    the largest losing length."""
    ce = _load_chip_experiments()
    results = [
        {"t": 2048, "b": 16, "train": True, "variants": {
            "fused_xla": {"ms": 3.0}, "flash_128x128": {"ms": 2.0}}},
        {"t": 8192, "b": 1, "train": True, "variants": {
            "fused_xla": {"ms": 5.0}, "flash_128x128": {"ms": 9.0}}},
    ]

    ce._attn_seed(results)
    assert autotune.flash_min_t(64) == autotune.NEVER


def test_attn_seed_split_sections_merge_crossover(tuned_env):
    """The split attn_2048/attn_8192 chip sections each seed one
    length; the second must REFINE the persisted crossover with the
    first's verdicts, not overwrite them."""
    ce = _load_chip_experiments()
    r2048_loss = [{"t": 2048, "b": 16, "train": True, "variants": {
        "fused_xla": {"ms": 1.0}, "flash_128x128": {"ms": 2.0}}}]
    r8192_win = [{"t": 8192, "b": 1, "train": True, "variants": {
        "fused_xla": {"ms": 10.0}, "flash_512x512": {"ms": 7.0}}}]
    ce._attn_seed(r2048_loss)
    assert autotune.flash_min_t(64) == autotune.NEVER
    autotune.clear_memo()
    ce._attn_seed(r8192_win)
    # merged view: loss@2048 + win@8192 -> gate opens at 8192
    assert autotune.flash_min_t(64) == 8192
    entry = autotune.lookup(autotune.min_t_key(64))
    assert entry["swept"] == {"2048": False, "8192": True}


# -- provenance stamps (PR 20): record() stamps, lookup() flags stale --


def test_record_stamps_jax_and_device_kind(tuned_env):
    """Every persisted entry carries the toolchain + chip that measured
    it — the provenance a later build checks before trusting the
    ranking."""
    import jax
    from veles_tpu.telemetry.counters import counters
    c0 = counters.get("veles_autotune_stale_total")
    autotune.record("flash_t2048_d64_causal",
                    {"block_q": 256, "block_k": 128})
    entry = autotune.lookup("flash_t2048_d64_causal")
    assert entry["jax"] == str(jax.__version__)
    assert entry["device_kind"] == "faketpu-v0"
    # a fresh same-toolchain stamp is NOT stale
    assert counters.get("veles_autotune_stale_total") == c0


def test_stale_entry_counts_every_lookup_warns_once(tuned_env, caplog):
    """An entry measured under another jax (or the pre-stamp DB format)
    is still USED, but veles_autotune_stale_total moves on EVERY lookup
    and the log warns ONCE per (kind, key) — the operator signal that a
    re-sweep is due, without a log storm per trace."""
    import logging
    from veles_tpu.telemetry.counters import counters
    with open(autotune.SHIPPED, "w") as fout:
        json.dump({"faketpu-v0": {
            "flash_t2048_d64_causal":            # pre-stamp format
                {"block_q": 512, "block_k": 128},
            "flash_t8192_d64_causal":            # other-toolchain stamp
                {"block_q": 256, "block_k": 256, "jax": "0.0.1"},
        }}, fout)
    c0 = counters.get("veles_autotune_stale_total")
    with caplog.at_level(logging.WARNING,
                         logger="veles_tpu.ops.autotune"):
        assert autotune.lookup("flash_t2048_d64_causal")["block_q"] \
            == 512                               # hit is still served
        autotune.lookup("flash_t2048_d64_causal")
        autotune.lookup("flash_t8192_d64_causal")
    assert counters.get("veles_autotune_stale_total") == c0 + 3
    stale = [r for r in caplog.records if "stale" in r.getMessage()]
    assert len(stale) == 2                       # once per key
    assert "unstamped" in stale[0].getMessage()
    assert "0.0.1" in stale[1].getMessage()
    # clear_memo() resets the warn-once set (fresh-process semantics)
    autotune.clear_memo()
    with caplog.at_level(logging.WARNING,
                         logger="veles_tpu.ops.autotune"):
        autotune.lookup("flash_t2048_d64_causal")
    assert len([r for r in caplog.records
                if "stale" in r.getMessage()]) == 3
