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


def test_chip_experiments_sections_are_the_kernels_and_generation():
    """What is left of the chip batch: the sections that write or check
    ``devices/kernel_tuning.json`` and the decode rates, each taking the
    device and the chip count and nothing else."""
    import inspect
    ce = _load_chip_experiments()
    assert [name for name, _ in ce.SECTIONS] == [
        "pallas_compile", "attn_2048", "attn_8192", "attn_d128",
        "attn_d256", "generation"]
    for _, fn in ce.SECTIONS:
        assert list(inspect.signature(fn).parameters) == ["dev", "n"]


def test_chip_experiments_refuses_an_unknown_section_before_jax():
    """A section that went (``mnist``) is an error said at once: exit 1
    before jax is imported or a device asked for."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys\n"
         "sys.argv = ['chip_experiments.py', '--sections', 'mnist']\n"
         "ce = runpy.run_path(%r)\n"
         "rc = ce['main']()\n"
         "assert 'jax' not in sys.modules, 'jax was imported'\n"
         "assert 'veles_tpu' not in sys.modules\n"
         "sys.exit(rc)\n"
         % os.path.join(repo, "scripts", "chip_experiments.py")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stderr
    assert "unknown section" in r.stderr and "mnist" in r.stderr


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


# -- head size 128 (PR 27): the committed row, the miss that is counted,
# -- the probe's dtype, the sweep's per-kernel winners --

V5E = "TPU v5 lite"


def test_shipped_d128_row_resolves_without_a_probe(monkeypatch):
    """The 4k training cell's call has a committed row on the v5e: its
    tiles divide 4,096 and are returned as they stand, no compile probe
    (a trace on a committed row pays nothing at set-up)."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def boom(*a, **k):
        raise AssertionError("a committed row was probed")

    monkeypatch.setattr(autotune, "_bwd_compiles", boom)
    autotune.clear_memo()
    row = json.load(open(autotune.SHIPPED))[V5E]["flash_t4096_d128_causal"]
    fwd = (row["block_q"], row["block_k"])
    bwd = (row.get("bwd_block_q", fwd[0]), row.get("bwd_block_k", fwd[1]))
    assert all(4096 % b == 0 and b > 128 for b in fwd + bwd)
    assert row["dtype"] == "float32" and (row["h"], row["kv"]) == (16, 8)
    assert autotune.flash_blocks(4096, 128, True, device_kind=V5E) == fwd
    assert autotune.flash_blocks_fwd_bwd(
        4096, 128, True, device_kind=V5E) == (fwd, bwd)
    assert "min_t" in json.load(open(autotune.SHIPPED))[V5E][
        "flash_min_t_d128"]
    assert autotune.flash_min_t(128, device_kind=V5E) <= 4096
    autotune.clear_memo()


@pytest.mark.parametrize("t", [2048, 8192])
def test_shipped_d64_rows_resolve_as_before(t):
    autotune.clear_memo()
    assert autotune.flash_blocks(t, 64, True,
                                 device_kind=V5E) == (1024, 1024)
    assert autotune.flash_min_t(64, device_kind=V5E) == 2048
    autotune.clear_memo()


def test_default_blocks_on_a_tpu_are_counted_and_logged_once(
        tuned_env, caplog):
    """Resolving to DEFAULT_BLOCKS for a TPU-named device kind moves
    veles_flash_default_blocks_traces_total at every resolution and
    names the missing row in the log once per (kind, key); any other
    kind (the tests' fake, the CPU) does neither."""
    import logging
    from veles_tpu.telemetry.counters import counters
    name = "veles_flash_default_blocks_traces_total"
    c0 = counters.get(name)
    with caplog.at_level(logging.WARNING,
                         logger="veles_tpu.ops.autotune"):
        assert autotune.flash_blocks(4096, 128) == autotune.DEFAULT_BLOCKS
        assert counters.get(name) == c0          # "faketpu-v0"
        assert autotune.flash_blocks(
            4096, 128, device_kind="TPU v0 fake") == autotune.DEFAULT_BLOCKS
        assert counters.get(name) == c0 + 1
        autotune.flash_blocks(4096, 128, device_kind="TPU v0 fake")
        autotune.flash_blocks(8192, 128, device_kind="TPU v0 fake")
    assert counters.get(name) == c0 + 3
    said = [r.getMessage() for r in caplog.records
            if "fallback tiles" in r.getMessage()]
    assert len(said) == 2                        # once per key
    assert "flash_t4096_d128_causal" in said[0]
    assert "chip_experiments.py" in said[0]
    # a row of the class to inherit from is no miss
    autotune.record(autotune.flash_key(2048, 128, True),
                    {"block_q": 512, "block_k": 512},
                    device_kind="TPU v0 fake")
    assert autotune.flash_blocks(4096, 128,
                                 device_kind="TPU v0 fake") == (512, 512)
    assert counters.get(name) == c0 + 3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", None])
def test_bwd_compiles_probes_in_the_dtype_it_is_given(monkeypatch, dtype):
    """The compile probe is built from the operand dtype of the call it
    decides for (bfloat16 only when the caller has none), and
    ``flash_blocks`` hands an inherited pair's probe that dtype."""
    import jax.numpy as jnp
    from veles_tpu.ops import flash_attention as fa
    seen = []

    def spy(q, k, v, **kw):
        seen.append((q.dtype, k.dtype, v.dtype, q.shape,
                     kw["block_q"], kw["block_k"], kw["interpret"]))
        return q

    monkeypatch.setattr(fa, "flash_attention", spy)
    assert autotune._bwd_compiles(512, 128, True, (256, 128), dtype)
    want = jnp.dtype(dtype or "bfloat16")
    assert seen == [(want, want, want, (1, 512, 1, 128), 256, 128, False)]


def test_inherited_pair_is_probed_at_the_calls_dtype(tuned_env,
                                                     monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    autotune.record(autotune.flash_key(2048, 128, True),
                    {"block_q": 1024, "block_k": 512})
    probes = []
    monkeypatch.setattr(
        autotune, "_bwd_compiles",
        lambda t, d, causal, blocks, dtype=None:
        probes.append((t, blocks, str(dtype))) or str(dtype) != "float32")
    # lowers at bfloat16, not at float32: one verdict per dtype, memoized
    assert autotune.flash_blocks(4096, 128, dtype="bfloat16") == (1024, 512)
    assert autotune.flash_blocks(4096, 128,
                                 dtype="float32") == autotune.DEFAULT_BLOCKS
    autotune.flash_blocks(4096, 128, dtype="float32")
    assert probes == [(4096, (1024, 512), "bfloat16"),
                      (4096, (1024, 512), "float32")]


def _kernel_row(t, variants, **shape):
    return dict({"t": t, "b": 1, "train": True, "variants": variants},
                **shape)


def test_attn_seed_picks_each_kernels_winner_and_records_the_shape(
        tuned_env):
    """Where the capture timed each kernel, the row's tiles are the
    forward kernel's winner, the backward pair gets tiles of its own
    only past BWD_SPLIT_GAIN, and the row says at which heads and dtype
    it was measured; the crossover is recorded for that head size."""
    ce = _load_chip_experiments()
    shape = {"h": 16, "kv": 8, "d": 128, "dtype": "float32"}

    def v(ms, fwd, dkv, dq):
        return {"ms": ms, "fwd_ms": fwd, "bwd_dkv_ms": dkv,
                "bwd_dq_ms": dq}

    ce._attn_seed([
        # one pair wins all three kernels: no backward tiles in the row
        _kernel_row(2048, {"fused_xla": {"ms": 5.0},
                           "flash_512x512": v(4.0, 1.0, 1.6, 1.4),
                           "flash_1024x1024": v(3.0, 0.8, 1.2, 1.0),
                           "flash_2048x2048": {"error": "vmem"}}, **shape),
        # the backward is 10 % faster on other tiles than the forward's
        _kernel_row(4096, {"fused_xla": {"error": "out of memory"},
                           "flash_1024x1024": v(9.0, 2.0, 4.0, 3.0),
                           "flash_512x1024": v(9.5, 3.2, 3.3, 3.0),
                           # 2 % faster at the backward: under the gain
                           "flash_1024x512": v(9.1, 2.2, 3.9, 2.96)},
                    **shape)])
    db = json.load(open(autotune.SHIPPED))["faketpu-v0"]
    r2048 = db["flash_t2048_d128_causal"]
    assert (r2048["block_q"], r2048["block_k"]) == (1024, 1024)
    assert "bwd_block_q" not in r2048 and r2048["ms"] == 3.0
    assert (r2048["h"], r2048["kv"], r2048["dtype"]) == (16, 8, "float32")
    assert (r2048["fwd_ms"], r2048["bwd_dkv_ms"]) == (0.8, 1.2)
    r4096 = db["flash_t4096_d128_causal"]
    assert (r4096["block_q"], r4096["block_k"]) == (1024, 1024)
    assert (r4096["bwd_block_q"], r4096["bwd_block_k"]) == (512, 1024)
    assert r4096["ms"] == 2.0 + 3.3 + 3.0
    assert autotune.flash_blocks_fwd_bwd(4096, 128) == ((1024, 1024),
                                                        (512, 1024))
    assert autotune.flash_blocks(2048, 128) == (1024, 1024)
    # flash won at 2048; the fused reference did not run at 4096
    assert db["flash_min_t_d128"]["swept"] == {"2048": True, "4096": True}
    assert autotune.flash_min_t(128) == 2048
    assert "flash_min_t_d64" not in db
