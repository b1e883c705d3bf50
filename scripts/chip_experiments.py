"""Chip measurement batch — ONE process holds the chip (a chip belongs
to one process at a time), sections run in priority order with
incremental saves, so a failure mid-batch still leaves the sections
that finished. The device is the strict ``Device_for("tpu")``: without
a chip nothing runs, and a failed section makes the exit code non-zero.

Sections:
  pallas_compile — per-kernel Mosaic compile/execute/numerics artifact,
             then every row of the committed tuning DB at its own shape
  attn_2048, attn_8192 (``attn``: both) — flash vs fused-XLA, fwd and
             train mode, sweeping Pallas block shapes;
             attn_d128: the 4k training cell's call (head size 128,
             GQA, float32 operands), each kernel timed on the device;
             attn_d256: the 8k training cell's (head size 256, 16 on 2,
             bfloat16 operands); attn_mla (by name only): the
             latent-attention cell's (16 heads, keys of 192 over values
             of 128, bfloat16)
  generation — KV-cached, speculative, beam and batched decode tokens/s
  paged_read (by name only) — ops/paged_attention.py alone at each
             serving cell's widths: ms a call and GB/s of the pages it copies

Run:  python scripts/chip_experiments.py [--sections attn_d128,...]
Results: chiprun_out/chip_experiments.json (atomic incremental writes
per section; git-ignored — what the chip tool brings back). The attn
sections also rewrite the committed veles_tpu/devices/kernel_tuning.json
and copy it beside the results.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "models"))
sys.path.insert(0, os.path.join(REPO, "scripts"))

OUT = os.path.join(REPO, "chiprun_out", "chip_experiments.json")


def save(section, value):
    doc = {}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    if os.path.exists(OUT):
        with open(OUT) as f:
            doc = json.load(f)
    doc[section] = value
    doc["_updated"] = time.strftime("%Y-%m-%d %H:%M:%S")
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, OUT)
    print("== saved %s" % section, flush=True)


def sec_pallas_compile(dev, n):
    """VERDICT r4 item 2, its OWN artifact before any sweep rests on
    the kernels: first Mosaic compile + execution + numerics status of
    the build's Pallas kernels on the real chip — flash forward, the
    custom-VJP backward pair, the external-lse ring backward engine,
    the GQA grouped forward, and the whole-epoch fused-FC SGD kernel.
    Per kernel: compiled? executed? XLA memory analysis? diff vs the
    jnp oracle? Any entry with ok=false is a lowering/VMEM bug that CI
    (CPU interpret mode) could never see. Then every flash block pair
    the committed tuning DB holds for this device_kind — what the
    model path resolves with no compile check of its own — forward and
    backward at its own length."""
    import functools
    import numpy
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import flash_attention as fa
    from veles_tpu.ops import fused_fc as ff
    from veles_tpu.parallel.ring_attention import attention_reference

    interp = False
    out = {"interpret_mode": interp}

    def compile_run(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        info = {"compiled": True}
        try:
            ma = compiled.memory_analysis()
            info["temp_mb"] = round(ma.temp_size_in_bytes / 2 ** 20, 2)
            info["code_mb"] = round(
                ma.generated_code_size_in_bytes / 2 ** 20, 2)
        except Exception:                     # noqa: BLE001
            pass
        res = compiled(*args)
        jax.block_until_ready(res)
        info["executed"] = True
        return res, info

    def rel_diff(got, want):
        got = jax.tree_util.tree_leaves(got)
        want = jax.tree_util.tree_leaves(want)
        worst = 0.0
        for g, w in zip(got, want):
            g = jnp.asarray(g, jnp.float32)
            w = jnp.asarray(w, jnp.float32)
            scale = float(jnp.max(jnp.abs(w))) or 1.0
            worst = max(worst, float(jnp.max(jnp.abs(g - w))) / scale)
        return worst

    def record(name, fn, tol):
        t0 = time.time()
        entry = {}
        try:
            entry.update(fn())
            entry["tol_rel"] = tol
            entry["numerics_ok"] = entry["rel_diff"] <= tol
            entry["ok"] = (bool(entry["numerics_ok"])
                           and entry.get("default_precision_ok", True))
        except Exception as e:                # noqa: BLE001
            import traceback
            traceback.print_exc()
            entry["ok"] = False
            entry["error"] = str(e)[-400:]
        entry["elapsed_s"] = round(time.time() - t0, 1)
        out[name] = entry
        print("  pallas_compile %s: %s" % (name, entry), flush=True)

    rng = numpy.random.RandomState(0)
    b, t, h, d = 2, 1024, 4, 64
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)
               for _ in range(3))
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))

    def flash_fwd():
        o, info = compile_run(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128,
                interpret=interp), q, k, v)
        info["rel_diff"] = rel_diff(
            o, attention_reference(qf, kf, vf, causal=True))
        return info

    ref_grads = {}          # computed once, shared by both bwd checks

    def _ref_grads():
        if not ref_grads:
            ref_grads["g"] = jax.grad(
                lambda q, k, v: attention_reference(
                    q, k, v, causal=True).sum(),
                argnums=(0, 1, 2))(qf, kf, vf)
        return ref_grads["g"]

    def flash_bwd_pair():
        from veles_tpu.config import root as vt_root
        prev = vt_root.common.engine.get("flash_attention_pallas_bwd",
                                         True)
        vt_root.common.engine.flash_attention_pallas_bwd = True
        try:
            grads, info = compile_run(jax.grad(
                lambda q, k, v: fa.flash_attention(
                    q, k, v, causal=True, block_q=128, block_k=128,
                    interpret=interp).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)), q, k, v)
        finally:
            vt_root.common.engine.flash_attention_pallas_bwd = prev
        info["rel_diff"] = rel_diff(grads, _ref_grads())
        return info

    def flash_bwd_lse():
        # the ring engine: backward against a CALLER-supplied global
        # softmax normalizer (parallel/ring_attention.py's per-step op)
        o, lse = fa.flash_attention_fwd_lse(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=interp)
        do = jnp.ones_like(o)
        delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
        grads, info = compile_run(
            lambda q, k, v, lse, delta, do: fa.flash_attention_bwd_lse(
                q, k, v, lse, delta, do, causal=True, block_q=128,
                block_k=128, interpret=interp),
            q, k, v, lse, delta, do)
        info["rel_diff"] = rel_diff(grads, _ref_grads())
        return info

    def flash_gqa():
        kv = 2
        kg = jnp.asarray(numpy.random.RandomState(1).randn(b, t, kv, d),
                         jnp.bfloat16)
        vg = jnp.asarray(numpy.random.RandomState(2).randn(b, t, kv, d),
                         jnp.bfloat16)
        o, info = compile_run(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128,
                interpret=interp), q, kg, vg)
        kx = jnp.repeat(kg, h // kv, axis=2).astype(jnp.float32)
        vx = jnp.repeat(vg, h // kv, axis=2).astype(jnp.float32)
        info["rel_diff"] = rel_diff(
            o, attention_reference(qf, kx, vx, causal=True))
        return info

    def fused_fc():
        d0, hid, nout, ksteps, mb = 784, 128, 10, 12, 100
        r = numpy.random.RandomState(3)
        ws = [jnp.asarray(r.randn(d0, hid) * 0.05, jnp.float32),
              jnp.asarray(r.randn(hid, nout) * 0.05, jnp.float32)]
        bs = [jnp.zeros((hid,), jnp.float32),
              jnp.zeros((nout,), jnp.float32)]
        vws = [jnp.zeros_like(w) for w in ws]
        vbs = [jnp.zeros_like(x) for x in bs]
        data = jnp.asarray(r.randn(ksteps * mb, d0), jnp.float32)
        labels = jnp.asarray(r.randint(0, nout, ksteps * mb), jnp.int32)
        plan = jnp.arange(ksteps * mb, dtype=jnp.int32).reshape(
            ksteps, mb)
        kw = dict(act_a=1.7159, act_b=0.6666, momentum=0.9, wd=0.0005,
                  lr_bias_ratio=2.0)
        # gate at matched 'highest' dot precision on both sides: an
        # algorithm-identity check with bf16 MXU rounding excluded.
        # (Measured 2026-08-02: at default precision the kernel tracks
        # the default oracle at ~2.6e-3 over the 12-step epoch — pure
        # bf16 multiply noise, docs/fused_fc_precision_probe.json.)
        run = functools.partial(ff.fused_fc_sgd_epoch, interpret=interp,
                                precision="highest", **kw)
        got, info = compile_run(run, ws, bs, vws, vbs, data, labels,
                                plan, 0.1)
        oracle = jax.jit(functools.partial(ff.fused_fc_oracle, **kw))
        with jax.default_matmul_precision("highest"):
            want = oracle(ws, bs, vws, vbs, data, labels, plan, 0.1)
        info["rel_diff"] = rel_diff(got, want)
        # the production-default path (what training actually runs):
        # vs a default oracle both sides do single-pass bf16 MXU
        # multiplies, so the expected drift is bf16 rounding (~2.6e-3
        # measured over this 12-step epoch) — gated LOOSELY so a gross
        # precision-plumbing regression still fails the section
        got_d = ff.fused_fc_sgd_epoch(ws, bs, vws, vbs, data, labels,
                                      plan, 0.1, interpret=interp, **kw)
        want_d = oracle(ws, bs, vws, vbs, data, labels, plan, 0.1)
        dd = rel_diff(got_d, want_d)
        info["rel_diff_default_precision"] = dd
        info["default_precision_ok"] = dd <= 0.05
        return info

    record("flash_fwd", flash_fwd, tol=0.02)
    record("flash_bwd_pair", flash_bwd_pair, tol=0.05)
    record("flash_bwd_lse", flash_bwd_lse, tol=0.05)
    record("flash_gqa_fwd", flash_gqa, tol=0.02)
    record("fused_fc_scan", fused_fc, tol=1e-3)

    def db_entry(t_db, d_db, entry):
        # b=1, h=2: the grid repeats per head/batch, so the per-block
        # compile verdict transfers; small enough that the f32
        # reference's (T, T) scores fit at T=8192. Operand dtype and
        # grouping as the row says it was measured; blocks resolved as
        # the model path resolves them (None), so a row's backward
        # tiles are the ones compiled
        r = numpy.random.RandomState(4)
        dtype = entry.get("dtype", "bfloat16")
        kv_db = 1 if entry.get("kv", 0) < entry.get("h", 0) else 2
        q2, k2, v2 = (jnp.asarray(r.randn(1, t_db, heads, width), dtype)
                      for heads, width in ((2, d_db), (kv_db, d_db),
                                           (kv_db, entry.get("dv", d_db))))
        qf2, kf2, vf2 = (jnp.repeat(x, 2 // x.shape[2], axis=2).astype(
            jnp.float32) for x in (q2, k2, v2))

        def loss(attn):
            return lambda q, k, v: attn(q, k, v).astype(
                jnp.float32).sum()

        def flash(q, k, v):
            return fa.flash_attention(q, k, v, causal=True,
                                      interpret=interp)

        def ref(q, k, v):
            return attention_reference(q, k, v, causal=True)

        o, info = compile_run(flash, q2, k2, v2)
        grads, binfo = compile_run(
            jax.grad(loss(flash), argnums=(0, 1, 2)), q2, k2, v2)
        info["bwd"] = binfo
        gq, gk, gv = jax.grad(loss(ref), argnums=(0, 1, 2))(
            qf2, kf2, vf2)
        if kv_db == 1:          # the group's two query heads share a row
            gk, gv = (g.sum(axis=2, keepdims=True) for g in (gk, gv))
        info["rel_diff"] = max(rel_diff(o, ref(qf2, kf2, vf2)),
                               rel_diff(grads, (gq, gk, gv)))
        return info

    import re
    from veles_tpu.ops import autotune
    kind = autotune.current_device_kind()
    for key, entry in sorted(autotune._device_db(kind).items()):
        m = re.fullmatch(r"flash_t(\d+)_d(\d+)(?:_v\d+)?_causal", key)
        if m and "block_q" in entry:
            name = "db_%s_%dx%d" % (key, entry["block_q"],
                                    entry["block_k"])
            record(name, functools.partial(
                db_entry, int(m.group(1)), int(m.group(2)), entry),
                tol=0.05)
            out[name]["jax_stamp"] = entry.get("jax")
    out["all_ok"] = all(v.get("ok") for k, v in out.items()
                        if isinstance(v, dict))
    return out


def sec_attn(dev, n, pairs=None, **shape):
    """The explicit block sweep: measure, then rewrite the committed
    tuning DB with the winners (stamped with this jax), and copy it
    beside the results so it survives the chip tool's machine.
    ``shape``: ``_attn_measure``'s h, kv, d, dtype, candidates, modes,
    extras."""
    import shutil
    from veles_tpu.ops import autotune
    results = _attn_measure(dev, n, pairs=pairs, **shape)
    _attn_seed(results)
    shutil.copy(autotune.SHIPPED, os.path.dirname(OUT))
    return results


def sec_attn_2048(dev, n):
    """Half the attn sweep per section (~20 compiles each, not ~40):
    a mid-section failure costs one length's measurements,
    not both — and the T=2048 crossover regime (the r3 0.62x result)
    lands first. Each half seeds its own DB entries, and
    _attn_seed's per-T crossover floor only ever OPENS the gate above
    a measured loss, so half-seeded state is safe."""
    return sec_attn(dev, n, pairs=((2048, 16),))


def sec_attn_8192(dev, n):
    return sec_attn(dev, n, pairs=((8192, 1),))


def sec_attn_d128(dev, n):
    """The call of the 4k training cell (chipbench internlm2_train4k:
    one sequence, 16 query heads on 8 KV heads of 128, float32 operands
    as nn/transformer.py hands them over), forward plus the custom-VJP
    backward, at the cell's length and at half of it for the crossover.
    Train mode alone: what the row records is the train-mode winner."""
    from veles_tpu.ops.autotune import CANDIDATES_WIDE
    return sec_attn(dev, n, pairs=((2048, 1), (4096, 1)),
                    h=16, kv=8, d=128, dtype="float32",
                    candidates=CANDIDATES_WIDE, modes=(True,),
                    extras=False)


def sec_attn_d256(dev, n):
    """The call of the 8k training cell (chipbench qwen3next_train8k: one
    sequence, 16 query heads on 2 KV heads of 256, bfloat16 operands as
    nn/hybrid.py hands them over), forward plus the custom-VJP backward,
    at the cell's length and at half of it for the crossover. Train mode
    alone, as ``sec_attn_d128``."""
    from veles_tpu.ops.autotune import CANDIDATES_WIDE
    return sec_attn(dev, n, pairs=((4096, 1), (8192, 1)),
                    h=16, kv=2, d=256, dtype="bfloat16",
                    candidates=CANDIDATES_WIDE, modes=(True,),
                    extras=False)


def sec_attn_mla(dev, n):
    """The call of the latent-attention training cell (chipbench
    moonlight_train8k: one sequence, 16 heads with keys of 192 and values
    of 128, bfloat16 operands as nn/hybrid.py hands them over), forward
    plus the custom-VJP backward at the cell's length. Train mode alone,
    as ``sec_attn_d128``."""
    from veles_tpu.ops.autotune import CANDIDATES_WIDE
    return sec_attn(dev, n, pairs=((8192, 1),), h=16, kv=16, d=192,
                    dv=128, dtype="bfloat16", candidates=CANDIDATES_WIDE,
                    modes=(True,), extras=False, fused=False)


ATTN_SWEEP_H, ATTN_SWEEP_D = 8, 64   # the sweep's shape unless told

#: the three Pallas calls of one flash forward + backward, by the name
#: a profiler capture gives the device operation
FLASH_KERNELS = ("veles_flash_fwd", "veles_flash_bwd_dkv",
                 "veles_flash_bwd_dq")


def _kernel_ms(fn, args, iters=4):
    """Device milliseconds a call of (compiled, warm) ``fn`` spends in
    each of FLASH_KERNELS, from a profiler capture of ``iters`` calls:
    {"fwd_ms", "bwd_dkv_ms", "bwd_dq_ms"}, a kernel the call does not
    run left out; {} where the capture shows none."""
    import shutil
    import tempfile
    import jax
    import bench_attention as ba
    from veles_tpu.telemetry import devtime
    logdir = tempfile.mkdtemp(prefix="veles_attn_sweep_")
    try:
        with jax.profiler.trace(logdir):
            out = None
            for _ in range(iters):
                out = fn(*args)
            ba.sync(out)
        capture = devtime.find_capture(logdir)
        scopes = (devtime.summarize_capture(
            devtime.load_capture(capture))["scopes"] if capture else {})
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    out = {}
    for (_, scope), seconds in scopes.items():
        for kernel in FLASH_KERNELS:
            if kernel in scope:     # jax may wrap it: jvp_veles_flash_fwd_
                key = kernel[len("veles_flash_"):] + "_ms"
                out[key] = out.get(key, 0.0) + 1e3 * seconds / iters
    return {k: round(ms, 3) for k, ms in out.items()}


def _attn_measure(dev, n, pairs=None, h=ATTN_SWEEP_H, kv=None,
                  d=ATTN_SWEEP_D, dtype="bfloat16", candidates=None,
                  modes=(False, True), extras=True, dv=None, fused=True):
    """Fused XLA against the flash kernels at each candidate tile pair,
    at (T, B) ``pairs`` of ``h`` query heads on ``kv`` KV heads (None:
    MHA) of size ``d`` (values of ``dv``, where given), operands of
    ``dtype``; ``fused``: whether the fused reference runs beside them.
    A variant's ``ms`` is
    the host's clock over the whole call; ``fwd_ms``, ``bwd_dkv_ms``,
    ``bwd_dq_ms`` are each kernel's device time in a capture. Every row
    carries the shape it was measured at, for ``_attn_seed``.
    ``extras``: the A/Bs beside the sweep (grouped against expanded
    K/V, windows, the jnp backward), which are of the standard shape."""
    import jax.numpy as jnp
    import bench_attention as ba
    from veles_tpu.config import root as vt_root
    from veles_tpu.ops.autotune import CANDIDATES
    from veles_tpu.ops.flash_attention import flash_attention
    from veles_tpu.nn.attention import expand_kv
    from veles_tpu.parallel.ring_attention import attention_reference
    import jax
    kv = kv or h
    results = []

    def fused_core(q, k, v, causal=True):
        return attention_reference(q, expand_kv(None, k, h),
                                   expand_kv(None, v, h), causal=causal)

    # (T, B) pairs from docs/perf.md so old and new numbers compare
    for t, b in (pairs or ((2048, 16), (8192, 1))):
        import numpy
        rng = numpy.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(b, t, heads, width), dtype)
                   for heads, width in ((h, d), (kv, d), (kv, dv or d)))
        flops_fwd = 2.0 * b * h * t * t * (d + (dv or d)) / 2  # causal
        for train in modes:
            flops = flops_fwd * (3.5 if train else 1.0)

            def wrap(core):
                if not train:
                    return jax.jit(
                        lambda q, k, v: core(q, k, v, causal=True))
                return jax.jit(jax.grad(
                    lambda q, k, v: core(
                        q, k, v,
                        causal=True).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2)))

            row = {"t": t, "b": b, "train": train, "h": h, "kv": kv,
                   "d": d, "dtype": str(dtype), "variants": {}}
            if dv:
                row["dv"] = dv
            try:
                if not fused:
                    raise RuntimeError("not run: no crossover is recorded "
                                       "for keys and values apart")
                dt = ba.time_fn(wrap(fused_core), q, k, v)
                row["variants"]["fused_xla"] = {
                    "ms": round(dt * 1e3, 2),
                    "tflops": round(flops / dt / 1e12, 2)}
            except Exception as e:            # noqa: BLE001 — the (T, T)
                # scores may not fit beside the process's other arrays
                row["variants"]["fused_xla"] = {"error": str(e)[-300:]}
            print("  attn t=%d train=%s fused_xla: %s"
                  % (t, train, row["variants"]["fused_xla"]), flush=True)
            for bq, bk in (candidates or CANDIDATES):
                if t % bq or t % bk:
                    continue
                name = "flash_%dx%d" % (bq, bk)

                def core(q, k, v, causal=True, bq=bq, bk=bk):
                    return flash_attention(q, k, v, causal=causal,
                                           block_q=bq, block_k=bk)
                try:
                    fn = wrap(core)
                    dt = ba.time_fn(fn, q, k, v)
                    row["variants"][name] = dict(
                        _kernel_ms(fn, (q, k, v)),
                        ms=round(dt * 1e3, 2),
                        tflops=round(flops / dt / 1e12, 2))
                except Exception as e:        # noqa: BLE001
                    row["variants"][name] = {"error": str(e)[-300:]}
                print("  attn t=%d train=%s %s: %s"
                      % (t, train, name, row["variants"][name]),
                      flush=True)
            if not train and extras:
                # GQA A/B: grouped k/v (index-map remapping) vs the
                # same attention on pre-expanded K/V — the grouped
                # kernel reads each kv block once per group instead of
                # re-reading an expanded copy
                kg = jnp.asarray(numpy.random.RandomState(1).randn(
                    b, t, 2, d), dtype)
                vg = jnp.asarray(numpy.random.RandomState(2).randn(
                    b, t, 2, d), dtype)
                kx = jnp.repeat(kg, h // 2, axis=2)
                vx = jnp.repeat(vg, h // 2, axis=2)
                for name, args in (("flash_gqa_kv2", (q, kg, vg)),
                                   ("flash_gqa_expanded", (q, kx, vx))):
                    try:
                        fn = jax.jit(lambda q, k, v: flash_attention(
                            q, k, v, causal=True))
                        dt = ba.time_fn(fn, *args)
                        row["variants"][name] = {
                            "ms": round(dt * 1e3, 2),
                            "tflops": round(flops / dt / 1e12, 2)}
                    except Exception as e:    # noqa: BLE001
                        row["variants"][name] = {"error": str(e)[-300:]}
                    print("  attn t=%d %s: %s"
                          % (t, name, row["variants"][name]),
                          flush=True)
                # sliding-window flash: dead-block skipping should make
                # cost ~O(T*W) — the long-T payoff of the window feature
                for w in (t // 4, t // 8):
                    def wcore(q, k, v, causal=True, w=w):
                        return flash_attention(q, k, v, causal=True,
                                               window=w)
                    name = "flash_win%d" % w
                    try:
                        dt = ba.time_fn(wrap(wcore), q, k, v)
                        row["variants"][name] = {
                            "ms": round(dt * 1e3, 2),
                            "tflops_full_equiv": round(
                                flops / dt / 1e12, 2)}
                    except Exception as e:    # noqa: BLE001
                        row["variants"][name] = {"error": str(e)[-300:]}
                    print("  attn t=%d %s: %s"
                          % (t, name, row["variants"][name]),
                          flush=True)
            if train and extras:
                # pallas-bwd (default) vs jnp blockwise bwd, same
                # 128x128 forward — the new backward's own A/B
                from veles_tpu.config import root as vt_root
                prev_bwd = vt_root.common.engine.get(
                    "flash_attention_pallas_bwd", True)
                vt_root.common.engine.flash_attention_pallas_bwd = False
                try:
                    jax.clear_caches()

                    def core128(q, k, v, causal=True):
                        # explicit blocks: the autotune default must
                        # not retarget this A/B mid-sweep
                        return flash_attention(q, k, v, causal=causal,
                                               block_q=128, block_k=128)
                    dt = ba.time_fn(wrap(core128), q, k, v)
                    row["variants"]["flash_128x128_jnpbwd"] = {
                        "ms": round(dt * 1e3, 2),
                        "tflops": round(flops / dt / 1e12, 2)}
                except Exception as e:        # noqa: BLE001
                    row["variants"]["flash_128x128_jnpbwd"] = {
                        "error": str(e)[-300:]}
                finally:
                    # restore what the OPERATOR configured, not a
                    # hard-coded default — later sections must measure
                    # the configured setup
                    vt_root.common.engine.flash_attention_pallas_bwd = \
                        prev_bwd
                    jax.clear_caches()
                print("  attn t=%d train=True flash_128x128_jnpbwd: %s"
                      % (t, row["variants"]["flash_128x128_jnpbwd"]),
                      flush=True)
            results.append(row)
    return results


#: the backward pair gets tiles of its own in the row only if they beat
#: the forward's winner at the backward by more than this share
BWD_SPLIT_GAIN = 0.03


def _attn_seed(results):
    # Seed the per-device block DB (ops/autotune.py — the build's port
    # of the reference's measured-per-device GEMM block sizes,
    # veles/backends.py:623-731) with the sweep winners, so production
    # flash calls stop using the hard-coded 128x128 default on this
    # device_kind. Train-mode winners take precedence (training is the
    # dominant consumer). record() rewrites the committed in-repo DB.
    # Where the capture gave each kernel's device time, the forward's
    # tiles are the forward kernel's winner and the row's ms the three
    # kernels' sum; else both come from the host's clock over the call.
    import re
    from veles_tpu.ops import autotune
    if not results:
        return
    shape = {k: results[0][k] for k in ("h", "kv", "dtype", "dv")
             if k in results[0]}
    d_swept = results[0].get("d", ATTN_SWEEP_D)
    dv_swept = results[0].get("dv")
    crossover = {}          # t -> flash beat fused (train-preferred)
    for t in sorted({r["t"] for r in results}):
        cands = {}             # train_mode -> {(bq, bk): variant}
        for r in results:
            if r["t"] != t:
                continue
            for name, res in r["variants"].items():
                m = re.fullmatch(r"flash_(\d+)x(\d+)", name)
                if m and "ms" in res:
                    cands.setdefault(r["train"], {})[
                        (int(m.group(1)), int(m.group(2)))] = res
        train = True in cands
        pool = cands.get(True) or cands.get(False)
        if not pool:
            continue
        entry = dict(shape, mode="train_sweep" if train else "fwd_sweep")
        kernels = ("fwd_ms", "bwd_dkv_ms", "bwd_dq_ms")
        if train and all(all(k in res for k in kernels)
                         for res in pool.values()):
            def bwd_ms(blocks):
                return pool[blocks]["bwd_dkv_ms"] + pool[blocks]["bwd_dq_ms"]
            fwd = min(pool, key=lambda blocks: pool[blocks]["fwd_ms"])
            bwd = min(pool, key=bwd_ms)
            if bwd_ms(bwd) > (1.0 - BWD_SPLIT_GAIN) * bwd_ms(fwd):
                bwd = fwd
            else:
                entry.update(bwd_block_q=bwd[0], bwd_block_k=bwd[1])
            entry.update(fwd_ms=pool[fwd]["fwd_ms"],
                         bwd_dkv_ms=pool[bwd]["bwd_dkv_ms"],
                         bwd_dq_ms=pool[bwd]["bwd_dq_ms"])
            # the host's clock over the whole call, where one pair ran
            # it all: what the fused reference is compared by
            ms = (pool[fwd]["ms"] if bwd == fwd else
                  round(pool[fwd]["fwd_ms"] + bwd_ms(bwd), 2))
        else:
            fwd = min(pool, key=lambda blocks: pool[blocks]["ms"])
            ms = pool[fwd]["ms"]
        bq, bk = fwd
        # flash-vs-fused verdict at this T, same mode as the pick
        mode_rows = [r for r in results if r["t"] == t
                     and r["train"] == train]
        fused_rows = [r["variants"].get("fused_xla", {})
                      for r in mode_rows]
        fused = min((f["ms"] for f in fused_rows if "ms" in f),
                    default=None)
        if fused is not None:
            crossover[t] = ms < fused
        elif any("error" in f for f in fused_rows):
            crossover[t] = True     # the fused reference did not run
        autotune.record(
            autotune.flash_key(t, d_swept, True, dv=dv_swept),
            dict(entry, block_q=bq, block_k=bk, ms=ms))
        print("  autotune seeded t=%d d=%d -> %dx%d (%.2f ms)%s"
              % (t, d_swept, bq, bk, ms,
                 " backward %dx%d" % (entry["bwd_block_q"],
                                      entry["bwd_block_k"])
                 if "bwd_block_q" in entry else ""), flush=True)
    # persist the MEASURED flash-vs-fused crossover: the smallest
    # swept T where tuned flash beat the fused-XLA reference AND no
    # larger swept T measured a loss — 't >= min_t' routes every
    # longer length to flash, so a win below a measured loss must
    # not open the gate over that loss (the r3 0.62x-at-2048 regime
    # gets re-gated by measurement, not by a hand-set constant).
    # choose_flash's "auto" mode reads this. MERGE with any
    # previously recorded verdicts first: the split attn_2048/
    # attn_8192 sections each see one length, and a later section
    # must refine the entry, not overwrite the other's data.
    merged = dict(crossover)
    prev = autotune.lookup(autotune.min_t_key(d_swept))
    for tk, won in (prev or {}).get("swept", {}).items():
        merged.setdefault(int(tk), bool(won))
    losses = [t for t, won in merged.items() if not won]
    floor = max(losses) if losses else -1
    wins = sorted(t for t, won in merged.items()
                  if won and t > floor)
    if crossover and dv_swept is None:
        min_t = wins[0] if wins else autotune.NEVER
        autotune.record(
            autotune.min_t_key(d_swept),
            {"min_t": min_t,
             "mode": "attn_sweep_crossover",
             "swept": {str(t): bool(w)
                       for t, w in sorted(merged.items())}})
        print("  autotune seeded flash_min_t d=%d -> %s"
              % (d_swept,
                 "never" if min_t == autotune.NEVER else min_t),
              flush=True)


def sec_generation(dev, n):
    """KV-cached decode throughput on chip (tokens/s). The re-forward
    oracle is SKIPPED here: it recompiles per context length; its
    parity is CPU-gated in CI."""
    import numpy
    import char_lm as lm
    from veles_tpu import prng
    from veles_tpu.nn import sampling
    rows = []
    for n_blocks, dim, n_new in ((2, 64, 96), (4, 256, 128)):
        prng.seed_all(7)
        # the big config trains briefly so the speculative A/B below
        # measures a REAL acceptance rate (draft agreement with random
        # weights is meaningless); throughput itself is weight-blind
        wf = lm.build_workflow(epochs=6 if n_blocks >= 4 else 1,
                               minibatch_size=64,
                               n_blocks=n_blocks, dim=dim,
                               n_train=256, n_valid=64)
        wf.initialize(device=dev)
        if n_blocks >= 4:
            wf.run()
        prompt = list(lm.make_corpus(numpy.random.RandomState(3), 24))
        sampling.generate(wf, prompt, n_new, temperature=0)  # compile
        t0 = time.time()
        reps = 3
        for _ in range(reps):
            out = sampling.generate(wf, prompt, n_new, temperature=0)
        dt = (time.time() - t0) / reps
        rows.append({"n_blocks": n_blocks, "dim": dim, "n_new": n_new,
                     "cached_tok_s": round(n_new / dt, 1),
                     "out_len": len(out)})
        print("  gen %dx%d: %s tok/s" % (n_blocks, dim,
                                         rows[-1]["cached_tok_s"]),
              flush=True)
        if n_blocks >= 4:
            # speculative decoding on chip: tokens per TARGET dispatch
            # is the point where dispatch latency dominates (one
            # big-model dispatch per ~gamma tokens); parity asserted
            from veles_tpu.nn.speculative import generate_speculative
            prng.seed_all(11)
            draft = lm.build_workflow(epochs=6, minibatch_size=64,
                                      n_blocks=1, dim=dim // 4,
                                      n_train=256, n_valid=64)
            draft.initialize(device=dev)
            draft.run()
            spec, stats = generate_speculative(wf, draft, prompt,
                                               n_new, gamma=4)
            assert spec == out, "speculative parity broke on chip"
            t0 = time.time()
            for _ in range(reps):
                _, stats = generate_speculative(wf, draft, prompt,
                                                n_new, gamma=4)
            dt = (time.time() - t0) / reps
            rows.append({"n_blocks": n_blocks, "dim": dim,
                         "n_new": n_new, "gamma": 4,
                         "spec_tok_s": round(n_new / dt, 1),
                         "acceptance": round(stats["acceptance"], 3)})
            print("  spec %dx%d: %s tok/s acc=%s"
                  % (n_blocks, dim, rows[-1]["spec_tok_s"],
                     rows[-1]["acceptance"]), flush=True)
            # beam=4 on chip: 4 hypotheses ride the batch axis, so the
            # per-token cost is ~one batched step — the number says
            # what width-4 search costs vs greedy on this hardware
            from veles_tpu.nn.beam import beam_generate
            beam_generate(wf, prompt, n_new, beam=4)      # compile
            t0 = time.time()
            for _ in range(reps):
                beam_generate(wf, prompt, n_new, beam=4)
            dt = (time.time() - t0) / reps
            rows.append({"n_blocks": n_blocks, "dim": dim,
                         "n_new": n_new, "beam": 4,
                         "beam_tok_s": round(n_new / dt, 1)})
            print("  beam %dx%d: %s tok/s"
                  % (n_blocks, dim, rows[-1]["beam_tok_s"]),
                  flush=True)
            # batched serving throughput (r5): 8 prompts ride ONE
            # batched cached decode and ONE batched speculative decode
            # — total tok/s vs the single-row numbers above quantifies
            # the GenerationAPI micro-batch win on this chip
            prompts8 = [list(lm.make_corpus(
                numpy.random.RandomState(100 + i), 24))
                for i in range(8)]
            sampling.generate(wf, prompts8, n_new, temperature=0)
            t0 = time.time()
            for _ in range(reps):
                sampling.generate(wf, prompts8, n_new, temperature=0)
            dt = (time.time() - t0) / reps
            rows.append({"n_blocks": n_blocks, "dim": dim,
                         "n_new": n_new, "batch": 8,
                         "cached_tok_s_total": round(8 * n_new / dt, 1)})
            print("  gen batch8 %dx%d: %s tok/s total"
                  % (n_blocks, dim, rows[-1]["cached_tok_s_total"]),
                  flush=True)
            generate_speculative(wf, draft, prompts8, n_new, gamma=4)
            t0 = time.time()
            for _ in range(reps):
                _, bstats = generate_speculative(wf, draft, prompts8,
                                                 n_new, gamma=4)
            dt = (time.time() - t0) / reps
            rows.append({"n_blocks": n_blocks, "dim": dim,
                         "n_new": n_new, "batch": 8, "gamma": 4,
                         "spec_tok_s_total": round(8 * n_new / dt, 1),
                         "mean_acceptance": round(
                             bstats["mean_acceptance"], 3)})
            print("  spec batch8 %dx%d: %s tok/s total acc=%s"
                  % (n_blocks, dim, rows[-1]["spec_tok_s_total"],
                     rows[-1]["mean_acceptance"]), flush=True)
    return rows


#: the paged decode read at each serving cell's full layers: (slots,
#: query heads as stored, KV heads as stored, key width as stored, true
#: key width, value width, pool dtype, table pages, prompt range, output
#: range)
PAGED_SHAPES = {
    "olmo_hybrid_decode_sat": (64, 32, 32, 128, 128, 128, "bfloat16", 96,
                               (128, 1024), (256, 512)),
    "mimo_v25_decode_mixed": (64, 64, 4, 256, 192, 128, "bfloat16", 288,
                              (256, 3840), (256, 512)),
    "mistral7b_decode_sat": (32, 32, 8, 128, 128, 128, "float32", 64,
                             (64, 256), (256, 512)),
}


def sec_paged_read(dev, n):
    """ops/paged_attention.py alone at each serving cell's widths: the
    kernel's device time a call (ten calls chained in one program, the
    host's clock over it), the bytes of the pages it copies (the live
    rows rounded up to pages) and GB/s, and the largest gap of its
    attention from a float32 einsum over the same rows. A slot's end is
    its prompt plus a uniform share of its output, both uniform over the
    cell's ranges."""
    import numpy
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import paged_attention as pa
    reps, chained = 5, 10
    out = {}
    for cell, (slots, heads, kv, hd, true_hd, vd, dtype, width, prompts,
               outputs) in PAGED_SHAPES.items():
        rng = numpy.random.RandomState(41)
        ends = (rng.randint(prompts[0], prompts[1] + 1, slots)
                + (rng.rand(slots) * rng.randint(outputs[0], outputs[1] + 1,
                                                 slots)).astype(int))
        ends = numpy.minimum(ends, width * 16)
        pages = slots * width + 1
        tables = 1 + numpy.arange(slots * width, dtype=numpy.int32).reshape(
            slots, width)
        dt = jnp.dtype(dtype)
        k_pool = jax.random.normal(jax.random.key(1), (pages, 16, kv, hd),
                                   jnp.float32).astype(dt)
        v_pool = jax.random.normal(jax.random.key(2), (pages, 16, kv, vd),
                                   jnp.float32).astype(dt)
        q = jax.random.normal(jax.random.key(3), (slots, heads, hd),
                              jnp.float32)
        copied = int(numpy.sum(-(-ends // 16))) * 16 * kv * (hd + vd) \
            * dt.itemsize
        row = {"slots": slots, "heads": heads, "kv": kv, "hd": hd,
               "vd": vd, "dtype": dtype, "table_pages": width,
               "live_mean": float(numpy.mean(ends)), "bytes_copied": copied}
        ends_d = jnp.asarray(ends, jnp.int32)
        tables_d = jnp.asarray(tables)
        starts = jnp.zeros_like(ends_d)
        scale = float(1 / numpy.sqrt(true_hd))

        @jax.jit
        def run(q, k_pool, v_pool, tables, starts, ends, scale=scale):
            def one(i, q_i):
                acc, m, l = pa.paged_attention(q_i, k_pool, v_pool, tables,
                                               starts, ends, scale=scale)
                # a dependency on the call before, no change to q
                return q_i + l[:, :1, None] * 0.0
            q = jax.lax.fori_loop(0, chained, one, q)
            acc, m, l = pa.paged_attention(q, k_pool, v_pool, tables,
                                           starts, ends, scale=scale)
            return acc / l[..., None]
        try:
            got = jax.block_until_ready(
                run(q, k_pool, v_pool, tables_d, starts, ends_d))
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    run(q, k_pool, v_pool, tables_d, starts, ends_d))
                times.append((time.perf_counter() - t0) / (chained + 1))
            ms = 1e3 * float(numpy.median(times))
            row.update(ms=round(ms, 4), gb_s=round(copied / ms / 1e6, 1),
                       gb_s_of_peak=round(copied / ms / 1e6 / 819 * 100, 1))
            # the reference, on a few slots' rows in float32
            for s in range(4):
                view_k = k_pool[tables[s]].reshape(-1, kv, hd)[:ends[s]]
                view_v = v_pool[tables[s]].reshape(-1, kv, vd)[:ends[s]]
                qs = q[s].reshape(kv, heads // kv, hd)
                sc = jnp.einsum("kgd,tkd->kgt", qs,
                                view_k.astype(jnp.float32)) * scale
                want = jnp.einsum("kgt,tkd->kgd", jax.nn.softmax(sc, -1),
                                  view_v.astype(jnp.float32))
                row["max_gap"] = max(row.get("max_gap", 0.0), float(
                    jnp.max(jnp.abs(want.reshape(heads, vd) - got[s]))))
        except Exception as e:            # noqa: BLE001 — the next goes on
            row["error"] = str(e)[-400:]
        out[cell] = row
        print("  paged_read %s: %s" % (cell, row), flush=True)
    return out


SECTIONS = [("pallas_compile", sec_pallas_compile),
            ("attn_2048", sec_attn_2048), ("attn_8192", sec_attn_8192),
            ("attn_d128", sec_attn_d128), ("attn_d256", sec_attn_d256),
            ("generation", sec_generation)]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sections", default=",".join(k for k, _ in SECTIONS))
    args = p.parse_args()
    want = [s.strip() for s in args.sections.split(",") if s.strip()]
    by_name = dict(SECTIONS)
    # manual alias outside the default batch: the split halves cover
    # both lengths, so the full sweep must not run twice by default
    by_name["attn"] = sec_attn
    # asked for by name alone: it writes no tuning row
    by_name["paged_read"] = sec_paged_read
    # asked for by name alone: its row is the latent-attention cell's
    by_name["attn_mla"] = sec_attn_mla
    unknown = [name for name in want if name not in by_name]
    if unknown:
        print("unknown section(s) %s" % unknown, file=sys.stderr)
        return 1

    import veles_tpu as vt
    try:
        dev = vt.Device_for("tpu")
    except vt.VelesError as e:
        print("chip_experiments: %s" % e, file=sys.stderr)
        return 1
    import jax
    n = dev.device_count
    save("_device", {"platform": dev.platform, "n_chips": n,
                     "device_kind": str(jax.devices()[0].device_kind),
                     "jax": jax.__version__})
    failed = []
    for name in want:
        print("== section %s" % name, flush=True)
        t0 = time.time()
        try:
            out = by_name[name](dev, n)
            save(name, {"result": out,
                        "elapsed_s": round(time.time() - t0, 1)})
            if isinstance(out, dict) and out.get("all_ok") is False:
                failed.append(name)
        except Exception as e:        # noqa: BLE001 — the batch goes on,
            # the exit code remembers
            import traceback
            traceback.print_exc()
            save(name, {"error": str(e)[-500:],
                        "elapsed_s": round(time.time() - t0, 1)})
            failed.append(name)
    if failed:
        print("chip_experiments: FAILED section(s): %s" % ", ".join(failed),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
