"""Driver benchmark: prints ONE JSON line with the headline metric.

Three measurements, one line:

1. headline (BASELINE.json): Znicz MNIST-784 workflow training throughput,
   samples/sec/chip, on the fused SPMD step. The reference published no
   throughput numbers ("published": {}), so vs_baseline is against the
   first recorded number of this build (BENCH_BASELINE.json). This config
   is dispatch-latency-bound — it proves dispatch amortization.
2. extras[0]: the compute-bound proof — the ImagenetAE conv autoencoder
   (models/imagenet_ae.build_bench_workflow) at 128x128, bf16 compute /
   f32 accumulation, reporting samples/sec/chip, achieved model TFLOP/s
   and MFU against the chip's nominal bf16 peak. This is where the MXU
   actually works (BASELINE.json names ImagenetAE samples/sec/chip).
3. extras[1]: transformer-LM training throughput (tokens/sec/chip) —
   GPT-style stack (512 dim x 6 RoPE blocks, T=512, per-token CE) under
   mixed precision with 4 whole epochs per dispatch; the modern-workload
   surface the reference never had.

Measurement notes (methodology fixed 2026-07-29, provenance stamped into
the JSON):
- one process takes the chip through the strict Device_for("tpu"): no
  chip, or a failed section, is a non-zero exit and no metric line —
  never a host number under a chip metric's name.
- sync = jax.block_until_ready on the parameter tree.
- windows: median of 3 x 10 s (max recorded as a secondary field; the
  median is the regression-detection number — best-of-N inflates).
- every section additionally stamps {device_time_s, wall_time_s,
  mfu_device} from the device-time measurement plane
  (veles_tpu/telemetry/devtime.py: profiler device-stream self-time,
  host-sync fallback counted) — `bench.py gate` keys its timing
  pass/fail on device time, which host noise cannot swing.
- MNIST: epochs_per_dispatch=8 — eight whole epochs (valid eval + train,
  600+100 minibatch rows each) fused into ONE device program; host round
  trips dominate that config. AE plan_steps=16 (one epoch per dispatch at
  n_train=1024, mb=64; compute dominates there) under mixed_precision.
- FLOPs are analytic model FLOPs (2*spatial*weight_size per conv position,
  x3 for training fwd+bwd), NOT hardware-counter FLOPs — the standard MFU
  numerator.
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "models"))

# the nominal dense bf16 peak table lives in the telemetry subsystem
# (veles_tpu/telemetry/cost.py PEAK_BF16) — ONE copy for bench, the
# CostModel and the docs; peak_bf16_flops() below delegates to it.


def host_sync(step):
    """Device sync: wait for the parameter tree the last dispatch
    produced."""
    import jax
    jax.block_until_ready(step.params)


def measure_windows(run_epoch, sync, n_windows=3, secs=10.0,
                    min_epochs=2, sync_every=32):
    """Each window: >= secs wall time and >= min_epochs epochs, synced
    at the end. Returns (per-window samples/sec, epochs, durations,
    devtimes) — ``devtimes`` is the per-window
    ``{device_time_s, wall_time_s, source}`` stamp: every window is
    sync-bracketed (the previous window's trailing sync is this one's
    leading sync), so its wall duration is the host-sync device-time
    estimate; the per-section profiler refinement
    (telemetry/devtime.py) replaces it when device streams are
    capturable.

    ``sync_every`` bounds the number of un-synced dispatches in flight:
    JAX dispatch is async and the wall-clock loop condition measures
    *enqueue* time, so a small program (e.g. epochs_per_dispatch=1)
    can flood the chip with thousands of queued executions per window.
    Syncing every N epochs keeps the backlog bounded at a cost of one device round trip
    per N dispatches, inside the timed window, so rates stay honest."""
    rates, epoch_counts, durations, devtimes = [], [], [], []
    for _ in range(n_windows):
        t0 = time.time()
        n = epochs = 0
        while time.time() - t0 < secs or epochs < min_epochs:
            n += run_epoch()
            epochs += 1
            if epochs % sync_every == 0:
                sync()
        sync()
        dt = time.time() - t0
        rates.append(n / dt)
        epoch_counts.append(epochs)
        durations.append(dt)
        devtimes.append({"device_time_s": dt, "wall_time_s": dt,
                         "source": "host_sync"})
    return rates, epoch_counts, durations, devtimes


def epoch_runner(wf):
    loader, step = wf.loader, wf.train_step

    def run_epoch():
        served0 = loader.samples_served
        while True:
            loader.run()
            step.run()
            if bool(loader.epoch_ended):
                break
        return loader.samples_served - served0
    return run_epoch


def model_flops_per_sample(wf):
    """Analytic forward model-FLOPs per sample: 2 * spatial positions *
    weight elements for convs (output spatial) / deconvs (input spatial),
    2 * weight elements for dense. Pool/activation/bias FLOPs are noise
    at MFU scale and excluded (standard practice)."""
    from veles_tpu.nn.conv import Conv
    from veles_tpu.nn.deconv import Deconv
    total = 0
    for f in wf.train_step.forwards:
        if not f.PARAMETERIZED:
            continue
        w = f.param_arrays().get("weights")
        if w is None:
            continue
        if isinstance(f, Conv):
            _, oh, ow, _ = f.output.shape
            total += 2 * oh * ow * w.mem.size
        elif isinstance(f, Deconv):
            _, ih, iw, _ = f.input.shape
            total += 2 * ih * iw * w.mem.size
        else:
            total += 2 * w.mem.size
    return total


def _counters_before(step=None):
    """Snapshot of the telemetry counters (and the step's per-program
    dispatch counts), taken right before a bench section's measurement
    windows."""
    from veles_tpu.telemetry.counters import counters
    return {"counters": counters.snapshot(),
            "key_counts": dict(getattr(step, "_dispatch_counts", {}))
            if step is not None else {}}


def _section_counters(before, step=None, seconds=None,
                      n_chips=1, epochs=None):
    """The deterministic accounting record every bench section carries:
    ``{flops, bytes, dispatches, compiles}`` for the measurement
    window, from the telemetry counter deltas plus the CostModel's
    per-program costs (``TrainStep.cost_report`` —
    ``Compiled.cost_analysis`` with the analytic Pallas fallback
    merged). Each program's dispatches are billed at that program's
    own cost (classic mode mixes 'train' and 'eval' dispatches in one
    window — a flat per-dispatch rate would inflate the eval share).

    Raw window totals scale with how many epochs the time-boxed
    windows fit, so the gate (``bench.py gate``) reads only the
    NORMALIZED fields — ``dispatches_per_epoch`` (``epochs`` = the
    section's run_epoch call count), ``flops_per_dispatch``,
    ``bytes_per_dispatch``, steady-state ``compiles`` (0 whatever the
    window length), ``dispatches_per_token`` — which are invariants of
    the program, not the wall clock."""
    from veles_tpu.telemetry.counters import counters
    delta = counters.delta(before["counters"])
    out = {
        "dispatches": int(delta.get("veles_dispatches_total", 0)),
        "compiles": int(delta.get("veles_compiles_total", 0)),
        "h2d_bytes": int(delta.get("veles_h2d_bytes_total", 0)),
        "d2h_bytes": int(delta.get("veles_d2h_bytes_total", 0)),
    }
    if epochs:
        out["epochs"] = int(epochs)
        out["dispatches_per_epoch"] = out["dispatches"] / epochs
    decode_toks = delta.get("veles_decode_tokens_total", 0)
    if decode_toks:
        out["dispatches_per_token"] = (
            delta.get("veles_decode_dispatches_total", 0) / decode_toks)
    if step is None:
        return out
    try:
        rep = step.cost_report()
    except Exception as e:            # noqa: BLE001 — accounting must
        out["cost_error"] = str(e)    # never take the section down
        return out
    if not rep:
        return out
    counts_now = dict(getattr(step, "_dispatch_counts", {}))
    flops = bytes_ = 0.0
    key_counts = {}
    for key, cost in rep["costs"].items():
        n = counts_now.get(key, 0) - before["key_counts"].get(key, 0)
        if n <= 0:
            continue
        key_counts[key] = n
        flops += cost.flops * n
        bytes_ += cost.bytes_accessed * n
    primary = rep["cost"]
    n_prog = sum(key_counts.values())
    out["flops"] = flops
    out["bytes"] = bytes_
    out["program_dispatches"] = key_counts
    out["flops_per_dispatch"] = flops / n_prog if n_prog else 0.0
    out["bytes_per_dispatch"] = bytes_ / n_prog if n_prog else 0.0
    out["peak_memory_bytes"] = primary.peak_memory
    out["cost_source"] = primary.source
    out["program"] = rep["key"]
    if seconds and flops:
        # measured MFU from the framework's own cost accounting — the
        # CostModel numerator over the chip's nominal peak, NOT a
        # hand-derived number in docs (docs/observability.md)
        from veles_tpu.telemetry.cost import Cost
        out["mfu_telemetry"] = Cost(flops, bytes_).mfu(
            seconds, n_chips=n_chips)
    return out


def _section_devtime(run_epoch, sync, epochs, durations, counters_rec,
                     n_chips=1, dtype=None):
    """The section's device-time stamp (telemetry/devtime.py):
    ``{device_time_s, wall_time_s, mfu_device, device_time_per_epoch,
    source, ...}``.

    One profiler refinement pass (a single ``run_epoch`` call between
    scalar-fetch syncs) attempts a ``jax.profiler`` capture; when it
    yields device-stream self-time, the stamp is device time scaled to
    the median window's epoch count — the host-noise-immune number
    the gate compares. When profiling is unavailable (counted
    ``veles_devtime_fallbacks_total``), the stamp falls back to the
    sync-bracketed window wall time itself. ``mfu_device`` is the
    CostModel FLOPs-per-epoch (from the section's counters record)
    over device-time-per-epoch and the chip's nominal peak FOR THE
    SECTION'S COMPUTE DTYPE (``dtype=`` — f32 sections are graded
    against PEAK_F32, not mispriced 2x against the bf16 peak; default
    bf16 preserves the historical denominator for mixed-precision
    sections). The peak used is stamped into the record
    (``peak_flops_used``/``peak_dtype``/``peak_source``) so every MFU
    names its own denominator."""
    from veles_tpu.telemetry import devtime as _devtime
    rec = _devtime.measure(run_epoch, sync)
    med_eps = statistics.median(epochs)
    wall_med = statistics.median(durations)
    if rec["source"] == "profiler":
        per_epoch = rec["device_time_per_call"]
        device_s = per_epoch * med_eps
    else:
        # the windows are already sync-bracketed: their wall duration
        # IS the host-sync device-time estimate (upper bound by the
        # bounded sync round trips inside the window)
        per_epoch = sum(durations) / max(1, sum(epochs))
        device_s = wall_med
    out = {
        "device_time_s": device_s,
        "wall_time_s": wall_med,
        "device_time_per_epoch": per_epoch,
        "source": rec["source"],
        "capture_calls": rec["calls"],
        "mfu_device": None,
    }
    if rec["source"] == "profiler" and rec.get("by_stream"):
        out["by_stream"] = rec["by_stream"]
    if rec.get("spans"):
        # device self-time attributed onto the telemetry span names
        # that closed inside the capture window (the same table
        # `veles-tpu trace self-time --spans` prints)
        out["spans"] = {k: round(v["device_time_s"], 6)
                        for k, v in rec["spans"].items()}
    from veles_tpu.telemetry.cost import peak_flops_entry
    peak_source, peak = peak_flops_entry(dtype or "bfloat16")
    out["peak_flops_used"] = peak
    out["peak_dtype"] = str(dtype or "bfloat16")
    out["peak_source"] = peak_source
    flops = (counters_rec or {}).get("flops")
    n_eps = (counters_rec or {}).get("epochs")
    if flops and n_eps and per_epoch > 0:
        out["mfu_device"] = (flops / n_eps) / per_epoch / (
            peak * n_chips)
    return out


def _stamp_devtime(section, devtime_rec):
    """Copy the stamp contract every bench section carries at its top
    level — ``{device_time_s, wall_time_s, mfu_device}`` — plus the
    full record under ``devtime`` (what ``bench.py gate`` reads)."""
    section["devtime"] = devtime_rec
    for key in ("device_time_s", "wall_time_s", "mfu_device",
                "peak_flops_used", "peak_dtype", "peak_source"):
        if key in devtime_rec:
            section[key] = devtime_rec[key]
    return section


BLOCK_EPOCHS = 8


def bench_mnist(dev, n_chips, h=BLOCK_EPOCHS):
    """``h`` is the dispatch block size (chip experiments measure h=1
    vs h=8 explicitly)."""
    from mnist import build_workflow
    # host round trips are the dominant cost of this config (measured
    # plan-size sweep: 50 -> 0.47M ... 600 -> 1.9M samples/s);
    # epochs_per_dispatch fuses 8 WHOLE epochs (valid eval + train) into
    # one device program, cutting the per-epoch dispatch+drain round
    # trips by 8x on top of the per-epoch scan
    wf = build_workflow(epochs=10 ** 9, minibatch_size=100,
                        epochs_per_dispatch=h)
    wf.initialize(device=dev)
    run_epoch = epoch_runner(wf)
    run_epoch()                  # warmup: compile + first placement
    host_sync(wf.train_step)
    before = _counters_before(wf.train_step)
    rates, eps, durs, _wins = measure_windows(
        run_epoch, lambda: host_sync(wf.train_step))
    counters_rec = _section_counters(before, wf.train_step,
                                     seconds=sum(durs),
                                     n_chips=n_chips, epochs=sum(eps))
    # the mnist section trains in plain f32 — its MFU denominator is
    # the f32 peak, not the bf16 one (satellite of the linalg family)
    dt = _section_devtime(run_epoch, lambda: host_sync(wf.train_step),
                          eps, durs, counters_rec, n_chips=n_chips,
                          dtype="float32")
    from veles_tpu import datasets
    return _stamp_devtime({
        "samples_per_sec_per_chip": statistics.median(rates) / n_chips,
        "max_window": max(rates) / n_chips,
        "epochs_per_dispatch": h,
        "data": "real" if datasets.mnist_is_real() else "synthetic",
        # which train-segment engine actually ran (a silent eligibility
        # fallback must never wear the fused-kernel method tag)
        "fused_fc_active": bool(getattr(wf.train_step,
                                        "_fused_fc_active", False)),
        "counters": counters_rec,
    }, dt)


import contextlib


@contextlib.contextmanager
def mixed_precision_on():
    """bf16 activation storage for the measurement inside (docs/perf.md
    roofline: the image/LM benches are HBM-bound); restored on exit so
    no other measurement inherits the flag."""
    from veles_tpu.config import root as vt_root
    prev = vt_root.common.engine.get("mixed_precision", False)
    vt_root.common.engine.mixed_precision = True
    try:
        yield
    finally:
        vt_root.common.engine.mixed_precision = prev


def peak_bf16_flops():
    from veles_tpu.telemetry.cost import peak_bf16_flops as _peak
    return _peak()      # this device's row; an unknown device raises


def _peak_or_none(dtype):
    """``(source label, peak FLOP/s)`` for the running device, or
    ``(None, None)`` where no peak is on file — the CPU the gate drills
    run on. Their records then carry no utilization at all, instead of
    one graded against some other chip's peak."""
    from veles_tpu.telemetry.cost import UnknownDevice, peak_flops_entry
    try:
        return peak_flops_entry(dtype)
    except UnknownDevice:
        return None, None


def measured_tflops(epoch_counts, durations, epoch_flops,
                    epochs_per_call=1):
    """Median across windows of executed model TFLOP/s.
    measure_windows counts run_epoch CALLS; under block dispatch each
    call executes epochs_per_call whole epochs — forgetting that factor
    under-reports FLOPs by exactly that factor."""
    return statistics.median(
        [e * epochs_per_call * epoch_flops / d
         for e, d in zip(epoch_counts, durations)]) / 1e12


def bench_conv_ae(dev, n_chips, minibatch_size=64):
    from veles_tpu.config import root as vt_root
    with mixed_precision_on():
        # bf16 dataset storage: halves HBM residency AND the one-time
        # 226 MB host-to-device staging (synthetic pixels; the metric
        # is throughput)
        prev_ds = vt_root.common.engine.get("dataset_dtype", None)
        vt_root.common.engine.dataset_dtype = "bfloat16"
        try:
            return _bench_conv_ae_inner(dev, n_chips,
                                        minibatch_size=minibatch_size)
        finally:
            vt_root.common.engine.dataset_dtype = prev_ds


def _bench_conv_ae_inner(dev, n_chips, minibatch_size=64):
    from imagenet_ae import build_bench_workflow
    wf = build_bench_workflow(image_size=128,
                              minibatch_size=minibatch_size,
                              n_train=1024, n_valid=128)
    wf.initialize(device=dev)
    fwd_flops = model_flops_per_sample(wf)
    loader = wf.loader
    # per-epoch model FLOPs: train x3 (fwd + bwd), valid x1 (eval fwd)
    epoch_flops = (loader.class_lengths[2] * 3 * fwd_flops
                   + loader.class_lengths[1] * fwd_flops)
    run_epoch = epoch_runner(wf)
    run_epoch()
    host_sync(wf.train_step)
    before = _counters_before(wf.train_step)
    rates, epochs, durs, _wins = measure_windows(
        run_epoch, lambda: host_sync(wf.train_step))
    tflops = measured_tflops(epochs, durs, epoch_flops)
    peak = peak_bf16_flops()
    counters_rec = _section_counters(before, wf.train_step,
                                     seconds=sum(durs),
                                     n_chips=n_chips,
                                     epochs=sum(epochs))
    dt = _section_devtime(run_epoch, lambda: host_sync(wf.train_step),
                          epochs, durs, counters_rec, n_chips=n_chips,
                          dtype="bfloat16")
    from veles_tpu.config import root
    # rates count every served sample; the metric is labeled TRAIN
    # throughput, so scale out the validation passes each epoch carries
    train_frac = loader.class_lengths[2] / (
        loader.class_lengths[1] + loader.class_lengths[2])
    return _stamp_devtime({
        "metric": "imagenet_ae_train_samples_per_sec_per_chip",
        "samples_per_sec_per_chip":
            statistics.median(rates) * train_frac / n_chips,
        "max_window": max(rates) * train_frac / n_chips,
        "model_tflops_per_sec_per_chip": tflops / n_chips,
        "mfu": tflops / n_chips / (peak / 1e12),
        "peak_bf16_tflops_assumed": peak / 1e12,
        "fwd_gflops_per_sample": fwd_flops / 1e9,
        "image_size": 128, "minibatch": minibatch_size, "plan_steps":
            wf.loader.plan_steps,
        "compute_dtype": str(root.common.engine.compute_dtype),
        "mixed_precision": bool(wf.train_step.mixed_precision),
        "dataset_dtype": str(wf.loader.original_data.mem.dtype),
        "data": "synthetic",
        "counters": counters_rec,
    }, dt)


LM_BLOCK_EPOCHS = 4


def bench_lm(dev, n_chips, cfg_overrides=None,
             epochs_per_dispatch=None):
    """Transformer-LM training throughput (tokens/sec/chip) — the
    modern-workload surface: embedding → RoPE blocks → per-token CE,
    under mixed precision with 4 whole epochs per dispatch.
    ``cfg_overrides`` parameterizes framework-ceiling extras (bigger
    model/sequence rows carry their own config in the result and are
    never compared to the default row)."""
    from char_lm import build_bench_workflow
    with mixed_precision_on():
        cfg = dict(seq_len=512, dim=512, n_blocks=6, ffn_hidden=2048,
                   n_heads=8, vocab=256, minibatch_size=16,
                   n_train=1024, n_valid=128)
        cfg.update(cfg_overrides or {})
        h = epochs_per_dispatch or LM_BLOCK_EPOCHS
        wf = build_bench_workflow(epochs_per_dispatch=h, **cfg)
        wf.initialize(device=dev)
        # analytic model FLOPs per token (matmul weights x2, embedding
        # gather excluded, + the attention T-term per block), x3 train
        d, t_len = cfg["dim"], cfg["seq_len"]
        p_block = 4 * d * d + 2 * d * cfg["ffn_hidden"]
        p_mat = cfg["n_blocks"] * p_block + d * cfg["vocab"]
        fwd_per_token = 2 * p_mat + cfg["n_blocks"] * 2 * 2 * t_len * d
        loader = wf.loader
        n_tr, n_va = loader.class_lengths[2], loader.class_lengths[1]
        epoch_flops = t_len * fwd_per_token * (3 * n_tr + n_va)
        run_epoch = epoch_runner(wf)
        run_epoch()
        host_sync(wf.train_step)
        before = _counters_before(wf.train_step)
        rates, epochs, durs, _wins = measure_windows(
            run_epoch, lambda: host_sync(wf.train_step))
        # each run_epoch call = one BLOCK of 4 whole epochs
        tflops = measured_tflops(
            epochs, durs, epoch_flops,
            epochs_per_call=wf.loader.block_length or 1)
        peak = peak_bf16_flops()
        counters_rec = _section_counters(before, wf.train_step,
                                         seconds=sum(durs),
                                         n_chips=n_chips,
                                         epochs=sum(epochs))
        dt = _section_devtime(run_epoch,
                              lambda: host_sync(wf.train_step),
                              epochs, durs, counters_rec,
                              n_chips=n_chips, dtype="bfloat16")
        train_frac = n_tr / (n_tr + n_va)
        return _stamp_devtime({
            "metric": "lm_train_tokens_per_sec_per_chip",
            "tokens_per_sec_per_chip":
                statistics.median(rates) * t_len * train_frac / n_chips,
            "model_tflops_per_sec_per_chip": tflops / n_chips,
            "mfu": tflops / n_chips / (peak / 1e12),
            "config": {k: cfg[k] for k in ("seq_len", "dim", "n_blocks",
                                           "minibatch_size")},
            "epochs_per_dispatch": h,
            "mixed_precision": True,
            "data": "synthetic",
            "counters": counters_rec,
        }, dt)


BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_BASELINE.json")


def _assemble(mnist, ae, lm, platform, device_kind, allow_rebaseline):
    """The ONE output line."""
    sps = mnist["samples_per_sec_per_chip"]
    h = mnist["epochs_per_dispatch"]
    # the window statistic AND the dispatch config are the methodology:
    # comparing plan-mode numbers against 8-epoch-block numbers would
    # conflate the dispatch speedup with perf drift (ADVICE r2)
    method = "median_of_3x10s" + ("_h%d" % h if h != 1 else "")
    base_path = BASELINE_PATH
    rebaselined = False
    base = None
    # baselines are stored PER METHOD TAG: one flat slot would let
    # alternating dispatch configs overwrite each other's anchor and
    # reset vs_baseline to 1.0 on every switch. Legacy single-slot
    # files ({"value", "method"}) migrate to their own key on read.
    baselines = {}
    if os.path.exists(base_path):
        with open(base_path) as f:
            stored = json.load(f)
        baselines = stored.get("baselines", {})
        if not baselines and "method" in stored:
            baselines = {stored["method"]: {"value": stored["value"],
                                            "ts": stored.get("ts")}}
        # comparable only when recorded with the same method tag — the
        # r1 baseline used best-of-3 (max), which would make every
        # median-based run read as a phantom regression
        if method in baselines:
            base = baselines[method]["value"]
    if base is None and allow_rebaseline:
        base = sps
        rebaselined = True
        baselines[method] = {"value": sps, "ts": time.time()}
        with open(base_path, "w") as f:
            json.dump({"baselines": baselines}, f)
    return {
        "metric": "mnist784_train_samples_per_sec_per_chip",
        "value": round(sps, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": None if base is None else round(sps / base, 3),
        "rebaselined": rebaselined,
        "window": method,
        "max_window": round(mnist["max_window"], 1),
        "data": mnist["data"],
        "epochs_per_dispatch": h,
        "sync": "block_until_ready",
        "platform": platform,
        "device_kind": device_kind,
        # deterministic accounting for the headline window (telemetry
        # counters + CostModel): what `bench.py gate` compares
        "counters": mnist.get("counters", {}),
        # device-time measurement plane (telemetry/devtime.py): the
        # host-noise-immune timing record the gate keys its pass/fail on —
        # wall-clock comparisons survive only as the counted legacy
        # fallback
        "devtime": mnist.get("devtime"),
        "device_time_s": mnist.get("device_time_s"),
        "wall_time_s": mnist.get("wall_time_s"),
        "mfu_device": mnist.get("mfu_device"),
        # overlap engine accounting (veles_tpu/overlap/): in the
        # default overlap-OFF bench these MUST be zero — the gate
        # fails if side-plane counters leaked into the serial path
        "overlap": _overlap_section(),
        # model-health accounting (veles_tpu/telemetry/tensormon.py):
        # in the default monitoring-OFF bench the sample/NaN counters
        # MUST be zero — taps leaking into an unmonitored step would
        # break the bit-identical-off contract
        "tensormon": _tensormon_section(),
        # continuous-batching serving accounting (veles_tpu/serving/):
        # the bench never serves, so every serving counter MUST read
        # zero here — the gate fails on leakage
        "serving": _serving_section(),
        # quantization accounting (veles_tpu/quant/): the bench runs
        # quant-off, so the quant/artifact counters MUST read zero —
        # int8 machinery leaking into a float measurement would break
        # the bit-identical-off contract. The fp-vs-int8 measurement
        # itself lives in `python bench.py quant` / the gate's quant
        # proof (docs/perf.md "Quantized serving").
        "quant": _quant_section(),
        # elastic training plane (veles_tpu/resilience/elastic.py):
        # the bench never runs elastic, so the generation/preemption
        # counters MUST read zero here — generation machinery leaking
        # into a plain training measurement would mean restores (and
        # their reshard device_puts) ran inside a perf window
        "elastic": _elastic_section(),
        # serving fleet router (veles_tpu/serving/router.py): the
        # bench never routes, so every router counter MUST read zero
        # here — the gate fails on leakage; the failover/exactly-once
        # measurement itself is the gate's live fleet proof
        "fleet": _fleet_section(),
        # lossless request plane (serving/journal.py + token-level
        # resume + drain-by-handoff): the bench never journals,
        # resumes or hands off, so every count MUST be zero here —
        # the gate fails on leakage; the resumed-decode-cheaper-than-
        # redo measurement is the gate's live lossless proof
        "lossless": _lossless_section(),
        # fleet tracing (telemetry/spans.py ring pulls + fleet.py
        # assembly): the bench never serves, pulls or merges, so the
        # request/route span count and the pull/rotation/merge
        # counters MUST be zero here — the gate fails on leakage;
        # the one-merged-trace-across-a-replica-death measurement is
        # the gate's live tracing proof
        "tracing": _tracing_section(),
        # prefix-sharing request plane (serving/pages.py PrefixCache
        # + engine adoption/COW/eviction): the bench never serves, so
        # every prefix counter MUST read zero here — the gate fails
        # on leakage; the share-ratio FLOP-reduction, stream-TTFT and
        # chunk-stall measurements are gate_prefix's live proof
        "prefix": _prefix_section(),
        # O(1)-state serving lane (serving/recurrent.py + the radix
        # StateCache): the bench never serves the recurrent slot
        # pool, so every checkpoint/restore counter MUST read zero
        # here — the gate fails on leakage; the flat-state-bytes,
        # scan-vs-recurrent id-exactness and slots-at-equal-HBM
        # measurements are gate_o1state's live proof
        "o1state": _o1state_section(),
        # overload-hardened request plane (serving/overload.py QoS +
        # veles_tpu/loadgen/): the bench never runs QoS or the load
        # harness, so every preemption/throttle/brownout/loadgen
        # counter MUST read zero here — the gate fails on leakage;
        # the interactive-SLO-under-2x-load, preempt-resume-id-exact
        # and exactly-once-terminal measurements are gate_overload's
        # live drill
        "overload": _overload_section(),
        # distributed linear-algebra family (veles_tpu/linalg/): the
        # training bench never runs blocked kernels or solvers, so
        # every linalg counter MUST read zero here — the gate fails on
        # leakage; the blocked-vs-dense residual, dtype-correct MFU
        # and predicted-vs-measured measurements are gate_linalg's
        # live proof (and `python bench.py linalg` standalone)
        "linalg": _linalg_section(),
        # fleet watchtower (telemetry/timeseries.py + alerts.py): the
        # bench never starts the watch sampler or the alert engine
        # (root.common.telemetry.watch.enabled defaults OFF and off
        # must be bit-identical to the pre-watchtower plane), so every
        # sample/eval/transition counter MUST read zero here — the
        # gate fails on leakage; the storm-fires-burn-rate-alert-
        # within-the-fast-window, resolve-after-heal and
        # transitions-visible-everywhere measurements are
        # gate_watch's live drill
        "watch": _watch_section(),
        # tensor-parallel serving (serving/engine.py tp= knob): the
        # bench trains and serves solo (tp=1), so the shard_map
        # engine/dispatch counters MUST read zero here — the gate
        # fails on leakage; the sharded-vs-solo id-exactness and
        # per-chip throughput measurements are gate_tp's live proof
        # on a 2-chip CPU virtual mesh (subprocess: the mesh needs
        # TPU_VISIBLE_CHIPS set before jax initializes)
        "tp_serving": _tp_section(),
        "extras": [ae, lm],
    }


def _overlap_section():
    """{enabled, sideplane_tasks, prefetch_hits, stall_seconds} for
    this bench process — absolute counter reads, since the whole bench
    is one process and the counters start at zero."""
    from veles_tpu.config import root as vt_root
    from veles_tpu.telemetry.counters import counters
    return {
        "enabled": bool(vt_root.common.overlap.get("enabled", False)),
        "sideplane_tasks": int(
            counters.get("veles_sideplane_tasks_total")),
        "prefetch_hits": int(counters.get("veles_prefetch_hits_total")),
        "stall_seconds": round(
            counters.get("veles_sideplane_stall_seconds_total")
            + counters.get("veles_prefetch_stall_seconds_total"), 6),
    }


def _serving_section():
    """{engine, admitted, tokens, decode_dispatches, prefill_dispatches,
    expired, pages_alloc, pages_total, pages_in_use, sustained_slots,
    histogram_samples, ttft_p50, ttft_p99, tpot_p50, queue_wait_p99}
    for this bench process — absolute counter reads (one process,
    counters start at zero) plus the paged-pool occupancy of any LIVE
    engine (none during a training bench, so the page stamps read 0)
    plus the request-plane SLO quantiles from the histogram registry
    (null + zero samples in a non-serving bench; a serving-mode
    document carries real p50/p99 TTFT for the gate to regress
    against). The bench itself never serves, so a non-zero count here
    means serving-engine work leaked into a training measurement —
    ``bench.py gate`` fails on it."""
    from veles_tpu import serving as vt_serving
    from veles_tpu.config import root as vt_root
    from veles_tpu.serving import SERVING_HISTOGRAMS
    from veles_tpu.telemetry.counters import counters, histograms
    pages_total = pages_in_use = sustained = 0
    for _name, engine in sorted(vt_serving.engines().items()):
        st = engine.stats()
        pages_total += int(st["pages_total"])
        pages_in_use += int(st["pages_in_use"])
        sustained = max(sustained, int(st["peak_slots"]))

    def q(name, quant):
        val = histograms.quantile(name, quant)
        return None if val is None else round(val, 6)

    return {
        "engine": str(vt_root.common.serving.get("engine",
                                                 "continuous")),
        # False: this document is a TRAINING bench and the gate holds
        # it to zero serving activity. A serving-mode bench (one that
        # serves on purpose and stamps real latency quantiles) flips
        # this True — the gate then SKIPS the leakage checks for the
        # doc and engages the ttft_p99/queue_wait_p99 regression
        # comparison instead.
        "serving_bench": False,
        "admitted": int(counters.get("veles_serving_admitted_total")),
        "tokens": int(counters.get("veles_serving_tokens_total")),
        "decode_dispatches": int(
            counters.get("veles_serving_decode_dispatches_total")),
        "prefill_dispatches": int(
            counters.get("veles_serving_prefill_dispatches_total")),
        "expired": int(counters.get("veles_serving_expired_total")),
        "pages_alloc": int(
            counters.get("veles_serving_pages_alloc_total")),
        "pages_total": pages_total,
        "pages_in_use": pages_in_use,
        "sustained_slots": sustained,
        "histogram_samples": sum(histograms.count(n)
                                 for n in SERVING_HISTOGRAMS),
        "ttft_p50": q("veles_serving_ttft_seconds", 0.5),
        "ttft_p99": q("veles_serving_ttft_seconds", 0.99),
        "tpot_p50": q("veles_serving_tpot_seconds", 0.5),
        "queue_wait_p99": q("veles_serving_queue_wait_seconds", 0.99),
        # serving-plane MFU stamps (telemetry/devtime.py measure +
        # CostModel program pricing): null in a training bench — the
        # decode-tick and chunked-prefill windows are measured live
        # inside gate_serving's throughput proof, which prices each
        # window as sum(cost_of_compiled(program).flops x dispatch
        # delta) over device self-time and the stamped nominal peak
        "decode_mfu_device": None,
        "prefill_chunk_mfu_device": None,
    }


def _prefix_section():
    """{hits, misses, shared_pages, cow_copies, evictions} for this
    bench process — absolute counter reads (one process, counters
    start at zero). The bench never serves, so every count MUST be
    zero — ``bench.py gate`` fails on leakage. The live prefix proof
    (share-ratio-bounded prefill-FLOP reduction over the actual
    compiled programs, streamed TTFT < full-response latency, chunked
    prefill bounding the in-flight decode stall) runs inside
    ``gate_prefix``."""
    from veles_tpu.telemetry.counters import counters
    return {
        "hits": int(counters.get("veles_prefix_hits_total")),
        "misses": int(counters.get("veles_prefix_misses_total")),
        "shared_pages": int(
            counters.get("veles_prefix_shared_pages_total")),
        "cow_copies": int(
            counters.get("veles_prefix_cow_copies_total")),
        "evictions": int(
            counters.get("veles_prefix_evictions_total")),
    }


def _o1state_section():
    """{checkpoints, restores, restored_tokens, rescans, evictions}
    for this bench process — absolute counter reads (one process,
    counters start at zero). The bench never serves the O(1)-state
    recurrent lane, so every count MUST be zero — ``bench.py gate``
    fails on leakage. The live proof (decode state bytes FLAT vs
    token count, pooled scan-prefill + recurrent-decode id-exact vs
    the solo sampler, >= 4x slots at equal HBM vs the paged
    transformer pool) runs inside ``gate_o1state``."""
    from veles_tpu.telemetry.counters import counters
    return {
        "checkpoints": int(
            counters.get("veles_o1_state_checkpoints_total")),
        "restores": int(
            counters.get("veles_o1_state_restores_total")),
        "restored_tokens": int(
            counters.get("veles_o1_state_restored_tokens_total")),
        "rescans": int(
            counters.get("veles_o1_state_rescans_total")),
        "evictions": int(
            counters.get("veles_o1_state_evictions_total")),
    }


def _fleet_section():
    """{requests, attempts, failovers, replica_errors, breaker_opens,
    duplicate_answers, respawns} for this bench process — absolute
    counter reads (one process, counters start at zero). The bench
    never runs a fleet router, so every count MUST be zero —
    ``bench.py gate`` fails on leakage. The live failover proof (a
    2-replica fleet under an injected replica kill answering every
    request exactly once) runs inside ``gate_fleet`` and stamps its
    failover count there."""
    from veles_tpu.telemetry.counters import counters
    return {
        "requests": int(counters.get("veles_router_requests_total")),
        "attempts": int(counters.get("veles_router_attempts_total")),
        "failovers": int(counters.get("veles_router_failovers_total")),
        "replica_errors": int(
            counters.get("veles_router_replica_errors_total")),
        "breaker_opens": int(
            counters.get("veles_router_breaker_opens_total")),
        "duplicate_answers": int(
            counters.get("veles_router_duplicate_answers_total")),
        "respawns": int(counters.get("veles_router_respawns_total")),
    }


def _overload_section():
    """Every QoS + loadgen counter for this bench process — absolute
    reads (one process, counters start at zero). The bench never runs
    QoS admission, preemption, brownout or the load harness, so every
    count MUST be zero — ``bench.py gate`` fails on leakage (QoS-off
    runs must be bit-identical to the QoS-less plane). The live
    overload drill (a 2-replica fleet at ~2x sustained capacity
    keeping interactive within SLO while batch is throttled/
    preempted, preempted decodes finishing id-exact, exactly one
    terminal per admitted request) runs inside ``gate_overload``."""
    from veles_tpu.loadgen import LOADGEN_COUNTERS
    from veles_tpu.serving import QOS_COUNTERS
    from veles_tpu.telemetry.counters import counters
    short = lambda n: n[len("veles_"):-len("_total")]  # noqa: E731
    return {short(name): int(counters.get(name))
            for name in QOS_COUNTERS + LOADGEN_COUNTERS}


def _watch_section():
    """{enabled} + every watchtower counter for this bench process —
    absolute reads (one process, counters start at zero). The bench
    never starts the watch sampler thread or the alert rule engine
    (``root.common.telemetry.watch.enabled`` defaults OFF, and off
    means the sampler never spawns, ``/metrics`` renders byte-
    identical and no ``veles_watch_*``/``veles_alert_*`` counter ever
    moves), so every count MUST be zero — ``bench.py gate`` fails on
    leakage. The live drill (a chaos storm burning the TTFT SLO until
    ``slo_ttft_burn`` fires within its fast window, then healing until
    it resolves, with every transition visible in /metrics/history,
    the flight recorder and a ``veles-tpu watch`` snapshot) runs
    inside ``gate_watch``."""
    from veles_tpu.config import root as vt_root
    from veles_tpu.telemetry import WATCH_COUNTERS
    from veles_tpu.telemetry.counters import counters
    short = lambda n: n[len("veles_"):-len("_total")]  # noqa: E731
    out = {"enabled": bool(
        vt_root.common.telemetry.watch.get("enabled", False))}
    out.update({short(name): int(counters.get(name))
                for name in WATCH_COUNTERS})
    return out


def _tp_section():
    """{tp, engines, dispatches, autotune_stale} for this bench
    process — absolute counter reads (one process, counters start at
    zero). The bench never starts a tensor-parallel engine (the
    ``root.common.serving.tp`` knob defaults 1, and tp=1 runs the
    exact pre-mesh jit path), so ``engines``/``dispatches`` MUST be
    zero — ``bench.py gate`` fails on leakage. ``autotune_stale`` is
    stamped for visibility only: a real-TPU bench may legitimately
    look up pre-stamp kernel_tuning entries. The live proof (sharded
    decode id-exact vs solo on a 2-device CPU virtual mesh, per-chip
    tokens/sec above the stated fraction of solo) runs inside
    ``gate_tp``'s subprocess."""
    from veles_tpu.config import root as vt_root
    from veles_tpu.telemetry.counters import counters
    return {
        "tp": int(vt_root.common.serving.get("tp", 1) or 1),
        "engines": int(counters.get("veles_tp_engines_total")),
        "dispatches": int(counters.get("veles_tp_dispatches_total")),
        "autotune_stale": int(
            counters.get("veles_autotune_stale_total")),
    }


def _linalg_section():
    """Every distributed linear-algebra counter for this bench process
    — absolute reads (one process, counters start at zero). The bench
    trains neural nets and never dispatches a blocked kernel or runs a
    solver, so every count MUST be zero — ``bench.py gate`` fails on
    leakage. The live proof (blocked matmul / Cholesky solve / CG on
    the Poisson operator matching the dense reference within stated
    tolerance, MFU graded against the f32 peak, predicted-vs-measured
    SUMMA step time) runs inside ``gate_linalg`` and stamps its
    numbers there. ``linalg_bench`` marks a document produced by
    ``bench.py linalg`` where nonzero counts are the point."""
    from veles_tpu.linalg import LINALG_COUNTERS
    from veles_tpu.telemetry.counters import counters
    short = lambda n: n[len("veles_linalg_"):-len("_total")]  # noqa: E731
    out = {"linalg_bench": False}
    out.update((short(name), int(counters.get(name)))
               for name in LINALG_COUNTERS)
    return out


def _lossless_section():
    """{journal_appends, journal_replayed, journal_salvaged,
    journal_compactions, resume_attempts, resume_tokens,
    handoff_requests} for this bench process — absolute counter reads
    (one process, counters start at zero). The bench never runs a
    journaled router, resumes a decode or drains by handoff, so every
    count MUST be zero — ``bench.py gate`` fails on leakage. The live
    resumed-decode proof runs inside ``gate_lossless``."""
    from veles_tpu.telemetry.counters import counters
    return {
        "journal_appends": int(
            counters.get("veles_journal_appends_total")),
        "journal_replayed": int(
            counters.get("veles_journal_replayed_total")),
        "journal_salvaged": int(
            counters.get("veles_journal_salvaged_total")),
        "journal_compactions": int(
            counters.get("veles_journal_compactions_total")),
        "resume_attempts": int(
            counters.get("veles_resume_attempts_total")),
        "resume_tokens": int(
            counters.get("veles_resume_tokens_total")),
        "handoff_requests": int(
            counters.get("veles_handoff_requests_total")),
    }


def _tracing_section():
    """{requests_traced, request_spans, span_pulls, rotations,
    fleet_merges} for this bench process — absolute reads (one
    process, counters start at zero). The bench never serves or
    routes, so the request-plane span count in the ring and every
    tracing counter MUST be zero — ``bench.py gate`` fails on
    leakage (``requests_traced`` is the config switch, information
    not leakage)."""
    from veles_tpu.config import root as vt_root
    from veles_tpu.telemetry.counters import counters
    from veles_tpu.telemetry.spans import recorder as span_recorder
    request_spans = sum(
        1 for r in span_recorder.records()
        if str(r.get("name", "")).startswith(("request", "route.")))
    return {
        "requests_traced": bool(
            vt_root.common.trace.get("requests", True)),
        "request_spans": int(request_spans),
        "span_pulls": int(
            counters.get("veles_trace_span_pulls_total")),
        "rotations": int(counters.get("veles_trace_rotations_total")),
        "fleet_merges": int(
            counters.get("veles_trace_fleet_merges_total")),
    }


def _quant_section():
    """{weights, kv, granularity, artifact, params_quantized,
    bytes_saved, calibrations, artifact_loads, artifact_load_failures}
    for this bench process — absolute counter reads (one process,
    counters start at zero). The bench itself runs quant-off with no
    artifact, so every count here MUST be zero — ``bench.py gate``
    fails on leakage."""
    from veles_tpu.config import root as vt_root
    from veles_tpu.quant import policy
    from veles_tpu.telemetry.counters import counters
    pol = policy()
    return {
        "weights": pol["weights"],
        "kv": pol["kv"],
        "granularity": pol["granularity"],
        "artifact": str(vt_root.common.serving.get("artifact", "")
                        or ""),
        "params_quantized": int(
            counters.get("veles_quant_params_total")),
        "bytes_saved": int(
            counters.get("veles_quant_bytes_saved_total")),
        "calibrations": int(
            counters.get("veles_quant_calibrations_total")),
        "artifact_loads": int(
            counters.get("veles_artifact_loads_total")),
        "artifact_load_failures": int(
            counters.get("veles_artifact_load_failures_total")),
    }


def _elastic_section():
    """{enabled, generations, preemptions, reshard_seconds,
    barrier_timeouts, cursor_defaults} for this bench process —
    absolute counter reads (one process, counters start at zero). The
    bench never runs elastic, so every count MUST be zero —
    ``bench.py gate`` fails on leakage and, in elastic documents,
    bounds the per-handoff reshard time."""
    from veles_tpu.resilience import elastic as vt_elastic
    from veles_tpu.telemetry.counters import counters
    return {
        "enabled": bool(vt_elastic.enabled()),
        "generations": int(
            counters.get("veles_elastic_generations_total")),
        "preemptions": int(
            counters.get("veles_elastic_preemptions_total")),
        "reshard_seconds": round(
            counters.get("veles_elastic_reshard_seconds_total"), 6),
        "barrier_timeouts": int(
            counters.get("veles_elastic_barrier_timeouts_total")),
        "cursor_defaults": int(
            counters.get("veles_manifest_cursor_defaults_total")),
    }


def _tensormon_section():
    """{enabled, samples, nan_total, blackbox_dumps, recorder_events}
    for this bench process — absolute counter reads, like the overlap
    section (one process, counters start at zero)."""
    from veles_tpu.config import root as vt_root
    from veles_tpu.telemetry.counters import counters
    from veles_tpu.telemetry.recorder import flight
    return {
        "enabled": bool(
            vt_root.common.telemetry.tensormon.get("enabled", False)),
        "samples": int(counters.get("veles_tensormon_samples_total")),
        "nan_total": int(counters.get("veles_model_nan_total")),
        "blackbox_dumps": int(
            counters.get("veles_blackbox_dumps_total")),
        "recorder_events": int(flight.stats()["recorded"]),
    }


def _section_pairs(baseline_doc, current_doc):
    """(name, baseline section, current section) triples — the
    headline document itself plus extras matched by metric name —
    shared by the counter gate and the device-time gate so both walk
    the same sections."""
    pairs = [("headline", baseline_doc or {}, current_doc or {})]
    base_extras = {e.get("metric"): e
                   for e in (baseline_doc or {}).get("extras", [])
                   if isinstance(e, dict)}
    for extra in (current_doc or {}).get("extras", []):
        if not isinstance(extra, dict):
            continue
        base = base_extras.get(extra.get("metric"))
        if base is None:
            continue
        pairs.append((extra.get("metric"), base, extra))
    return pairs


def gate_docs(baseline_doc, current_doc):
    """Counter-based perf gate between two BENCH_*.json documents:
    compares the deterministic ``counters`` records (headline +
    extras matched by metric name) and returns failure strings (empty
    = pass). This is the gate that stays meaningful when the host is
    noisy: an extra dispatch per token or an unexpected recompile
    fails exactly, no matter what wall-clock did. Sections without
    counters (legacy baselines, skipped extras) are ignored —
    the gate can only tighten as baselines regenerate."""
    from veles_tpu.telemetry import gate_counters
    failures = []
    for name, base, cur in _section_pairs(baseline_doc, current_doc):
        base_c = base.get("counters") or {}
        cur_c = cur.get("counters") or {}
        if not base_c or not cur_c:
            continue
        # decode sections carry dispatches_per_token; >1 means the
        # scan degenerated to per-token dispatch (the round-5 finding)
        ceiling = (1.0 if "dispatches_per_token" in cur_c else None)
        for failure in gate_counters(
                cur_c, base_c, max_dispatches_per_token=ceiling):
            failures.append("%s: %s" % (name, failure))
    return failures


def _section_rate(sec):
    """The section's primary wall-clock throughput — what the counted
    LEGACY fallback compares when a document predates the device-time
    format."""
    for key in ("samples_per_sec_per_chip", "tokens_per_sec_per_chip",
                "value"):
        v = sec.get(key)
        if isinstance(v, (int, float)):
            return float(v)
    return None


def _doc_on_cpu(doc):
    plat = str(doc.get("platform", ""))
    return doc.get("smoke") or plat in ("cpu", "numpy")


def gate_devtime(baseline_doc=None, current_doc=None):
    """``devtime`` gate section — THE timing gate (ISSUE 9 /
    ROADMAP 5): (1) the measurement-plane counters must be
    registered; (2) every section pair is compared on its
    ``device_time_per_epoch`` with the stated
    :data:`~veles_tpu.telemetry.devtime.DEVTIME_TOLERANCE` when both
    sides were profiler-captured on a chip; host-sync-sourced records
    compare at the loose wall-clock tolerance (the measurement
    already counted its fallback); (3) on CPU/smoke documents the
    gate proves the harness invariants instead of timing ratios
    (fields present, device time positive, wall ≥ device, known
    source); (4) legacy documents without ``device_time_s`` never
    crash the gate — their sections compare wall-clock rates with a
    counted ``veles_bench_legacy_sections_total`` warning."""
    from veles_tpu.telemetry import devtime as _devtime
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in _devtime.DEVTIME_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "devtime: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    on_cpu = (_doc_on_cpu(baseline_doc or {})
              or _doc_on_cpu(current_doc or {}))
    for name, base, cur in _section_pairs(baseline_doc, current_doc):
        base_dt = base.get("devtime")
        cur_dt = cur.get("devtime")
        base_rate = _section_rate(base)
        cur_rate = _section_rate(cur)
        if (cur_dt is None and cur_rate is None) \
                or (base_dt is None and base_rate is None):
            continue      # skipped/pending/error stubs: no timing to
            # compare and no format claim to enforce
        smoke = bool(base.get("smoke") or cur.get("smoke"))
        timing = not (on_cpu or smoke)
        both_prof = (bool(base_dt) and bool(cur_dt)
                     and base_dt.get("source") == "profiler"
                     and cur_dt.get("source") == "profiler")
        tol = (_devtime.DEVTIME_TOLERANCE if both_prof
               else _devtime.LEGACY_TOLERANCE)
        for failure in _devtime.compare_sections(
                name, base_dt, cur_dt,
                # rates are only comparable method-to-method: a CPU
                # smoke against a chip baseline is the vs_baseline=null
                # rule, not a regression — legacy sections still COUNT
                # either way
                base_rate=base_rate if timing else None,
                cur_rate=cur_rate if timing else None,
                timing=timing, tolerance=tol):
            failures.append("devtime: %s" % failure)
    return failures


def gate_resilience():
    """``resilience`` gate section: the fault/retry/shed counters must
    be REGISTERED (HELP strings exist) and show zero leakage in a clean
    process — firing every registered injection point with no fault
    spec armed must be a no-op. A chaos run (VELES_FAULTS set) skips
    the zero check: counting faults is then the whole point."""
    from veles_tpu.resilience import RESILIENCE_COUNTERS, faults
    from veles_tpu.telemetry.counters import DESCRIPTIONS, counters
    failures = []
    for name in RESILIENCE_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "resilience: counter %s not registered in "
                "telemetry DESCRIPTIONS" % name)
    if faults.plane.active():
        return failures
    for point in faults.list_points():
        faults.fire(point)
    for name in RESILIENCE_COUNTERS:
        value = counters.get(name)
        if value:
            failures.append(
                "resilience: %s = %s in a clean run — a fault/retry/"
                "shed path fired with no fault spec set" % (name, value))
    return failures


#: reshard-time budget per elastic generation (seconds): each
#: generation restores at most once — a fresh job's first generation
#: restores nothing, but a RESPAWNED worker's first (local) generation
#: legitimately does, so the budget is per generation, not per
#: handoff. A restore+reshard is one chain read + device_puts —
#: minutes would mean the elastic plane re-initializes far more than
#: it restores
ELASTIC_RESHARD_BUDGET_S = 60.0


def gate_elastic(baseline_doc=None, current_doc=None):
    """``elastic`` gate section: (1) the generation/preemption/reshard
    counters must be registered; (2) a non-elastic bench document must
    carry ZERO elastic activity — generation machinery leaking into a
    plain run means restores happened inside a perf window; (3) an
    elastic document's reshard time must stay inside the
    per-generation budget (each generation restores at most once:
    its handoff in)."""
    from veles_tpu.resilience.elastic import ELASTIC_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in ELASTIC_COUNTERS + (
            "veles_manifest_cursor_defaults_total",):
        if name not in DESCRIPTIONS:
            failures.append(
                "elastic: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc), ("current", current_doc)):
        sec = (doc or {}).get("elastic")
        if not sec:
            continue
        if not sec.get("enabled"):
            for key in ("generations", "preemptions",
                        "barrier_timeouts", "cursor_defaults"):
                if sec.get(key):
                    failures.append(
                        "elastic: %s doc has %s=%s with elastic OFF — "
                        "generation machinery leaked into a plain run"
                        % (tag, key, sec[key]))
            if sec.get("reshard_seconds"):
                failures.append(
                    "elastic: %s doc spent %.3fs resharding with "
                    "elastic OFF" % (tag, sec["reshard_seconds"]))
        else:
            generations = max(1, int(sec.get("generations", 0)))
            budget = ELASTIC_RESHARD_BUDGET_S * generations
            spent = float(sec.get("reshard_seconds", 0.0))
            if spent > budget:
                failures.append(
                    "elastic: %s doc reshard_seconds=%.3f exceeds the "
                    "%.0fs budget for %d generation(s)"
                    % (tag, spent, budget, generations))
    return failures


def gate_overlap(baseline_doc=None, current_doc=None):
    """``overlap`` gate section: (1) the side-plane/prefetch counters
    must be registered; (2) an overlap-OFF bench document must carry
    ZERO side-plane activity — async machinery leaking into the serial
    path is a determinism bug; (3) stall_seconds may not regress
    between two overlap-ON documents; (4) live proof that the
    overlapped configuration stalls LESS than the serial one (the
    whole point of the engine)."""
    from veles_tpu.overlap import OVERLAP_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in OVERLAP_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "overlap: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc), ("current", current_doc)):
        sec = (doc or {}).get("overlap")
        if not sec or sec.get("enabled"):
            continue
        for key in ("sideplane_tasks", "prefetch_hits"):
            if sec.get(key):
                failures.append(
                    "overlap: %s doc has %s=%s with overlap OFF — "
                    "side-plane work leaked into the serial path"
                    % (tag, key, sec[key]))
    base_sec = (baseline_doc or {}).get("overlap") or {}
    cur_sec = (current_doc or {}).get("overlap") or {}
    if base_sec.get("enabled") and cur_sec.get("enabled"):
        base_stall = base_sec.get("stall_seconds")
        cur_stall = cur_sec.get("stall_seconds")
        # 1.5x + 100ms: stall is wall-clock, leave jitter headroom —
        # a real regression (lost overlap) is a many-x move
        if (base_stall is not None and cur_stall is not None
                and cur_stall > base_stall * 1.5 + 0.1):
            failures.append(
                "overlap: stall_seconds regressed %.3f -> %.3f"
                % (base_stall, cur_stall))
    return failures + _overlap_stall_proof()


def _overlap_stall_proof():
    """Measure the same producer/consumer pair serially and through
    the Prefetcher; the overlapped configuration must report lower
    stall_seconds. Consumer work (6 ms) > producer work (3 ms), so in
    steady state the staged batch is always ready: serial stall ≈
    N x 3 ms, overlapped ≈ one initial miss — a 10x+ margin over
    scheduler jitter."""
    import time as _t
    from veles_tpu.overlap import Prefetcher
    from veles_tpu.telemetry.counters import counters
    n, produce_s, consume_s = 24, 0.003, 0.006

    def batches():
        for i in range(n):
            _t.sleep(produce_s)     # the host-side gather being hidden
            yield i

    serial_stall = 0.0
    it = batches()
    for _ in range(n):
        t0 = _t.time()
        next(it)
        serial_stall += _t.time() - t0
        _t.sleep(consume_s)         # the device step
    before = counters.snapshot()
    try:
        with Prefetcher(batches(), depth=4, name="bench.overlap") as pf:
            for _ in range(n):
                pf.get(timeout=30)
                _t.sleep(consume_s)
    except TimeoutError as e:
        # a wedged producer is a gate FAILURE line, not a traceback
        return ["overlap: stall proof prefetcher wedged (%s)" % e]
    delta = counters.delta(before)
    overlapped_stall = delta.get("veles_prefetch_stall_seconds_total",
                                 0.0)
    failures = []
    if not delta.get("veles_prefetch_hits_total"):
        failures.append("overlap: prefetcher served no hits in the "
                        "stall proof")
    if overlapped_stall >= serial_stall:
        failures.append(
            "overlap: prefetch did not reduce stall (serial %.4fs vs "
            "overlapped %.4fs)" % (serial_stall, overlapped_stall))
    return failures


#: max allowed current/baseline ratio for the serving latency
#: quantiles (ttft_p99, queue_wait_p99) when BOTH documents stamp
#: them. Generous on purpose: these are wall-clock quantiles on a
#: shared box — the gate
#: catches order-of-magnitude SLO collapses, the counter gates catch
#: program regressions exactly.
SERVING_LATENCY_TOLERANCE = 2.5


def gate_serving(baseline_doc=None, current_doc=None):
    """``serving`` gate section: (1) the continuous-batching counters
    AND the request-plane SLO histograms must be registered; (2) bench
    documents must carry ZERO serving activity — including zero
    latency-histogram samples — the bench never serves, so a non-zero
    count means engine work leaked into a training measurement;
    (3) the clean gate process itself must read zero before the
    proof; (4) TTFT/queue-wait p99 regression between documents that
    both stamp them — documents that declare ``serving_bench: true``
    serve on purpose, skip the leakage checks and are gated on their
    latency quantiles instead (today's training bench stamps
    ``serving_bench: false`` + null quantiles and takes the leakage
    path); (5) live proofs: continuous batching strictly beats
    the window-coalescing baseline on tokens/sec under a mixed-length
    concurrent load (greedy AND sampled rows id-exact vs their solo
    decodes, jit programs bounded by len(buckets)+1), with per-request
    TTFT/TPOT/queue-wait histograms recorded for every request and
    quantiles internally consistent, the paged pool sustains strictly
    more concurrent slots than the dense configuration at the same
    pool HBM, and pooled speculation + beam beat their window-plane
    baselines on a fresh-shape load with zero new compiles."""
    from veles_tpu.serving import SERVING_COUNTERS, SERVING_HISTOGRAMS
    from veles_tpu.telemetry.counters import (DESCRIPTIONS, HISTOGRAMS,
                                              counters, histograms)
    failures = []
    for name in SERVING_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "serving: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for name in SERVING_HISTOGRAMS:
        entry = HISTOGRAMS.get(name)
        if not entry or not entry.get("help") \
                or not entry.get("buckets"):
            failures.append(
                "serving: histogram %s not registered in telemetry "
                "HISTOGRAMS with help + buckets" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("serving")
        if not sec:
            continue
        if sec.get("serving_bench"):
            # a self-declared serving-mode document: serving activity
            # and latency samples are the MEASUREMENT, not a leak —
            # the latency regression comparison below is its gate
            continue
        for key in ("admitted", "tokens", "decode_dispatches",
                    "pages_alloc"):
            if sec.get(key):
                failures.append(
                    "serving: %s doc has %s=%s — serving-engine work "
                    "leaked into a non-serving bench run"
                    % (tag, key, sec[key]))
        # zero-leakage for the SLO layer too: a non-serving bench must
        # stamp zero histogram samples (a sample means a Ticket
        # terminated inside a training measurement)
        if sec.get("histogram_samples"):
            failures.append(
                "serving: %s doc has histogram_samples=%s — latency "
                "histograms leaked into a non-serving bench run"
                % (tag, sec["histogram_samples"]))
    # TTFT/queue-wait SLO regression between docs that BOTH carry
    # stamps (serving-mode documents; legacy/non-serving stamp null)
    base_sec = (baseline_doc or {}).get("serving") or {}
    cur_sec = (current_doc or {}).get("serving") or {}
    for key in ("ttft_p99", "queue_wait_p99"):
        base_v, cur_v = base_sec.get(key), cur_sec.get(key)
        if base_v and cur_v \
                and cur_v > SERVING_LATENCY_TOLERANCE * base_v:
            failures.append(
                "serving: %s regressed %.6fs -> %.6fs (>%.1fx "
                "tolerance)" % (key, base_v, cur_v,
                                SERVING_LATENCY_TOLERANCE))
    # the zero check must precede the live proof (which serves for
    # real and legitimately moves every one of these counters)
    for name in SERVING_COUNTERS:
        value = counters.get(name)
        if value:
            failures.append(
                "serving: %s = %s before any serving ran in this "
                "process" % (name, value))
    for name in SERVING_HISTOGRAMS:
        value = histograms.count(name)
        if value:
            failures.append(
                "serving: histogram %s holds %d samples before any "
                "serving ran in this process" % (name, value))
    return failures + _serving_throughput_proof()


def _serving_throughput_proof():
    """Serve the same mixed-length concurrent load through the
    window-coalescing baseline (the shipped batch_window worker
    semantics: coalesce 20 ms, group by exact shape key, one batched
    decode per group — mixed lengths degrade every group to a solo
    decode) and through the continuous-batching engine (slot-pool
    admission at chunk boundaries). Continuous must strictly win on
    tokens/sec, every row must be id-exact vs its solo decode (greedy
    AND sampled — the per-slot PRNG contract), and the engine may
    build at most len(buckets)+1 jitted programs. Runs on the CPU
    backend unless the caller pinned JAX_PLATFORMS."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import time as _t
    import numpy
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.nn import sampling
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request

    prng.seed_all(4242)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    # the mixed-length load the window coalescer is worst at: distinct
    # (prompt length, n_new, temp, seed) shapes never share a batch
    # key, so every request decodes solo; half the rows are
    # stochastic. 32 requests (4 pool waves) so a scheduling hiccup
    # on this shared box cannot swamp the measurement
    lengths = [5, 9, 14, 7, 12, 16, 6, 11, 13, 8, 15, 10, 5, 12, 9,
               14] * 2
    n_news = [8, 12, 6, 10, 16, 11, 9, 14]
    rng = numpy.random.RandomState(17)
    reqs = []
    for i, t_p in enumerate(lengths):
        prompt = [int(t) for t in rng.randint(0, char_lm.VOCAB, t_p)]
        reqs.append(make_request(
            prompt, n_news[i % len(n_news)],
            temperature=0.7 if i % 2 else 0.0, seed=100 + i))
    total_tokens = sum(r["n_new"] for r in reqs)
    failures = []
    engine = ContinuousEngine(wf, max_slots=8, buckets=(8, 16),
                              max_context=32, decode_block=8,
                              name="bench.serving")
    engine.start()
    try:
        # solo pass: warms every bucket program + the decode step AND
        # yields the id-exactness reference
        solo = [engine.serve([r])[0] for r in reqs]
        # window-baseline warmup: one compile per distinct shape key
        groups = {}
        for r in reqs:
            key = (len(r["prompt"]), r["n_new"], r["temperature"],
                   r["seed"])
            groups.setdefault(key, []).append(r)

        def run_window_baseline():
            _t.sleep(0.02)          # the shipped batch_window
            out = []
            for group in groups.values():
                prompts = [g["prompt"] for g in group]
                rows = sampling.generate(
                    wf, prompts if len(prompts) > 1 else prompts[0],
                    group[0]["n_new"],
                    temperature=group[0]["temperature"],
                    seed=group[0]["seed"])
                out.extend(rows if len(prompts) > 1 else [rows])
            return out

        run_window_baseline()       # warm the per-shape executables
        base_times, cont_times = [], []
        for _ in range(3):
            t0 = _t.time()
            run_window_baseline()
            base_times.append(_t.time() - t0)
            t0 = _t.time()
            conc = engine.serve(list(reqs))
            cont_times.append(_t.time() - t0)
        for i, (a, b) in enumerate(zip(solo, conc)):
            if a != b:
                failures.append(
                    "serving: request %d (temp %.1f) not id-exact vs "
                    "its solo decode under concurrent load"
                    % (i, reqs[i]["temperature"]))
                break
        bound = len(engine.buckets) + 1
        if engine.programs_built > bound:
            failures.append(
                "serving: engine built %d jitted programs, bound is "
                "len(buckets)+1 = %d" % (engine.programs_built, bound))
        # best-of-3 on BOTH planes: the minimum wall-clock is the
        # least-interference estimate on a shared box (symmetric, so
        # neither plane profits from the other's noisy run)
        base_tps = total_tokens / min(base_times)
        cont_tps = total_tokens / min(cont_times)
        if cont_tps <= base_tps:
            failures.append(
                "serving: continuous batching did not beat the window "
                "baseline (%.0f vs %.0f tokens/sec)"
                % (cont_tps, base_tps))
        else:
            print("serving proof: continuous %.0f tokens/sec vs "
                  "window-coalescing %.0f (%.2fx), %d programs"
                  % (cont_tps, base_tps, cont_tps / base_tps,
                     engine.programs_built))
        # request-plane SLO accounting (the histograms the /metrics
        # surfaces and `veles-tpu metrics aggregate` quantile from):
        # every engine-served request must have recorded one TTFT
        # sample and one queue-wait sample, and the bucket-derived
        # quantiles must be internally consistent
        from veles_tpu.telemetry.counters import counters as _ctrs
        from veles_tpu.telemetry.counters import histograms as _hists
        served = int(_ctrs.get("veles_serving_admitted_total"))
        ttft_n = _hists.count("veles_serving_ttft_seconds")
        wait_n = _hists.count("veles_serving_queue_wait_seconds")
        if ttft_n != served:
            failures.append(
                "serving: %d TTFT histogram samples for %d admitted "
                "requests — per-request SLO accounting is broken"
                % (ttft_n, served))
        if wait_n < served:
            failures.append(
                "serving: %d queue-wait samples for %d admitted "
                "requests" % (wait_n, served))
        slo = {}
        for name, label in (("veles_serving_ttft_seconds", "ttft"),
                            ("veles_serving_tpot_seconds", "tpot"),
                            ("veles_serving_queue_wait_seconds",
                             "queue_wait")):
            p50 = _hists.quantile(name, 0.5)
            p99 = _hists.quantile(name, 0.99)
            if p50 is not None and p99 is not None and p50 > p99:
                failures.append(
                    "serving: %s p50 %.6f > p99 %.6f — quantile "
                    "arithmetic is broken" % (label, p50, p99))
            slo[label] = (p50, p99)
        print("serving slo: ttft p50=%.4fs p99=%.4fs, tpot "
              "p50=%.4fs, queue_wait p99=%.4fs over %d requests"
              % (slo["ttft"][0] or 0.0, slo["ttft"][1] or 0.0,
                 slo["tpot"][0] or 0.0, slo["queue_wait"][1] or 0.0,
                 served))
        # decode-tick MFU stamp: one devtime.measure window around a
        # re-serve of the warmed mixed load (decode-step dominated —
        # every program is compiled, so the window is execution only)
        decode_mfu, dec_rec = _serving_window_mfu(
            engine, lambda: engine.serve(list(reqs)))
    finally:
        engine.stop()
    failures += _serving_mfu_stamp(wf, char_lm, reqs, decode_mfu,
                                   dec_rec)
    failures += _paged_occupancy_proof(wf, reqs)
    failures += _pooled_modes_proof(lm=char_lm, wf=wf)
    return failures


def _serving_window_mfu(engine, run):
    """Measure one serving window (``devtime.measure``) and price the
    programs it actually dispatched: ``sum(cost_of_compiled(program)
    .flops x dispatch delta)`` over device self-time and the f32
    nominal peak — the same CostModel-over-devtime arithmetic every
    training section's ``mfu_device`` stamp uses, applied to the
    engine's per-program ``prog_calls`` tally. Measurement only (no
    kernel work, nothing gated), and only on a device with a peak on
    file: on the CPU CI backend there is none, so the MFU is None.
    Returns ``(mfu_or_None, devtime_record)``."""
    from veles_tpu.telemetry import devtime as _devtime
    from veles_tpu.telemetry.cost import cost_of_compiled
    calls0 = dict(engine.prog_calls)
    rec = _devtime.measure(run, sync=lambda: None)
    _, peak = _peak_or_none("float32")
    if peak is None:
        return None, rec
    flops = 0.0
    for key, calls in engine.prog_calls.items():
        delta = calls - calls0.get(key, 0)
        if not delta:
            continue
        prog = engine._progs.get(key)
        exe = prog.compiled() if prog is not None else None
        if exe is None:
            return None, rec       # unpriceable (non-pjit backend)
        flops += cost_of_compiled(exe).flops * delta
    if not flops or rec["device_time_s"] <= 0:
        return None, rec
    return flops / rec["device_time_s"] / peak, rec


def _serving_mfu_stamp(wf, lm, reqs, decode_mfu, dec_rec):
    """The serving-MFU satellite: print the decode-tick window's MFU
    (measured on the throughput engine above) and measure + print the
    chunked-prefill window on its own chunk-enabled engine — long
    prompts, one new token, so ``pchunk`` dispatches dominate. Pure
    measurement (``decode_mfu_device``/``prefill_chunk_mfu_device``
    stamp null in a training bench document); never a gate failure."""
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request
    peak_source, _ = _peak_or_none("float32")
    rng = __import__("numpy").random.RandomState(23)
    long_reqs = [make_request(
        [int(t) for t in rng.randint(0, lm.VOCAB, 24)], 1,
        seed=700 + i) for i in range(4)]
    engine = ContinuousEngine(wf, max_slots=4, buckets=(8, 32),
                              max_context=40, decode_block=8,
                              prefill_chunk=8,
                              name="bench.serving_mfu")
    engine.start()
    try:
        engine.serve([dict(r) for r in long_reqs])   # warm compiles
        chunks0 = engine.chunk_dispatches
        prefill_mfu, pre_rec = _serving_window_mfu(
            engine, lambda: engine.serve(
                [dict(r) for r in long_reqs]))
        chunked = engine.chunk_dispatches - chunks0
    finally:
        engine.stop()
    fmt = lambda v: "n/a" if v is None else "%.4f" % v  # noqa: E731
    print("serving mfu: decode-tick window %s, chunked-prefill "
          "window %s (%d chunk dispatches) — device-time source "
          "%s/%s vs %s peak"
          % (fmt(decode_mfu), fmt(prefill_mfu), chunked,
             dec_rec["source"], pre_rec["source"],
             peak_source or "no (unknown device)"))
    return []


def _paged_occupancy_proof(wf, reqs):
    """The tentpole HBM claim, measured: at the SAME pool HBM
    (16 pages x 8 positions), the dense configuration — every slot
    reserves ``max_context``, so 128 positions fund 4 slots — tops out
    at 4 concurrent rows, while the paged pool admits on each
    request's OWN footprint and sustains strictly more on the same
    mixed-length load."""
    from veles_tpu.serving import ContinuousEngine
    failures = []
    peaks = {}
    for tag, slots in (("dense", 4), ("paged", 8)):
        engine = ContinuousEngine(wf, max_slots=slots, buckets=(8, 16),
                                  max_context=32, decode_block=8,
                                  page_size=8, pages=16,
                                  name="bench.occ_" + tag)
        engine.start()
        try:
            engine.serve(list(reqs))
            peaks[tag] = engine.peak_slots
            st = engine.stats()
            if st["pages_total"] != 16:
                failures.append(
                    "serving: %s occupancy engine reports %s pages, "
                    "configured 16" % (tag, st["pages_total"]))
        finally:
            engine.stop()
    if peaks["paged"] <= peaks["dense"]:
        failures.append(
            "serving: paged pool sustained %d concurrent slots vs "
            "dense %d at the same pool HBM — the paged engine must "
            "strictly win" % (peaks["paged"], peaks["dense"]))
    else:
        print("serving proof: paged pool sustained %d concurrent "
              "slots vs dense %d at the same 16-page HBM"
              % (peaks["paged"], peaks["dense"]))
    return failures


def _pooled_modes_proof(lm, wf):
    """Speculative + beam on the slot pool vs their window-plane
    baselines, on a FRESH-SHAPE load — the arrival pattern serving
    actually sees (prompt lengths and budgets the process has not
    served before). The window plane jit-compiles ``_build_spec_
    sampler`` / ``_build_beam`` once per exact ``(t_p, n_new)`` shape,
    so every fresh shape stalls its request for a full trace+compile;
    the pool's programs are shape-generic (prompts pad to buckets,
    page tables are data), so the same load runs with ZERO new
    compiles — asserted, not assumed. Tokens/sec on the pool must
    strictly win, every pooled answer must be id-exact vs its
    window-plane baseline, and the program count stays within
    ``programs_bound()``."""
    import time as _t
    import numpy
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.nn.beam import beam_generate
    from veles_tpu.nn.speculative import generate_speculative
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request

    prng.seed_all(4243)
    draft = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                              dim=16, n_train=64, n_valid=32)
    draft.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    failures = []
    rng = numpy.random.RandomState(23)
    engine = ContinuousEngine(wf, max_slots=8, buckets=(8, 16),
                              max_context=40, decode_block=8,
                              page_size=8, spec_gamma=4, beam_width=4,
                              draft=draft, name="bench.modes")
    engine.start()
    try:
        # warm every shape-generic pool program (both prefill buckets,
        # draft prefills, the spec round, the beam step) on THROWAWAY
        # shapes — the fresh-shape load below must not be able to
        # trigger a single new trace
        warm = [make_request([1, 2, 3], 4, mode="speculative",
                             gamma=4),
                make_request(list(range(10)), 4, mode="speculative",
                             gamma=4),
                make_request([3, 2, 1], 4, mode="beam", beam=4),
                make_request(list(range(9, -1, -1)), 4, mode="beam",
                             beam=4)]
        engine.serve(warm)
        programs_before = engine.programs_built

        def fresh(t_p, n_new, **kw):
            prompt = [int(t) for t in rng.randint(0, lm.VOCAB, t_p)]
            return make_request(prompt, n_new, **kw)

        spec_reqs = [fresh(t_p, n_new, mode="speculative", gamma=4,
                           seed=300 + t_p)
                     for t_p, n_new in ((5, 10), (9, 8), (7, 12),
                                        (11, 9), (6, 11), (10, 13),
                                        (8, 9), (12, 14))]
        beam_reqs = [fresh(t_p, n_new, mode="beam", beam=4)
                     for t_p, n_new in ((4, 9), (9, 7), (7, 10),
                                        (11, 8))]
        spec_tokens = sum(r["n_new"] for r in spec_reqs)
        beam_tokens = sum(r["n_new"] for r in beam_reqs)
        # window plane first (its outputs are the id-exactness
        # reference): one compile per fresh shape, requests served
        # sequentially after the coalescing window — the shipped
        # batch_window worker's cost profile
        t0 = _t.time()
        _t.sleep(0.02)
        spec_base_out = [generate_speculative(wf, draft, r["prompt"],
                                              r["n_new"], gamma=4)[0]
                         for r in spec_reqs]
        spec_base = spec_tokens / (_t.time() - t0)
        t0 = _t.time()
        _t.sleep(0.02)
        beam_base_out = [beam_generate(wf, r["prompt"], r["n_new"],
                                       beam=4)[0] for r in beam_reqs]
        beam_base = beam_tokens / (_t.time() - t0)
        # the pool serves the SAME fresh shapes through its
        # shape-generic programs
        t0 = _t.time()
        spec_pool_out = engine.serve(list(spec_reqs))
        spec_pool = spec_tokens / (_t.time() - t0)
        t0 = _t.time()
        beam_pool_out = engine.serve(list(beam_reqs))
        beam_pool = beam_tokens / (_t.time() - t0)
        if engine.programs_built != programs_before:
            failures.append(
                "serving: the fresh-shape load grew the pool's jit "
                "cache %d -> %d — programs must be shape-generic"
                % (programs_before, engine.programs_built))
        if engine.programs_built > engine.programs_bound():
            failures.append(
                "serving: modes engine built %d programs, bound is %d"
                % (engine.programs_built, engine.programs_bound()))
        if spec_pool_out != spec_base_out:
            failures.append("serving: pooled speculation not id-exact "
                            "vs its window-plane baseline")
        if beam_pool_out != [[int(t) for t in row]
                             for row in beam_base_out]:
            failures.append("serving: pooled beam not id-exact vs its "
                            "window-plane baseline")
        if spec_pool <= spec_base:
            failures.append(
                "serving: pooled speculation did not beat the window "
                "plane on the fresh-shape load (%.0f vs %.0f "
                "tokens/sec)" % (spec_pool, spec_base))
        if beam_pool <= beam_base:
            failures.append(
                "serving: pooled beam did not beat the window plane "
                "on the fresh-shape load (%.0f vs %.0f tokens/sec)"
                % (beam_pool, beam_base))
        if not failures:
            print("serving proof: fresh-shape load — pooled "
                  "speculation %.0f tokens/sec vs window %.0f "
                  "(%.1fx), pooled beam %.0f vs %.0f (%.1fx); %d "
                  "programs (bound %d), 0 new compiles on the pool"
                  % (spec_pool, spec_base, spec_pool / spec_base,
                     beam_pool, beam_base, beam_pool / beam_base,
                     engine.programs_built, engine.programs_bound()))
    finally:
        engine.stop()
    return failures


def gate_fleet(baseline_doc=None, current_doc=None):
    """``fleet`` gate section: (1) every ``veles_router_*`` counter
    must be registered with a HELP string; (2) bench documents must
    carry ZERO router activity — the bench never routes, so a
    non-zero count means fleet machinery leaked into a training
    measurement; (3) the clean gate process must read zero before the
    proof; (4) live proof: a 2-replica fleet under an injected
    ``serve.replica_death`` kill answers every request exactly once —
    the router opens the breaker, fails the in-flight request over to
    the survivor, the ReplicaSupervisor respawns the dead replica,
    and no request is dropped, double-answered or silently 504'd
    (failover count stamped)."""
    from veles_tpu.serving import ROUTER_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS, counters
    failures = []
    for name in ROUTER_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "fleet: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("fleet")
        if not sec:
            continue
        for key in ("requests", "attempts", "failovers",
                    "replica_errors", "respawns"):
            if sec.get(key):
                failures.append(
                    "fleet: %s doc has %s=%s — router work leaked "
                    "into a non-fleet bench run" % (tag, key,
                                                    sec[key]))
    # the zero check must precede the live proof (which routes for
    # real and legitimately moves every one of these counters)
    for name in ROUTER_COUNTERS:
        value = counters.get(name)
        if value:
            failures.append(
                "fleet: %s = %s before any routing ran in this "
                "process" % (name, value))
    return failures + _fleet_failover_proof()


def _fleet_failover_proof():
    """THE chaos drill, live: two in-process GenerationAPI replicas
    over one tiny LM behind a FleetRouter; ``serve.replica_death`` is
    armed to kill one replica mid-decode partway through the load.
    Every request must come back exactly once with the same tokens
    the solo decode produces (responses keyed by request_id — no
    duplicates, no silent 504s), the router must record at least one
    failover + breaker open, and the ReplicaSupervisor must respawn
    the dead replica (proven by it serving again)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.nn import sampling
    from veles_tpu.resilience import faults
    from veles_tpu.serving.router import (FleetRouter,
                                          ReplicaSupervisor)
    from veles_tpu.telemetry.counters import counters as _ctrs

    prng.seed_all(5151)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8,),
                             max_context=24, name="fleet_bench_%d" % i)
            for i in range(2)]

    class _Handle:
        def __init__(self, api):
            self.api = api

        def poll(self):
            return (None if self.api._service is not None
                    else faults.CRASH_EXIT_CODE)

    def spawn(i, _incarnation):
        apis[i].initialize()
        return _Handle(apis[i])

    failures = []
    rng = numpy.random.RandomState(23)
    prompts = [[int(t) for t in rng.randint(0, char_lm.VOCAB, 5 + i)]
               for i in range(8)]
    expected = [sampling.generate(wf, p, 4, temperature=0)
                for p in prompts]
    sup = ReplicaSupervisor(spawn, 2, poll_interval=0.1,
                            name="fleet_bench")
    saved_spec = os.environ.get("VELES_FAULTS")
    router = None
    try:
        sup.start()
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=1,
            retry_budget=2, attempt_timeout=30.0,
            request_timeout=60.0, name="bench.router").start()
        import json as _json
        import urllib.request as _rq
        url = "http://127.0.0.1:%d/generate" % router.port

        def post(payload):
            import urllib.error as _er
            req = _rq.Request(url,
                              data=_json.dumps(payload).encode(),
                              headers={"Content-Type":
                                       "application/json"})
            try:
                with _rq.urlopen(req, timeout=90) as r:
                    return r.status, _json.loads(r.read())
            except _er.HTTPError as e:
                # a shed/expiry answer IS data for this proof — the
                # non-200 branches below must report it as a GATE
                # FAIL, not crash the gate with a traceback
                try:
                    return e.code, _json.loads(e.read() or b"{}")
                except ValueError:
                    return e.code, {"error": "replica answered %d"
                                    % e.code}

        post({"prompt": prompts[0], "n_new": 4})        # warm
        fo_before = _ctrs.get("veles_router_failovers_total")
        # the 3rd replica-side request dies mid-decode, exactly once
        os.environ["VELES_FAULTS"] = \
            "serve.replica_death:raise:after=2,times=1"
        answers = {}
        for i, prompt in enumerate(prompts):
            status, body = post({"prompt": prompt, "n_new": 4})
            if status != 200:
                failures.append(
                    "fleet: request %d answered %d (%s) — the fleet "
                    "dropped a request" % (i, status,
                                           body.get("error")))
                continue
            rid = body.get("request_id")
            if rid in answers:
                failures.append(
                    "fleet: request_id %s answered twice" % rid)
            answers[rid] = body["tokens"]
            if body["tokens"] != expected[i]:
                failures.append(
                    "fleet: request %d tokens differ from the solo "
                    "decode after failover" % i)
        if len(answers) != len(prompts):
            failures.append(
                "fleet: %d distinct answers for %d requests — "
                "exactly-once accounting broken"
                % (len(answers), len(prompts)))
        failovers = _ctrs.get("veles_router_failovers_total") \
            - fo_before
        if failovers < 1:
            failures.append(
                "fleet: injected replica death caused no failover "
                "(the kill never fired, or the router never "
                "re-routed)")
        if _ctrs.get("veles_router_breaker_opens_total") < 1:
            failures.append(
                "fleet: the dead replica's breaker never opened")
        os.environ.pop("VELES_FAULTS", None)
        # the supervisor must respawn the dead replica, and the
        # respawned replica must actually serve again (wait on the
        # respawn COUNTER — alive() alone is racy while the dying
        # replica's teardown is still in flight)
        rs_before = 0
        deadline = time.time() + 60
        while _ctrs.get("veles_router_respawns_total") - rs_before \
                < 1 and time.time() < deadline:
            time.sleep(0.1)
        deadline = time.time() + 30
        while sup.alive() < 2 and time.time() < deadline:
            time.sleep(0.1)
        if sup.alive() < 2:
            failures.append(
                "fleet: ReplicaSupervisor did not respawn the dead "
                "replica within its deadline")
        respawns = int(_ctrs.get("veles_router_respawns_total"))
        if respawns < 1:
            failures.append("fleet: zero respawns counted after an "
                            "injected replica death")
        router.probe_all()
        status, body = post({"prompt": prompts[0], "n_new": 4})
        if status != 200 or body["tokens"] != expected[0]:
            failures.append(
                "fleet: the fleet cannot serve after the respawn "
                "(%s)" % (body,))
        if not failures:
            print("fleet proof: %d requests exactly-once through an "
                  "injected replica death — %d failover(s), %d "
                  "breaker open(s), %d respawn(s)"
                  % (len(prompts), int(failovers),
                     int(_ctrs.get(
                         "veles_router_breaker_opens_total")),
                     respawns))
    finally:
        if saved_spec is None:
            os.environ.pop("VELES_FAULTS", None)
        else:
            os.environ["VELES_FAULTS"] = saved_spec
        if router is not None:
            router.stop()
        sup.stop()
        for api in apis:
            api.stop()
    return failures


def gate_lossless(baseline_doc=None, current_doc=None):
    """``lossless`` gate section: (1) every journal/resume/handoff
    counter must be registered with a HELP string; (2) bench
    documents must carry ZERO lossless-plane activity — the bench
    never journals, resumes or hands off, so a non-zero count means
    that machinery leaked into a training measurement; (3) live
    proof: a journaled 2-replica fleet under an injected mid-decode
    replica death answers the request id-exactly by RESUMING from
    tokens_done on the survivor, with the resumed decode costing
    fewer FLOPs (CostModel over the actual compiled programs) than a
    full redo — and the journal holds zero pending entries once
    every answer is terminal. Runs AFTER gate_fleet in _gate_main:
    the fleet proof's dying gasps legitimately move the resume
    counters, so this gate asserts deltas, not process-absolute
    zeros."""
    from veles_tpu.serving import LOSSLESS_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in LOSSLESS_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "lossless: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("lossless")
        if not sec:
            continue
        for key, value in sec.items():
            if value:
                failures.append(
                    "lossless: %s doc has %s=%s — journal/resume/"
                    "handoff work leaked into a non-fleet bench run"
                    % (tag, key, value))
    return failures + _lossless_resume_proof()


def _lossless_resume_proof():
    """THE lossless drill, live: two in-process GenerationAPI
    replicas behind a JOURNALED FleetRouter; ``serve.replica_death``
    is armed to kill one replica a few decode ticks into a long
    request. The dying gasp (503 + resume progress) must make the
    failover RESUME from tokens_done on the survivor: the answer is
    token-for-token the solo decode, ``resumed_from`` > 0, the
    resumed decode's FLOPs (CostModel cost_analysis over the actual
    compiled prefill/step programs) undercut a full redo's, and the
    journal ends with zero pending entries (every accepted request
    reached a terminal record)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import shutil
    import tempfile
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.nn import sampling
    from veles_tpu.serving.router import FleetRouter
    from veles_tpu.telemetry.cost import cost_of_compiled
    from veles_tpu.telemetry.counters import counters as _ctrs

    prng.seed_all(6161)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8, 16, 32),
                             max_context=48, name="lossless_%d" % i)
            for i in range(2)]
    for api in apis:
        api.initialize()
    failures = []
    prompt = [1, 5, 3, 2, 4]
    n_new = 12
    expected = sampling.generate(wf, prompt, n_new, temperature=0)
    journal_dir = tempfile.mkdtemp(prefix="veles_journal_gate_")
    saved_spec = os.environ.get("VELES_FAULTS")
    router = None
    try:
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=1, retry_budget=2,
            attempt_timeout=60.0, request_timeout=120.0,
            journal_dir=journal_dir, journal_fsync=False,
            name="lossless.router").start()
        import json as _json
        import urllib.error as _er
        import urllib.request as _rq
        url = "http://127.0.0.1:%d/generate" % router.port

        def post(payload, to=url):
            req = _rq.Request(to,
                              data=_json.dumps(payload).encode(),
                              headers={"Content-Type":
                                       "application/json"})
            try:
                with _rq.urlopen(req, timeout=90) as r:
                    return r.status, _json.loads(r.read())
            except _er.HTTPError as e:
                try:
                    return e.code, _json.loads(e.read() or b"{}")
                except ValueError:
                    return e.code, {"error": "replica answered %d"
                                    % e.code}

        # warm BOTH replicas' programs (incl. the original bucket's
        # prefill) outside the armed window
        for api in apis:
            status, body = post(
                {"prompt": prompt, "n_new": 4},
                to="http://127.0.0.1:%d/generate" % api.port)
            if status != 200:
                failures.append("lossless: warm-up answered %d (%s)"
                                % (status, body.get("error")))
        ra = _ctrs.get("veles_resume_attempts_total")
        rt = _ctrs.get("veles_resume_tokens_total")
        ja = _ctrs.get("veles_journal_appends_total")
        # the in-flight request dies a few decode ticks in: hit 1 is
        # the request-path site at admission, hits 2+ the engine's
        # per-tick site — after=4 kills mid-decode deterministically
        os.environ["VELES_FAULTS"] = \
            "serve.replica_death:raise:after=4,times=1"
        status, body = post({"prompt": prompt, "n_new": n_new})
        os.environ.pop("VELES_FAULTS", None)
        if status != 200:
            failures.append(
                "lossless: resumed request answered %d (%s)"
                % (status, body.get("error")))
            return failures
        k = int(body.get("resumed_from", 0))
        if k < 1:
            failures.append(
                "lossless: the failover never resumed (resumed_from="
                "%s — the dying gasp carried no progress)" % k)
        if body.get("tokens") != expected:
            failures.append(
                "lossless: resumed tokens differ from the solo "
                "decode (%s vs %s)" % (body.get("tokens"), expected))
        if _ctrs.get("veles_resume_attempts_total") - ra < 1:
            failures.append(
                "lossless: no resume attempt counted")
        if _ctrs.get("veles_resume_tokens_total") - rt < k:
            failures.append(
                "lossless: resume_tokens counter did not cover the "
                "carried prefix")
        if _ctrs.get("veles_journal_appends_total") - ja < 2:
            failures.append(
                "lossless: the journal never recorded the request "
                "(admit + terminal)")
        # -- resumed decode FLOPs < full redo, over the ACTUAL
        # compiled programs of the surviving engine ------------------------
        survivor = [api for api in apis
                    if api._service is not None]
        if not survivor or survivor[0]._engine is None:
            failures.append("lossless: no surviving engine to cost")
            return failures
        eng = survivor[0]._engine
        sched = eng.scheduler

        def flops_of(kind, bucket=None):
            prog = eng._progs.get((kind, bucket))
            exe = prog.compiled() if prog is not None else None
            if exe is None:
                return None
            return cost_of_compiled(exe).flops

        step_f = flops_of("step")
        pre_orig = flops_of("prefill", sched.bucket_for(len(prompt)))
        pre_res = flops_of("prefill",
                           sched.bucket_for(len(prompt) + max(k, 1)))
        if not step_f or pre_res is None:
            failures.append(
                "lossless: CostModel could not price the compiled "
                "serving programs (step=%s prefill=%s)"
                % (step_f, pre_res))
        elif k >= 1:
            # prefill emits the first token of each leg; the rest
            # ride decode steps (decode_block=1 in this drill)
            resumed = pre_res + (n_new - k - 1) * step_f
            redo = (pre_orig if pre_orig is not None
                    else pre_res) + (n_new - 1) * step_f
            if resumed >= redo:
                failures.append(
                    "lossless: resumed decode cost %.3e flops >= "
                    "full redo %.3e — resume saved nothing"
                    % (resumed, redo))
            else:
                print("lossless proof: death at token %d of %d -> "
                      "failover resumed id-exact; resumed cost "
                      "%.3e flops vs %.3e full redo (%.2fx), "
                      "journal clean" % (k, n_new, resumed, redo,
                                         redo / resumed))
        # every accepted request must have reached a terminal record
        pending = router.journal.pending()
        if pending:
            failures.append(
                "lossless: %d journal entr%s left pending after all "
                "answers (%s)" % (len(pending),
                                  "y" if len(pending) == 1 else "ies",
                                  [r["request_id"] for r in pending]))
    finally:
        if saved_spec is None:
            os.environ.pop("VELES_FAULTS", None)
        else:
            os.environ["VELES_FAULTS"] = saved_spec
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
    return failures


def gate_tracing(baseline_doc=None, current_doc=None):
    """``tracing`` gate section: (1) the fleet-tracing counters must
    be registered; (2) bench documents must carry ZERO tracing-plane
    activity — the bench never serves, pulls a span ring or merges a
    fleet trace, so request/route spans or pull/rotation/merge counts
    in a training measurement mean the plane leaked; (3) live proof:
    decode dispatch counts are bit-identical tracing on/off THROUGH
    THE ROUTER PATH (the PR 11 per-process lock extended to the
    fleet), with tracing off appending zero request-plane spans to
    the ring; and a journaled 2-replica fleet under an injected
    mid-decode replica death yields ONE merged Chrome trace where the
    router's route.request/route.attempt spans and both replicas'
    request spans carry the same trace_id, with the resume attempt's
    tokens_done visible. Runs AFTER gate_fleet/gate_lossless in
    _gate_main (their drills legitimately emit request spans), so
    doc-leakage is asserted on the DOCUMENTS, never process-absolute
    span counts."""
    from veles_tpu.telemetry import TRACE_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in TRACE_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "tracing: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("tracing")
        if not sec:
            continue
        if ((doc or {}).get("serving") or {}).get("serving_bench"):
            # a serving-mode bench document SERVES on purpose — its
            # request spans are the measurement, not a leak (the
            # same skip gate_serving applies to its leakage keys)
            continue
        for key in ("request_spans", "span_pulls", "rotations",
                    "fleet_merges"):
            if sec.get(key):
                failures.append(
                    "tracing: %s doc has %s=%s — request-plane "
                    "tracing leaked into a non-serving bench run"
                    % (tag, key, sec[key]))
    return failures + _fleet_trace_proof()


def _fleet_trace_proof():
    """THE fleet-tracing drill, live: two in-process GenerationAPI
    replicas behind a JOURNALED FleetRouter. First the dispatch lock:
    the same sequential load routed with tracing ON and OFF must move
    the decode/prefill dispatch counters identically (tracing is
    host-side stamps, never device work — now proven through the
    router too) and tracing OFF must append zero request/route spans
    to the ring. Then the merge: ``serve.replica_death`` kills one
    replica mid-decode; the answer must be id-exact with
    ``resumed_from >= 1``, and pulling /trace/spans from the router +
    the survivor and assembling with ``--request <trace_id>``
    semantics must yield ONE valid Chrome trace carrying
    route.request, >= 2 route.attempt spans (the resume attempt's
    tokens_done >= 1), and both replicas' request spans — every
    event under the same trace_id — with the journal left clean."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import shutil
    import tempfile
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.config import root as vt_root
    from veles_tpu.nn import sampling
    from veles_tpu.serving.router import FleetRouter
    from veles_tpu.telemetry import fleet as vt_fleet
    from veles_tpu.telemetry.counters import counters as _ctrs
    from veles_tpu.telemetry.spans import recorder as span_recorder

    prng.seed_all(7171)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8, 16, 32),
                             max_context=48, name="trace_bench_%d" % i)
            for i in range(2)]
    for api in apis:
        api.initialize()
    failures = []
    prompt = [1, 5, 3, 2, 4]
    n_new = 12
    expected = sampling.generate(wf, prompt, n_new, temperature=0)
    journal_dir = tempfile.mkdtemp(prefix="veles_trace_gate_")
    saved_spec = os.environ.get("VELES_FAULTS")
    prev_traced = vt_root.common.trace.get("requests", True)
    router = None
    try:
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=1, retry_budget=2,
            attempt_timeout=60.0, request_timeout=120.0,
            journal_dir=journal_dir, journal_fsync=False,
            name="trace.router").start()
        import json as _json
        import urllib.error as _er
        import urllib.request as _rq
        url = "http://127.0.0.1:%d/generate" % router.port

        def post(payload, to=url):
            req = _rq.Request(to,
                              data=_json.dumps(payload).encode(),
                              headers={"Content-Type":
                                       "application/json"})
            try:
                with _rq.urlopen(req, timeout=90) as r:
                    return r.status, _json.loads(r.read())
            except _er.HTTPError as e:
                try:
                    return e.code, _json.loads(e.read() or b"{}")
                except ValueError:
                    return e.code, {"error": "replica answered %d"
                                    % e.code}

        # warm BOTH replicas' programs outside any measured window
        for api in apis:
            status, body = post(
                {"prompt": prompt, "n_new": 4},
                to="http://127.0.0.1:%d/generate" % api.port)
            if status != 200:
                failures.append("tracing: warm-up answered %d (%s)"
                                % (status, body.get("error")))

        # -- dispatch lock, router path: tracing on == tracing off ----
        keys = ("veles_serving_decode_dispatches_total",
                "veles_serving_prefill_dispatches_total",
                "veles_decode_dispatches_total")

        def load():
            outs = []
            for _ in range(3):
                status, body = post({"prompt": prompt, "n_new": 4})
                outs.append((status, body.get("tokens")))
            return outs

        def measured(fn):
            before = {k: _ctrs.get(k) for k in keys}
            out = fn()
            return out, {k: _ctrs.get(k) - before[k] for k in keys}

        vt_root.common.trace.requests = True
        out_on, d_on = measured(load)
        ring_cursor = span_recorder.cursor()
        vt_root.common.trace.requests = False
        out_off, d_off = measured(load)
        off_spans, _ = span_recorder.records_since(ring_cursor)
        off_leak = [r["name"] for r in off_spans
                    if str(r.get("name", "")).startswith(
                        ("request", "route."))]
        vt_root.common.trace.requests = True
        if out_on != out_off:
            failures.append(
                "tracing: answers differ tracing on vs off through "
                "the router (%s vs %s)" % (out_on, out_off))
        if d_on != d_off:
            failures.append(
                "tracing: dispatch counts differ tracing on vs off "
                "through the router path (%s vs %s) — tracing moved "
                "device work" % (d_on, d_off))
        if off_leak:
            failures.append(
                "tracing: %d request-plane span(s) %s appended to "
                "the ring with root.common.trace.requests OFF"
                % (len(off_leak), sorted(set(off_leak))))

        # -- the merged-trace drill: death mid-decode -> ONE trace ----
        merges = _ctrs.get("veles_trace_fleet_merges_total")
        pulls = _ctrs.get("veles_trace_span_pulls_total")
        os.environ["VELES_FAULTS"] = \
            "serve.replica_death:raise:after=4,times=1"
        status, body = post({"prompt": prompt, "n_new": n_new})
        os.environ.pop("VELES_FAULTS", None)
        if status != 200:
            failures.append("tracing: death-drill request answered "
                            "%d (%s)" % (status, body.get("error")))
            return failures
        if body.get("tokens") != expected:
            failures.append("tracing: resumed tokens differ from the "
                            "solo decode")
        if int(body.get("resumed_from", 0)) < 1:
            failures.append("tracing: the failover never resumed — "
                            "no tokens_done to show in the trace")
        tid = body.get("trace_id")
        if not tid:
            failures.append("tracing: the router's answer carries no "
                            "trace_id")
            return failures
        endpoints = ["127.0.0.1:%d" % router.port] + \
            ["127.0.0.1:%d" % api.port for api in apis
             if api._service is not None]
        try:
            doc, summary = vt_fleet.trace_fleet(endpoints,
                                                request=tid)
        except ValueError as e:
            failures.append("tracing: fleet trace assembly failed "
                            "(%s)" % e)
            return failures
        # (assemble_fleet_trace already schema-validated the doc —
        # an invalid merge raises and lands in the branch above)
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        names = [e["name"] for e in evs]
        if "route.request" not in names:
            failures.append("tracing: merged trace lacks the "
                            "route.request root span")
        attempts = [e for e in evs if e["name"] == "route.attempt"]
        if len(attempts) < 2:
            failures.append(
                "tracing: merged trace holds %d route.attempt "
                "span(s); the failover needs >= 2" % len(attempts))
        if not any(int(e["args"].get("tokens_done", 0)) >= 1
                   for e in attempts):
            failures.append(
                "tracing: no route.attempt span shows the resume's "
                "tokens_done")
        req_spans = [e for e in evs if e["name"] == "request"]
        span_attempts = {int(e["args"].get("attempt", 0))
                         for e in req_spans}
        if not {1, 2} <= span_attempts:
            failures.append(
                "tracing: merged trace lacks both replicas' request "
                "spans (attempts seen: %s)" % sorted(span_attempts))
        wrong = [e["name"] for e in evs
                 if e["args"].get("trace_id") not in (None, tid)]
        if wrong:
            failures.append(
                "tracing: merged trace carries foreign trace_ids on "
                "%s" % sorted(set(wrong)))
        if all("trace_id" not in e["args"] for e in evs):
            failures.append("tracing: no event in the merged trace "
                            "is tagged with the trace_id")
        if _ctrs.get("veles_trace_fleet_merges_total") - merges < 1:
            failures.append("tracing: the merge was never counted")
        if _ctrs.get("veles_trace_span_pulls_total") - pulls \
                < len(endpoints):
            failures.append("tracing: fewer span pulls counted than "
                            "endpoints pulled")
        pending = router.journal.pending()
        if pending:
            failures.append(
                "tracing: %d journal entr%s left pending after the "
                "drill" % (len(pending),
                           "y" if len(pending) == 1 else "ies"))
        if not failures:
            print("tracing proof: router-path dispatches identical "
                  "tracing on/off; death at token %d of %d -> ONE "
                  "merged trace (%d spans, %d lane(s)) under "
                  "trace_id %s with the resume visible"
                  % (int(body.get("resumed_from", 0)), n_new,
                     summary["spans"], summary["processes"], tid))
    finally:
        if saved_spec is None:
            os.environ.pop("VELES_FAULTS", None)
        else:
            os.environ["VELES_FAULTS"] = saved_spec
        vt_root.common.trace.requests = prev_traced
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
    return failures


#: chunk-overhead allowance for the share-ratio FLOP bound: a chunked
#: suffix pass re-reads the whole gathered page view per chunk and
#: pads its final chunk, so the measured prefill-FLOP reduction is
#: required to reach share_ratio x this factor, not share_ratio
#: itself (the stamps print both numbers)
PREFIX_SHARE_TOLERANCE = 0.75


def gate_prefix(baseline_doc=None, current_doc=None):
    """``prefix`` gate section: (1) every prefix-sharing counter must
    be registered with a HELP string; (2) bench documents must carry
    ZERO prefix-plane activity — the bench never serves, so
    hits/COW/evictions in a training measurement mean the sharing
    machinery leaked; (3) live proof (:func:`_prefix_sharing_proof`):
    a 16-request shared-prefix load under prefix_cache=on shows a
    prefill-FLOP reduction >= share_ratio x PREFIX_SHARE_TOLERANCE
    (CostModel over the ACTUAL compiled prefill/chunk programs),
    id-exact vs the prefix-off engine; a streamed response's first
    token arrives strictly before the full buffered response; and
    chunked prefill bounds the per-tick in-flight decode stall below
    the monolithic prefill's. Runs AFTER the fleet/lossless/tracing
    drills in _gate_main (their serving legitimately moves shared
    counters), so leakage is asserted on the DOCUMENTS only."""
    from veles_tpu.serving import PREFIX_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in PREFIX_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "prefix: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("prefix")
        if not sec:
            continue
        if ((doc or {}).get("serving") or {}).get("serving_bench"):
            continue        # a serving-mode bench shares on purpose
        for key, value in sec.items():
            if value:
                failures.append(
                    "prefix: %s doc has %s=%s — prefix-sharing work "
                    "leaked into a non-serving bench run"
                    % (tag, key, value))
    return failures + _prefix_sharing_proof()


def _prefix_sharing_proof():
    """THE prefix/chunk/stream drill, live on this process's CPU (or
    chip) backend. One small char_lm stack serves three measurements:

    1. **share-ratio FLOP bound** — 16 requests sharing a 48-token
       prefix (4-token unique tails) served by a prefix-OFF and a
       prefix-ON engine; each engine's prefill FLOPs are priced as
       sum(CostModel(compiled program) x dispatches) over its ACTUAL
       programs (``ContinuousEngine.prog_calls``), answers asserted
       id-exact, and the ON engine's reduction must reach
       share_ratio x PREFIX_SHARE_TOLERANCE;
    2. **chunk stall bound** — a long-prompt admission lands while a
       decode is in flight on each engine; the monolithic engine's
       ``prefill_stall_max`` (seconds of prefill work in a tick with
       co-tenants) must exceed the chunked engine's — chunked prefill
       bounds in-flight TPOT jitter, measured;
    3. **streamed TTFT** — the same request POSTed ``stream=true``
       and buffered against a live GenerationAPI: the first SSE token
       event must arrive strictly before the buffered response
       completes, with the TTFT/TPOT p50/p99 histogram quantiles
       stamped alongside."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import urllib.request
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.nn import sampling
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request
    from veles_tpu.serving.scheduler import Ticket
    from veles_tpu.telemetry.cost import cost_of_compiled
    from veles_tpu.telemetry.counters import counters as _ctrs
    from veles_tpu.telemetry.counters import histograms as _hists

    prng.seed_all(5151)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=2, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    failures = []
    rng = __import__("numpy").random.RandomState(9)
    shared = [int(t) for t in char_lm.make_corpus(rng, 48)]
    reqs = []
    for i in range(16):
        tail = [int(t) for t in char_lm.make_corpus(
            __import__("numpy").random.RandomState(200 + i), 4)]
        reqs.append(make_request(
            shared + tail, 8,
            temperature=0.8 if i % 2 else 0.0,
            seed=300 + i, mode="sample" if i % 2 else "greedy"))

    def prefill_flops(engine):
        total = 0.0
        for key, calls in engine.prog_calls.items():
            if key[0] not in ("prefill", "pchunk", "dprefill"):
                continue
            prog = engine._progs.get(key)
            exe = prog.compiled() if prog is not None else None
            if exe is None:
                return None
            total += cost_of_compiled(exe).flops * calls
        return total

    def run_load(engine):
        out = engine.serve([dict(reqs[0])])
        out += engine.serve([dict(r) for r in reqs[1:]])
        return out

    def stall_drill(engine):
        """Long-prompt admission mid-decode; returns the engine's
        worst per-tick prefill stall with co-tenants in flight.
        BOTH prompt shapes are served (and so compiled) solo first
        and the gauge reset, so the measured stall is prefill
        EXECUTION — the steady-state number — never the one-time XLA
        compile a warm production engine would not pay."""
        long_prompt = [int(t) for t in char_lm.make_corpus(
            __import__("numpy").random.RandomState(77), 200)]
        engine.serve([make_request([1, 5, 3, 2], 2, seed=7),
                      make_request(long_prompt, 2, seed=8)])
        engine.prefill_stall_max = engine.prefill_stall_last = 0.0
        inflight = Ticket()
        assert engine.submit(make_request([1, 5, 3, 2], 64, seed=7),
                             inflight)
        deadline = time.time() + 30
        while engine.scheduler.busy_count() == 0 \
                and time.time() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)        # decoding under way
        long = Ticket()
        assert engine.submit(make_request(long_prompt, 4, seed=8),
                             long)
        long.event.wait(60)
        inflight.event.wait(60)
        return engine.prefill_stall_max

    hits0 = _ctrs.get("veles_prefix_hits_total")
    # two geometries: the FLOP phase keeps the logical view short
    # (the chunk pass attends over the whole gathered view, so a
    # stall-drill-sized max_context would bill every chunk for dead
    # masked keys); the stall phase needs the big bucket
    geometry = dict(max_slots=4, buckets=(64,), max_context=96,
                    page_size=8, decode_block=1)
    stall_geo = dict(max_slots=4, buckets=(64, 256), max_context=288,
                     page_size=8, decode_block=1)
    # constructed INSIDE the try: a later constructor failing must
    # not leak earlier engines' tick threads into the rest of the
    # gate run (they would keep mutating shared counters)
    engines = []
    api = None
    try:
        e_off = ContinuousEngine(wf, name="prefix_off",
                                 prefix_cache=False,
                                 prefill_chunk=0, **geometry).start()
        engines.append(e_off)
        e_on = ContinuousEngine(wf, name="prefix_on",
                                prefix_cache=True,
                                prefill_chunk=8, **geometry).start()
        engines.append(e_on)
        s_off = ContinuousEngine(wf, name="stall_off",
                                 prefix_cache=False,
                                 prefill_chunk=0, **stall_geo).start()
        engines.append(s_off)
        s_on = ContinuousEngine(wf, name="stall_on",
                                prefix_cache=False,
                                prefill_chunk=8, **stall_geo).start()
        engines.append(s_on)
        out_off = run_load(e_off)
        out_on = run_load(e_on)
        if out_off != out_on:
            failures.append(
                "prefix: prefix-cache ON answers differ from OFF — "
                "id-exactness under sharing is broken")
        hits = _ctrs.get("veles_prefix_hits_total") - hits0
        if hits < 15:
            failures.append(
                "prefix: only %d/15 shared-prefix admissions hit the "
                "cache" % hits)
        flops_off = prefill_flops(e_off)
        flops_on = prefill_flops(e_on)
        if not flops_off or flops_on is None:
            failures.append(
                "prefix: CostModel could not price the compiled "
                "prefill programs (off=%s on=%s)"
                % (flops_off, flops_on))
        else:
            total_pos = sum(len(r["prompt"]) for r in reqs)
            share_ratio = (len(reqs) - 1) * len(shared) / total_pos
            reduction = 1.0 - flops_on / flops_off
            required = share_ratio * PREFIX_SHARE_TOLERANCE
            if reduction < required:
                failures.append(
                    "prefix: prefill-FLOP reduction %.3f below the "
                    "share-ratio bound %.3f (share_ratio %.3f x "
                    "tolerance %.2f; %.3e -> %.3e flops)"
                    % (reduction, required, share_ratio,
                       PREFIX_SHARE_TOLERANCE, flops_off, flops_on))
            else:
                print("prefix proof: 16-request shared-prefix load -> "
                      "prefill %.3e flops (off) vs %.3e (on), "
                      "reduction %.1f%% >= bound %.1f%% "
                      "(share ratio %.1f%%), %d cache hits, id-exact"
                      % (flops_off, flops_on, reduction * 100,
                         required * 100, share_ratio * 100, hits))
        # -- chunk stall bound (min-of-2 per engine: scheduler noise
        # must not flip a genuine 256-row vs 8-row execution contrast)
        stall_off = min(stall_drill(s_off), stall_drill(s_off))
        stall_on = min(stall_drill(s_on), stall_drill(s_on))
        if stall_off <= 0:
            failures.append(
                "prefix: monolithic stall drill recorded no co-tenant "
                "prefill stall (harness broken?)")
        elif stall_on >= stall_off:
            failures.append(
                "prefix: chunked prefill stall %.4fs does not undercut "
                "the monolithic prefill's %.4fs — chunking is not "
                "bounding in-flight decode stalls"
                % (stall_on, stall_off))
        else:
            print("prefix proof: per-tick decode stall %.4fs "
                  "(monolithic 256-token prefill) -> %.4fs (8-token "
                  "chunks), %.1fx smaller"
                  % (stall_off, stall_on, stall_off / max(stall_on,
                                                          1e-9)))
        # -- streamed TTFT < full-response latency ----------------------------
        api = vt.GenerationAPI(wf, port=0, engine="continuous",
                               max_slots=2, buckets=(8, 16),
                               max_context=64, decode_block=1,
                               prefix_cache=True, prefill_chunk=8,
                               name="prefix_stream")
        api.initialize()
        url = "http://127.0.0.1:%d/generate" % api.port
        payload = {"prompt": [1, 5, 3, 2, 4], "n_new": 24}

        def post(body):
            req = urllib.request.Request(
                url, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=60)

        post(dict(payload, n_new=4)).read()      # warm the programs
        t0 = time.time()
        post(payload).read()
        full_latency = time.time() - t0
        t0 = time.time()
        t_first = None
        toks = []
        final = {}
        with post(dict(payload, stream=True)) as r:
            for line in r:
                line = line.strip()
                if not line.startswith(b"data:"):
                    continue
                ev = json.loads(line[5:])
                if ev.get("done"):
                    final = ev
                elif ev.get("tokens"):
                    if t_first is None:
                        t_first = time.time() - t0
                    toks += ev["tokens"]
        expected = sampling.generate(wf, payload["prompt"], 24,
                                     temperature=0)
        if toks != expected or final.get("tokens") != expected:
            failures.append(
                "prefix: streamed tokens differ from the solo decode")
        if t_first is None or t_first >= full_latency:
            failures.append(
                "prefix: streamed TTFT %s not below the full-response "
                "latency %.4fs" % (t_first, full_latency))
        else:
            def q(name, quant):
                val = _hists.quantile(name, quant)
                return -1.0 if val is None else val
            print("prefix proof: streamed TTFT %.4fs < full response "
                  "%.4fs (%.1fx); ttft p50/p99 %.4f/%.4fs, tpot "
                  "p50/p99 %.4f/%.4fs"
                  % (t_first, full_latency, full_latency / t_first,
                     q("veles_serving_ttft_seconds", 0.5),
                     q("veles_serving_ttft_seconds", 0.99),
                     q("veles_serving_tpot_seconds", 0.5),
                     q("veles_serving_tpot_seconds", 0.99)))
    finally:
        for engine in engines:
            engine.stop()
        if api is not None:
            api.stop()
    for engine in engines:
        ledger = engine.page_pool.ledger()
        if ledger:
            failures.append(
                "prefix: %s page refcount ledger did not balance "
                "after the drill (%d entries left)"
                % (engine.name, len(ledger)))
    return failures


def gate_quant(baseline_doc=None, current_doc=None):
    """``quant`` gate section: (1) the quantization/artifact counters
    must be registered; (2) quant-off bench documents must carry ZERO
    quant/artifact activity (int8 leaking into a float measurement
    breaks the bit-identical-off contract); (3) live proof —
    quantized greedy serving is TOKEN-EXACT vs float on the bench
    model with a bounded max logit delta and a sane throughput ratio,
    and an AOT artifact engine initializes + serves with ZERO jit
    compiles (vs >= 2 for live jit) while staying id-exact."""
    from veles_tpu.quant import QUANT_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in QUANT_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "quant: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("quant")
        if not sec:
            continue
        if not (sec.get("weights") or sec.get("kv")):
            for key in ("params_quantized", "bytes_saved",
                        "calibrations"):
                if sec.get(key):
                    failures.append(
                        "quant: %s doc has %s=%s with quantization "
                        "OFF — int8 work leaked into a float run"
                        % (tag, key, sec[key]))
        if not sec.get("artifact"):
            for key in ("artifact_loads", "artifact_load_failures"):
                if sec.get(key):
                    failures.append(
                        "quant: %s doc has %s=%s with no artifact "
                        "configured" % (tag, key, sec[key]))
    proof_failures, metrics = _quant_serving_proof()
    if metrics:
        print("quant proof: fp %.0f vs int8 %.0f tokens/sec (%.2fx), "
              "greedy token-match %.2f, max logit delta %.2e; "
              "artifact: %d compiles (live jit: %d), id-exact=%s"
              % (metrics["fp_tokens_per_sec"],
                 metrics["int8_tokens_per_sec"],
                 metrics["int8_vs_fp"],
                 metrics["greedy_token_match"],
                 metrics["max_logit_delta"],
                 metrics["artifact_compiles"],
                 metrics["live_compiles"],
                 metrics["artifact_id_exact"]))
    return failures + proof_failures


def _quant_serving_proof():
    """Serve the same all-greedy mixed-length load through a float
    engine and an int8 (weights + KV) engine; then boot a third engine
    from a freshly exported AOT artifact. Enforced: every quantized
    greedy answer token-exact vs float, max logit delta under 0.25 (a
    loose ceiling — measured ~1e-2 on this model; an order-of-
    magnitude regression means broken scales), int8 throughput at
    least 0.25x float (the HBM win needs a chip; on CPU the dequant
    is pure overhead, so this is an anti-collapse floor, not the
    speedup claim — docs/perf.md), zero jit compiles for the artifact
    engine vs >= 2 live, artifact answers id-exact. Returns
    (failures, metrics) so the caller can both gate and record."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import shutil
    import statistics as _stats
    import tempfile
    import time as _t
    import numpy
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.nn import sampling
    from veles_tpu.quant import dequantize_params, quantize_params
    from veles_tpu.serving import ContinuousEngine
    from veles_tpu.serving.engine import make_request
    from veles_tpu.export.serve_artifact import export_serve_artifact
    from veles_tpu.telemetry.counters import counters

    prng.seed_all(515)
    wf = char_lm.build_workflow(epochs=2, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=256,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    # token-exactness is a claim about a MODEL, not about noise: an
    # untrained stack has near-uniform logits whose argmax gaps sit
    # below the int8 rounding floor. Two epochs on the grammar corpus
    # put the margins where a real checkpoint's are (measured: every
    # request exact under weights/kv/both; at 1×64 samples one
    # near-tie request still flipped).
    wf.run()
    lengths = [5, 9, 14, 7, 12, 16, 6, 11, 13, 8, 15, 10]
    rng = numpy.random.RandomState(23)
    reqs = [make_request([int(t) for t in
                          rng.randint(0, char_lm.VOCAB, t_p)], 8)
            for t_p in lengths]
    total_tokens = sum(r["n_new"] for r in reqs)
    failures = []
    metrics = {}
    knobs = dict(max_slots=8, buckets=(8, 16), max_context=32,
                 decode_block=8)

    def measure(engine):
        engine.serve(list(reqs))          # warm every program
        times = []
        for _ in range(3):
            t0 = _t.time()
            out = engine.serve(list(reqs))
            times.append(_t.time() - t0)
        return out, total_tokens / _stats.median(times)

    fp = ContinuousEngine(wf, name="bench.quant.fp", **knobs).start()
    try:
        fp_out, fp_tps = measure(fp)
    finally:
        fp.stop()
    q = ContinuousEngine(wf, quant_weights=True, quant_kv=True,
                         name="bench.quant.int8", **knobs).start()
    try:
        q_out, q_tps = measure(q)
    finally:
        q.stop()
    match = sum(a == b for a, b in zip(fp_out, q_out)) / len(reqs)
    qparams, _ = quantize_params(sampling.params_of(wf))
    dq = dequantize_params(qparams)
    deltas = [numpy.abs(
        sampling.prompt_logits(wf, r["prompt"])
        - sampling.prompt_logits(wf, r["prompt"], params=dq)
    ).max() for r in reqs]
    metrics.update({
        "fp_tokens_per_sec": fp_tps,
        "int8_tokens_per_sec": q_tps,
        "int8_vs_fp": q_tps / fp_tps,
        "greedy_token_match": match,
        "max_logit_delta": float(max(deltas)),
    })
    if match < 1.0:
        failures.append(
            "quant: int8 greedy serving not token-exact on the bench "
            "model (match rate %.2f)" % match)
    if metrics["max_logit_delta"] > 0.25:
        failures.append(
            "quant: max logit delta %.3f exceeds the 0.25 ceiling — "
            "quantization scales are broken"
            % metrics["max_logit_delta"])
    if q_tps < 0.25 * fp_tps:
        # an anti-collapse floor, NOT the speedup claim: on CPU the
        # dequant is pure extra ALU work (no HBM to win back) and this
        # box's wall clock is contention-noisy — the int8 throughput
        # GAIN is a chip-side claim, recorded here and in docs/perf.md
        failures.append(
            "quant: int8 serving collapsed to %.0f tokens/sec vs "
            "float %.0f (floor is 0.25x)" % (q_tps, fp_tps))

    # AOT cold-start proof: artifact initialize+serve = 0 jit
    # compiles; a fresh live-jit engine pays >= 2 (prefill + decode)
    art_dir = tempfile.mkdtemp(prefix="veles_quant_gate_")
    try:
        export_serve_artifact(wf, os.path.join(art_dir, "art"),
                              **knobs)
        before = counters.get("veles_compiles_total")
        art = ContinuousEngine(wf, artifact=os.path.join(art_dir,
                                                         "art"),
                               name="bench.quant.art", **knobs).start()
        try:
            art_out = art.serve(list(reqs))
            art_compiles = int(counters.get("veles_compiles_total")
                               - before)
            if not art.artifact_mode:
                failures.append("quant: artifact engine fell back to "
                                "live jit")
        finally:
            art.stop()
        before = counters.get("veles_compiles_total")
        live = ContinuousEngine(wf, name="bench.quant.live",
                                **knobs).start()
        try:
            live.serve(list(reqs))
            live_compiles = int(counters.get("veles_compiles_total")
                                - before)
        finally:
            live.stop()
        metrics.update({
            "artifact_compiles": art_compiles,
            "live_compiles": live_compiles,
            "artifact_id_exact": art_out == fp_out,
        })
        if art_compiles != 0:
            failures.append(
                "quant: artifact engine paid %d jit compiles at "
                "initialize+serve (must be 0)" % art_compiles)
        if live_compiles < 2:
            failures.append(
                "quant: live-jit control paid %d compiles (expected "
                ">= 2) — the compile counter is broken, so the "
                "artifact zero-compile proof proves nothing"
                % live_compiles)
        if art_out != fp_out:
            failures.append(
                "quant: artifact serving not id-exact vs the live "
                "engine")
    finally:
        shutil.rmtree(art_dir, ignore_errors=True)
    return failures, metrics


#: the O(1)-state lane's reason to exist: per-slot recurrent state
#: must undercut the paged transformer's per-slot KV allotment (same
#: geometry) by at least this factor — the slots-at-equal-HBM
#: headline the gate stamps
O1_HBM_MULTIPLIER = 4.0


def gate_o1state(baseline_doc=None, current_doc=None):
    """``o1state`` gate section: (1) every O(1)-state lane counter
    must be registered with a HELP string; (2) bench documents must
    carry ZERO state-checkpoint activity — the bench never serves the
    recurrent lane, so checkpoints/restores in a training measurement
    mean the lane leaked; (3) live proof (:func:`_o1state_proof`):
    a recurrent char_lm stack pool-serves id-exact vs the solo
    sampler (greedy AND sampled — the scan-prefill ↔ recurrent-decode
    duality), decode state bytes stay FLAT whatever the token count
    (pageless pool), and per-slot state undercuts the paged
    transformer's per-slot KV allotment by >= O1_HBM_MULTIPLIER x at
    the same geometry."""
    from veles_tpu.serving import O1_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in O1_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "o1state: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("o1state")
        if not sec:
            continue
        if ((doc or {}).get("serving") or {}).get("serving_bench"):
            continue      # a serving-mode bench checkpoints on purpose
        for key, value in sec.items():
            if value:
                failures.append(
                    "o1state: %s doc has %s=%s — O(1)-state serving "
                    "work leaked into a non-serving bench run"
                    % (tag, key, value))
    proof_failures, metrics = _o1state_proof()
    if metrics:
        print("o1state proof: pooled scan/recurrent id-exact "
              "(greedy+sampled), state pool %d bytes at 4 and %d "
              "tokens (flat, 0 pages), %.1fx slots at equal HBM "
              "(kv %d vs state %d bytes/slot)"
              % (metrics["pool_bytes"], metrics["long_tokens"],
                 metrics["hbm_multiplier"], metrics["kv_per_slot"],
                 metrics["state_per_slot"]))
    return failures + proof_failures


def _o1state_proof():
    """THE O(1)-state drill, live on this process's backend. One tiny
    recurrent (LSTM) char_lm stack plus a transformer twin at the
    same geometry prove the lane's three claims:

    1. **scan ↔ recurrence id-exact** — the pooled engine (chunked
       scan prefill + fixed-shape recurrent decode over interleaved
       slots) answers token-identical to the private solo sampler,
       greedy AND sampled.
    2. **flat decode state** — the state pool's byte count is
       identical after a 4-token and a 44-token decode: per-slot
       state is fixed, no page table, nothing grows with context.
    3. **slots at equal HBM** — per-slot state bytes undercut the
       paged transformer's per-slot KV allotment by >=
       O1_HBM_MULTIPLIER x, so the same memory holds that many more
       concurrent decodes.

    Returns (failures, metrics) so the caller can gate and stamp."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy
    import jax
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.serving import RecurrentEngine, generate_recurrent
    from veles_tpu.serving.engine import ContinuousEngine, make_request

    failures = []
    prng.seed_all(616)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32, arch="lstm")
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    prng.seed_all(617)
    twf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                 n_blocks=1, dim=32, n_train=64,
                                 n_valid=32)
    twf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    prompt = [int(t) for t in
              char_lm.make_corpus(numpy.random.RandomState(9), 12)]

    # 1. pooled == solo, greedy AND sampled (the duality lock, over
    # the exact programs the engine serves with)
    loads = [("greedy", 0.0, 0), ("sample", 0.9, 33)]
    solo = {m: [generate_recurrent(wf, prompt, 10, temperature=t,
                                   seed=s + i, mode=m)
                for i in range(3)]
            for m, t, s in loads}
    eng = RecurrentEngine(wf, max_slots=3, max_context=64,
                          page_size=8, name="bench_o1state").start()
    try:
        for m, t, s in loads:
            out = eng.serve([make_request(prompt, 10, temperature=t,
                                          seed=s + i, mode=m)
                             for i in range(3)])
            if out != solo[m]:
                failures.append(
                    "o1state: pooled %s serve diverged from the solo "
                    "scan/recurrent sampler" % m)
        # 2. flat decode state bytes: same pool before/after a 11x
        # longer decode, and never a page
        eng.serve([make_request(prompt, 4)])
        short_bytes = int(eng.stats()["kv_pool_bytes"])
        eng.serve([make_request(prompt, 44)])
        st = eng.stats()
        if not (short_bytes == int(st["kv_pool_bytes"]) > 0):
            failures.append(
                "o1state: decode state pool moved with token count "
                "(%s bytes at 4 tokens vs %s at 44)"
                % (short_bytes, st["kv_pool_bytes"]))
        if st["pages_total"]:
            failures.append(
                "o1state: recurrent engine reports %d KV pages — "
                "the lane must be pageless" % st["pages_total"])
    finally:
        eng.stop()

    # 3. slots at equal HBM: the paged twin's pool is built (never
    # compiled, never started) just to weigh its per-slot KV rows
    paged = ContinuousEngine(twf, max_slots=3, buckets=(16, 32, 64),
                             max_context=64, page_size=8,
                             name="bench_o1state_paged")
    paged._ensure_pool(paged._prepare_params())
    kv_per_slot = sum(
        int(leaf.nbytes)
        for leaf in jax.tree_util.tree_leaves(paged._caches)
    ) // paged.max_slots
    state_per_slot = int(eng.state_bytes_per_slot())
    mult = kv_per_slot / state_per_slot
    if mult < O1_HBM_MULTIPLIER:
        failures.append(
            "o1state: equal-HBM multiplier %.2f under the %.0fx bar "
            "(kv %d vs state %d bytes/slot)"
            % (mult, O1_HBM_MULTIPLIER, kv_per_slot, state_per_slot))
    metrics = {
        "pool_bytes": short_bytes,
        "long_tokens": 44,
        "hbm_multiplier": round(mult, 2),
        "kv_per_slot": int(kv_per_slot),
        "state_per_slot": state_per_slot,
    }
    return failures, metrics


def gate_linalg(baseline_doc=None, current_doc=None):
    """``linalg`` gate section: (1) every distributed linear-algebra
    counter must be registered with a HELP string; (2) legacy bench
    documents that predate the linalg family (no ``linalg`` section at
    all) are TOLERATED — counted on
    ``veles_bench_legacy_sections_total``, never a crash, the same
    rule legacy device-time documents get; (3) documents that do carry
    the section must show ZERO linalg activity unless stamped
    ``linalg_bench`` — the training bench never dispatches a blocked
    kernel, so a matmul/solve count in a training measurement means
    the workload family leaked; (4) live proof
    (:func:`_linalg_proof`): blocked matmul and Cholesky solve match
    the dense reference within the stated dtype tolerance on this
    process's device mesh, CG on the Poisson operator converges below
    1e-5, MFU is graded against the f32 peak table (not bf16), and
    the SUMMA step prediction states its inputs next to the measured
    time."""
    from veles_tpu.linalg import LINALG_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS, inc
    failures = []
    for name in LINALG_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "linalg: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        if doc and "linalg" not in doc:
            # pre-family document: tolerated and counted, never a
            # crash (the PR 8 legacy-document rule)
            inc("veles_bench_legacy_sections_total")
            continue
        sec = (doc or {}).get("linalg")
        if not sec:
            continue
        if sec.get("linalg_bench"):
            continue      # a `bench.py linalg` run counts on purpose
        for key, value in sec.items():
            if key != "linalg_bench" and value:
                failures.append(
                    "linalg: %s doc has %s=%s — linear-algebra "
                    "workload leaked into a training bench run"
                    % (tag, key, value))
    proof_failures, metrics = _linalg_proof()
    if metrics:
        print("linalg proof: matmul rel err %.1e / cholesky solve "
              "rel err %.1e vs dense (tol %.1e) on grid %s, CG "
              "converged in %d iters to %.1e, MFU %s, "
              "SUMMA measured/predicted %.2f"
              % (metrics["matmul_rel_err"], metrics["chol_rel_err"],
                 metrics["tolerance"], metrics["grid"],
                 metrics["cg_iterations"], metrics["cg_residual"],
                 "%.2e at %s" % (metrics["mfu"], metrics["peak_source"])
                 if "mfu" in metrics else "not graded (no peak on "
                 "file for this device)",
                 metrics["measured_over_predicted"]))
    return failures + proof_failures


def _linalg_proof():
    """THE distributed linear-algebra drill, live on this process's
    devices. Small f32 problems with deliberately awkward shapes
    (non-divisible blocks) prove the family's claims:

    1. **blocked == dense** — the block-cyclic SUMMA matmul and the
       right-looking blocked Cholesky solve match ``numpy.linalg``
       within the stated f32 tolerance on whatever device mesh this
       process has (1x1 on the gate's CPU, wider on a chip).
    2. **CG converges and verifies** — the Workflow-graph solver on
       the 5-point Poisson operator reaches < 1e-5 relative residual
       and survives the trusted dense re-verification.
    3. **dtype-correct MFU** — the achieved-FLOP grade divides by the
       f32 peak table entry, and the stamped source label proves it
       (an f32 solve graded against the bf16 peak would flatter
       itself 2x).
    4. **stated prediction** — ``predict_summa_time`` publishes its
       inputs (panel bytes, psum bytes, assumed ICI bandwidth) next
       to the measured step time, the same falsifiable-record shape
       as SCALING.json.

    Returns (failures, metrics) so the caller can gate and stamp."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy
    from veles_tpu.linalg import (blocked_matmul, cholesky_solve,
                                  build_cg_workflow, default_tolerance,
                                  linalg_mesh, poisson2d_matvec,
                                  predict_summa_time)

    failures = []
    rng = numpy.random.RandomState(20260807)
    mesh = linalg_mesh()
    grid = tuple(mesh.devices.shape)
    tol = default_tolerance(numpy.float32)

    # 1a. blocked-cyclic SUMMA matmul vs dense, awkward shapes
    m, k, n = 96, 80, 72
    a = rng.standard_normal((m, k)).astype(numpy.float32)
    b = rng.standard_normal((k, n)).astype(numpy.float32)
    c = numpy.asarray(blocked_matmul(a, b, block=32, mesh=mesh))
    ref = a.astype(numpy.float64) @ b.astype(numpy.float64)
    mm_err = float(numpy.linalg.norm(c - ref)
                   / numpy.linalg.norm(ref))
    if not mm_err < tol:
        failures.append(
            "linalg: blocked matmul off dense reference by %.3e "
            "(tolerance %.3e) on grid %s" % (mm_err, tol, grid))
    # timed step (second call: compiled) for MFU + the prediction row
    t0 = time.perf_counter()
    blocked_matmul(a, b, block=32, mesh=mesh)
    measured_s = max(time.perf_counter() - t0, 1e-9)
    peak_source, peak = _peak_or_none("float32")
    if peak is not None and "PEAK_F32" not in peak_source:
        failures.append(
            "linalg: f32 matmul graded against %s — MFU must use the "
            "f32 peak table, not bf16" % peak_source)
    pred = predict_summa_time(m, k, n, grid, t1_step_s=measured_s,
                              dtype=numpy.float32)
    for field in ("block_bytes_a_panel", "block_bytes_b_panel",
                  "psum_bytes_per_device",
                  "ici_bw_assumed_bytes_per_s", "ici_bw_source"):
        if field not in pred["inputs"]:
            failures.append(
                "linalg: predict_summa_time hides its %s input — the "
                "prediction must state every assumption" % field)

    # 1b. blocked Cholesky solve vs dense (check=True re-verifies the
    # residual through the trusted dense path and raises on failure)
    size = 72
    g = rng.standard_normal((size, size)).astype(numpy.float32)
    spd = g @ g.T + size * numpy.eye(size, dtype=numpy.float32)
    rhs = rng.standard_normal((size, 3)).astype(numpy.float32)
    try:
        x = numpy.asarray(cholesky_solve(spd, rhs, block=32,
                                         mesh=mesh, check=True))
        xref = numpy.linalg.solve(spd.astype(numpy.float64),
                                  rhs.astype(numpy.float64))
        ch_err = float(numpy.linalg.norm(x - xref)
                       / numpy.linalg.norm(xref))
    except Exception as e:        # noqa: BLE001
        ch_err = float("inf")
        failures.append("linalg: cholesky_solve failed live: %s" % e)
    if not ch_err < tol:
        failures.append(
            "linalg: cholesky solve off dense reference by %.3e "
            "(tolerance %.3e)" % (ch_err, tol))

    # 2. CG on the Poisson model problem, on the Workflow graph
    pn = 16
    prhs = rng.standard_normal(pn * pn).astype(numpy.float32)
    wf = build_cg_workflow(poisson2d_matvec(pn), prhs, tol=1e-6,
                           max_iters=400)
    wf.initialize()
    wf.run()
    cg = wf.cg_decision.get_metric_values()
    if not (cg["converged"] and cg["residual"] < 1e-5):
        failures.append(
            "linalg: CG on the %dx%d Poisson operator did not reach "
            "1e-5 (converged=%s residual=%.3e after %d iters)"
            % (pn, pn, cg["converged"], cg["residual"],
               cg["iterations"]))

    metrics = {
        "grid": "%dx%d" % grid,
        "tolerance": tol,
        "matmul_rel_err": mm_err,
        "chol_rel_err": ch_err,
        "cg_iterations": int(cg["iterations"]),
        "cg_residual": float(cg["residual"]),
        "measured_step_s": measured_s,
        "predicted_step_s": pred["predicted_step_s"],
        "measured_over_predicted": (measured_s
                                    / max(pred["predicted_step_s"],
                                          1e-12)),
    }
    if peak is not None:
        metrics.update(
            mfu=(2.0 * m * n * k) / (measured_s * peak * mesh.size),
            peak_source=peak_source, peak_flops_used=peak)
    return failures, metrics


#: per-chip tokens/sec bar for the tensor-parallel proof: each chip
#: of the tp=2 CPU virtual mesh must deliver at least this fraction
#: of the solo engine's tokens/sec. Deliberately lenient — the CPU
#: mesh pays shard_map's collective overhead on a toy model with no
#: memory-bandwidth win to show; the bar locks "the sharded plane is
#: not pathologically slow", real speedups are a chip measurement
TP_PER_CHIP_FRACTION = 0.10

#: wall budget for the tp proof child (compiles 2x the serving
#: programs: solo + shard_mapped, all on CPU)
TP_CHILD_BUDGET = 600.0


def gate_tp(baseline_doc=None, current_doc=None):
    """``tp`` gate section: (1) every tensor-parallel counter (and
    the autotune staleness counter riding this PR) must be registered
    with a HELP string; (2) bench documents must carry ZERO shard_map
    engine/dispatch activity at tp=1 — the mesh plane leaking into a
    solo measurement would break the tp=1-is-the-pre-mesh-path
    contract; (3) live proof (:func:`_tp_proof`, subprocess): on a
    2-device CPU virtual mesh the tp=2 engine answers token-identical
    to the solo engine, counts its dispatches, reports LOGICAL page
    gauges equal to solo's, and clears the per-chip throughput bar."""
    from veles_tpu.serving import TP_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in TP_COUNTERS + ("veles_autotune_stale_total",):
        if name not in DESCRIPTIONS:
            failures.append(
                "tp: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("tp_serving")
        if not sec:
            continue          # legacy document predating the section
        if int(sec.get("tp", 1) or 1) > 1:
            continue          # a tp-mode bench dispatches on purpose
        for key in ("engines", "dispatches"):
            if sec.get(key):
                failures.append(
                    "tp: %s doc has %s=%s at tp=1 — shard_map "
                    "serving leaked into a solo bench run"
                    % (tag, key, sec[key]))
    proof_failures, metrics = _tp_proof()
    if metrics:
        print("tp proof: tp=%d sharded decode id-exact vs solo, "
              "%d shard_map dispatches, logical kv pool %d bytes on "
              "both, per-chip %.2f tok/s = %.2fx solo (bar %.2fx)"
              % (metrics["tp"], metrics["dispatches"],
                 metrics["kv_tp"], metrics["tp_tok_s"] / metrics["tp"],
                 metrics["per_chip_fraction"], TP_PER_CHIP_FRACTION))
    return failures + proof_failures


def _tp_proof():
    """THE tensor-parallel drill. Runs in a SUBPROCESS because the
    2-device CPU virtual mesh exists only when ``TPU_VISIBLE_CHIPS``
    is set before jax initializes — this (gate) process already has a
    backend up. The child (``bench.py --tp-child``) serves the same
    request mix through a solo (tp=1) and a sharded (tp=2) engine
    and prints one JSON line; asserted here:

    - **id-exact** — the tp=2 tokens equal the solo tokens;
    - **counted** — ``veles_tp_engines_total`` moved exactly once,
      ``veles_tp_dispatches_total`` moved with the decode, and
      NEITHER moved while the solo engine served (zero leakage);
    - **logical gauges** — ``kv_pool_bytes`` identical at tp=1 and
      tp=2 (pages are logical; only bytes-per-chip divides), with
      ``kv_pool_bytes_per_shard`` = the pool over tp;
    - **per-chip throughput** — tp tokens/sec over the chip count
      stays >= ``TP_PER_CHIP_FRACTION`` x the solo tokens/sec.

    Returns (failures, metrics) so the caller can gate and stamp."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TPU_VISIBLE_CHIPS="0,1")
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--tp-child"],
            capture_output=True, text=True, env=env,
            timeout=TP_CHILD_BUDGET)
    except subprocess.TimeoutExpired:
        return ["tp: proof child exceeded %.0fs budget"
                % TP_CHILD_BUDGET], {}
    if r.returncode != 0 or not r.stdout.strip():
        tail = (r.stderr or "").strip().splitlines()
        return ["tp: proof child rc=%d%s"
                % (r.returncode,
                   (": " + tail[-1][-160:]) if tail else "")], {}
    try:
        m = json.loads(r.stdout.strip().splitlines()[-1])
    except ValueError:
        return ["tp: proof child printed no parseable JSON"], {}
    failures = []
    if not m.get("equal"):
        failures.append("tp: tp=%s sharded decode diverged from the "
                        "solo engine" % m.get("tp"))
    if m.get("leak"):
        failures.append("tp: %s tp counter increment(s) while the "
                        "SOLO engine served — tp=1 must run the "
                        "pre-mesh path untouched" % m["leak"])
    if int(m.get("engines", 0)) != 1:
        failures.append("tp: veles_tp_engines_total=%s after one "
                        "tp engine start (want 1)" % m.get("engines"))
    if not m.get("dispatches"):
        failures.append("tp: veles_tp_dispatches_total never moved "
                        "during a sharded serve")
    if m.get("kv_solo") != m.get("kv_tp"):
        failures.append("tp: logical kv_pool_bytes differ — solo %s "
                        "vs tp %s (page gauges must be shard-"
                        "agnostic)" % (m.get("kv_solo"),
                                       m.get("kv_tp")))
    if m.get("kv_shard") != m.get("kv_tp", 0) // max(
            1, int(m.get("tp", 1))):
        failures.append("tp: kv_pool_bytes_per_shard %s != pool %s "
                        "over tp=%s" % (m.get("kv_shard"),
                                        m.get("kv_tp"), m.get("tp")))
    frac = 0.0
    if m.get("solo_tok_s"):
        frac = (m.get("tp_tok_s", 0.0) / max(1, int(m.get("tp", 1)))
                / m["solo_tok_s"])
    if frac < TP_PER_CHIP_FRACTION:
        failures.append(
            "tp: per-chip throughput %.3fx of solo under the %.2fx "
            "bar (solo %.2f tok/s, tp %.2f over %s chips)"
            % (frac, TP_PER_CHIP_FRACTION, m.get("solo_tok_s", 0.0),
               m.get("tp_tok_s", 0.0), m.get("tp")))
    metrics = dict(m, per_chip_fraction=round(frac, 3))
    return failures, metrics


def _tp_child_main():
    """``bench.py --tp-child``: the in-mesh half of :func:`_tp_proof`.
    Runs only under the parent's env (TPU_VISIBLE_CHIPS=0,1 +
    JAX_PLATFORMS=cpu, set before this interpreter imported jax), so
    two virtual CPU devices exist; serves one request mix through a
    solo and a tp=2 engine and prints ONE JSON line."""
    import numpy
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.serving.engine import ContinuousEngine, make_request
    from veles_tpu.telemetry.counters import counters

    tp = len([c for c in os.environ.get(
        "TPU_VISIBLE_CHIPS", "0").split(",") if c.strip()])
    prng.seed_all(971)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    wf.run()

    def requests():
        return [make_request(
            [int(t) for t in char_lm.make_corpus(
                numpy.random.RandomState(100 + i), 10 + i)], 24)
            for i in range(3)]

    def run(tp_n, name):
        eng = ContinuousEngine(wf, max_slots=4, buckets=(8, 16, 32),
                               max_context=64, page_size=8, tp=tp_n,
                               name=name).start()
        try:
            eng.serve([make_request(requests()[0]["prompt"], 2)])
            t0 = time.time()
            toks = eng.serve(requests())
            dt = max(time.time() - t0, 1e-9)
            st = eng.stats()
        finally:
            eng.stop()
        return toks, sum(len(t) for t in toks) / dt, st

    solo_toks, solo_tps, solo_st = run(1, "tp_proof_solo")
    leak = int(counters.get("veles_tp_dispatches_total")) \
        + int(counters.get("veles_tp_engines_total"))
    tp_toks, tp_tps, tp_st = run(tp, "tp_proof_mesh")
    print(json.dumps({
        "tp": tp,
        "equal": tp_toks == solo_toks,
        "leak": leak,
        "engines": int(counters.get("veles_tp_engines_total")),
        "dispatches": int(
            counters.get("veles_tp_dispatches_total")),
        "solo_tok_s": round(solo_tps, 3),
        "tp_tok_s": round(tp_tps, 3),
        "kv_solo": int(solo_st["kv_pool_bytes"]),
        "kv_tp": int(tp_st["kv_pool_bytes"]),
        "kv_shard": int(tp_st["kv_pool_bytes_per_shard"]),
    }))
    return 0


def _tp_main():
    """``python bench.py tp`` — run the tensor-parallel drill
    standalone and print its metrics as one JSON line (the numbers
    docs/perf.md's tp row cites)."""
    failures, metrics = _tp_proof()
    for failure in failures:
        print("TP FAIL %s" % failure, file=sys.stderr)
    print(json.dumps(dict(metrics, failures=len(failures))))
    return 1 if failures else 0


def gate_overload(baseline_doc=None, current_doc=None):
    """``overload`` gate section: (1) every QoS + loadgen counter
    must be registered with a HELP string; (2) bench documents must
    carry ZERO QoS/loadgen activity — the bench runs QoS-off, so a
    preemption/throttle/brownout/loadgen count in a training
    measurement means the overload plane leaked into the feature-off
    path; (3) the clean gate process must read zero before the
    drill; (4) live drill (:func:`_overload_proof`): preempted batch
    decodes finish bit-identical to their uninterrupted solo runs
    (greedy AND sampled) with exactly-once terminal accounting, and
    an open-loop loadgen burst at ~2x sustained capacity against a
    2-replica QoS fleet keeps interactive lossless and within SLO
    while batch absorbs the pressure, ledgers draining to zero."""
    from veles_tpu.loadgen import LOADGEN_COUNTERS
    from veles_tpu.serving import QOS_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS, counters
    failures = []
    for name in QOS_COUNTERS + LOADGEN_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "overload: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("overload")
        if not sec:
            continue
        for key, value in sec.items():
            if value:
                failures.append(
                    "overload: %s doc has %s=%s — QoS/loadgen work "
                    "leaked into a QoS-off bench run"
                    % (tag, key, value))
    # the zero check must precede the live drill (which preempts,
    # throttles and load-generates for real)
    for name in QOS_COUNTERS + LOADGEN_COUNTERS:
        value = counters.get(name)
        if value:
            failures.append(
                "overload: %s = %s before any QoS machinery ran in "
                "this process" % (name, value))
    proof_failures, metrics = _overload_proof()
    if metrics:
        print("overload proof: preempted batch id-exact "
              "(greedy+sampled, %d preemption(s), %d token(s) "
              "carried), %d-request 2x burst on a 2-replica QoS "
              "fleet — interactive lossless (ttft_p99 %sms), %d "
              "throttle(s)/%d deferral(s), goodput %.1f tok/s, "
              "exactly-once terminals, ledgers zero"
              % (metrics["preemptions"], metrics["preempted_tokens"],
                 metrics["offered"], metrics["interactive_ttft_p99_ms"],
                 metrics["throttled"], metrics["deferrals"],
                 metrics["goodput_tokens_per_s"]))
    return failures + proof_failures


def _overload_proof():
    """THE overload drill, live on this process's backend, two parts.

    **Preempt-and-resume lock** — one tiny char_lm stack on a
    1-slot QoS engine, driven TICK BY TICK (the engine is never
    started; step boundaries are explicit, so the preemption point is
    deterministic): a batch decode is run solo for the reference,
    then re-run and preempted mid-decode by an interactive arrival.
    The batch request must requeue, resume and finish **bit-identical
    to its uninterrupted solo decode** — greedy AND sampled — with
    exactly one terminal per request (e2e/queue-wait histogram counts
    and the admitted counter move once per request, however many
    times the row bounced) and the page ledger at zero after drain.

    **Overload drill** — two QoS GenerationAPI replicas behind a
    QoS FleetRouter, hit by an open-loop loadgen burst (mixed
    interactive/batch, ~2x what the 4 total slots sustain). The
    interactive class must come through lossless and within a
    generous SLO while the QoS plane visibly works (throttles,
    deferrals or preemptions > 0), goodput must not collapse, every
    offered request must be answered exactly once (server-side
    retired terminals == client-side 200s), and both replicas'
    page/queue ledgers must read zero after the drain.

    Returns (failures, metrics) so the caller can gate and stamp."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.config import root as vt_root
    from veles_tpu.loadgen import LoadGen, Workload
    from veles_tpu.loadgen import verdict as loadgen_verdict
    from veles_tpu.serving.engine import ContinuousEngine, make_request
    from veles_tpu.serving.router import FleetRouter
    from veles_tpu.serving.scheduler import Ticket
    from veles_tpu.telemetry.counters import counters as _ctrs
    from veles_tpu.telemetry.counters import histograms

    failures = []
    prng.seed_all(8282)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    rng = numpy.random.RandomState(41)
    prompt_b = [int(t) for t in rng.randint(0, char_lm.VOCAB, 6)]
    prompt_i = [int(t) for t in rng.randint(0, char_lm.VOCAB, 5)]

    # -- part 1: preempt-and-resume bit-identical, greedy AND sampled
    vt_root.common.serving.qos = True
    preemptions = preempted_tokens = 0
    try:
        for mode, temp in (("greedy", 0.0), ("sample", 0.9)):
            eng = ContinuousEngine(wf, max_slots=1, buckets=(8, 24),
                                   max_context=48,
                                   name="bench_overload_" + mode)

            def drive(done, limit=3000):
                for _ in range(limit):
                    if done():
                        return True
                    eng._tick()
                return done()

            req = make_request(prompt_b, 16, temperature=temp,
                               seed=77, mode=mode)
            req["priority"] = "batch"
            # uninterrupted solo decode: THE reference
            t_solo = Ticket()
            eng.submit(dict(req), t_solo)
            if not drive(t_solo.event.is_set):
                failures.append("overload: %s solo reference decode "
                                "never finished" % mode)
                continue
            expected = t_solo.result["tokens"]
            e2e0 = histograms.count("veles_serving_e2e_seconds")
            qw0 = histograms.count("veles_serving_queue_wait_seconds")
            adm0 = _ctrs.get("veles_serving_admitted_total")
            # the same request again — preempted mid-decode this time
            t_b, t_i = Ticket(), Ticket()
            eng.submit(dict(req), t_b)

            def mid_decode():
                active = eng.scheduler.active()
                return bool(active and active[0].tokens
                            and active[0].prefilled is None
                            and len(active[0].tokens) < 12)
            if not drive(mid_decode, limit=200):
                failures.append("overload: %s batch row never reached "
                                "mid-decode" % mode)
            req_i = make_request(prompt_i, 4)
            req_i["priority"] = "interactive"
            eng.submit(req_i, t_i)
            if not drive(lambda: t_b.event.is_set()
                         and t_i.event.is_set()):
                failures.append(
                    "overload: %s preemption drill never drained"
                    % mode)
                continue
            if t_i.error is not None:
                failures.append(
                    "overload: interactive co-tenant failed in the "
                    "%s drill: %s" % (mode, t_i.error))
            if t_b.error is not None \
                    or t_b.result["tokens"] != expected:
                failures.append(
                    "overload: preempted %s batch decode diverged "
                    "from its uninterrupted solo run" % mode)
            if eng.preemptions < 1:
                failures.append(
                    "overload: the %s drill finished without a "
                    "preemption — slot pressure never forced the "
                    "batch row out" % mode)
            preemptions += eng.preemptions
            preempted_tokens += eng.preempted_tokens
            # exactly-once terminal accounting across
            # preempt -> requeue -> finish: 2 requests, 2 samples in
            # every per-request histogram, 2 admissions — however
            # many times the batch row bounced
            e2e_d = histograms.count("veles_serving_e2e_seconds") \
                - e2e0
            qw_d = histograms.count(
                "veles_serving_queue_wait_seconds") - qw0
            adm_d = _ctrs.get("veles_serving_admitted_total") - adm0
            if not e2e_d == qw_d == int(adm_d) == 2:
                failures.append(
                    "overload: %s terminal accounting not "
                    "exactly-once (e2e %d, queue_wait %d, admitted "
                    "%d for 2 requests)" % (mode, e2e_d, qw_d, adm_d))
            if eng.page_pool.in_use():
                failures.append(
                    "overload: %d page(s) still held after the %s "
                    "drill drained"
                    % (eng.page_pool.in_use(), mode))
    finally:
        vt_root.common.serving.qos = False

    # -- part 2: the 2x overload drill through loadgen
    vt_root.common.serving.qos = True
    vt_root.common.router.qos = True
    vt_root.common.router.slo_ttft_ms = 500.0
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8, 16),
                             max_context=32,
                             name="overload_bench_%d" % i)
            for i in range(2)]
    router = None
    metrics = {}
    try:
        for api in apis:
            api.initialize()
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=3,
            retry_budget=2, attempt_timeout=60.0,
            request_timeout=90.0, name="overload_bench.router").start()
        # ~2x capacity: 24 mixed requests offered in well under the
        # fleet's 4-slot service time — the queue MUST form
        workload = Workload(n_requests=24, rate=400.0, shape="burst",
                            min_prompt=4, max_prompt=8, n_new=4,
                            vocab=char_lm.VOCAB, batch_fraction=0.5,
                            stream_fraction=0.0, sample_fraction=0.0,
                            shared_fraction=0.25, seed=11)
        e2e0 = histograms.count("veles_serving_e2e_seconds")
        pressure0 = sum(int(_ctrs.get(n)) for n in
                        ("veles_qos_throttled_total",
                         "veles_qos_preemptions_total",
                         "veles_qos_batch_deferrals_total"))
        report = LoadGen("http://127.0.0.1:%d" % router.port,
                         workload, timeout=120.0,
                         name="bench.loadgen").run()
        agg = report["aggregates"]
        slo = loadgen_verdict(report, slo_ttft_ms=30000.0,
                              max_interactive_loss=0.0,
                              min_goodput_tokens_per_s=0.5)
        if report["answered"] != report["offered"]:
            failures.append(
                "overload: %d of %d offered requests never answered"
                % (report["offered"] - report["answered"],
                   report["offered"]))
        accounted = sum(agg[c]["ok"] + agg[c]["shed"]
                        + agg[c]["errors"]
                        for c in ("interactive", "batch"))
        if accounted != report["offered"]:
            failures.append(
                "overload: %d terminals for %d offered requests — "
                "a request was dropped or double-answered"
                % (accounted, report["offered"]))
        if agg["interactive"]["shed"] or agg["interactive"]["errors"]:
            failures.append(
                "overload: interactive lost %d shed + %d errors "
                "under the burst — the protected class must come "
                "through lossless"
                % (agg["interactive"]["shed"],
                   agg["interactive"]["errors"]))
        for check in slo["checks"]:
            if not check["ok"]:
                failures.append(
                    "overload: SLO verdict failed %s (%s vs bound "
                    "%s)" % (check["name"], check["observed"],
                             check["bound"]))
        pressure = sum(int(_ctrs.get(n)) for n in
                       ("veles_qos_throttled_total",
                        "veles_qos_preemptions_total",
                        "veles_qos_batch_deferrals_total")) \
            - pressure0
        if pressure < 1:
            failures.append(
                "overload: the 2x burst never pressured the QoS "
                "plane (no throttle, no preemption, no deferral)")
        # server-side retired terminals == client-side 200s:
        # exactly-once through however much requeueing happened
        ok_total = agg["interactive"]["ok"] + agg["batch"]["ok"]
        e2e_d = histograms.count("veles_serving_e2e_seconds") - e2e0
        if e2e_d != ok_total:
            failures.append(
                "overload: %d retired terminals server-side for %d "
                "client 200s — terminal accounting broke under "
                "load" % (e2e_d, ok_total))
        deadline = time.time() + 15
        while time.time() < deadline and any(
                api._engine.scheduler.busy_count()
                or api._engine.scheduler.queue_depth()
                for api in apis):
            time.sleep(0.1)
        for api in apis:
            held = api._engine.page_pool.in_use()
            if held or api._engine.scheduler.queue_depth():
                failures.append(
                    "overload: replica %s ledger dirty after drain "
                    "(%d pages held, %d queued)"
                    % (api.name, held,
                       api._engine.scheduler.queue_depth()))
        metrics = {
            "preemptions": int(preemptions),
            "preempted_tokens": int(preempted_tokens),
            "offered": report["offered"],
            "interactive_ttft_p99_ms":
                agg.get("server_ttft_p99_ms")
                or agg["interactive"]["ttft_p99_ms"],
            "throttled": int(_ctrs.get("veles_qos_throttled_total")),
            "deferrals": int(
                _ctrs.get("veles_qos_batch_deferrals_total")),
            "goodput_tokens_per_s": agg["goodput_tokens_per_s"],
        }
    finally:
        vt_root.common.serving.qos = False
        vt_root.common.router.qos = False
        if router is not None:
            router.stop()
        for api in apis:
            api.stop()
    return failures, metrics


def gate_watch(baseline_doc=None, current_doc=None):
    """``watch`` gate section: (1) every watchtower counter must be
    registered with a HELP string; (2) bench documents stamped with
    the watchtower OFF must carry ZERO sample/eval/transition counts —
    off means the sampler thread never spawns, so any movement breaks
    the bit-identical-off contract; (3) the clean gate process must
    read zero AND hold no live store/engine/firing-gauge rows before
    the drill — every gate above served, routed and load-generated
    with the knob off, so this check IS the zero-leakage live proof;
    (4) live drill (:func:`_watch_proof`): a decode-delay chaos storm
    burns the TTFT SLO on a live 2-replica fleet until
    ``slo_ttft_burn`` fires within its fast window (the loadgen
    ``--abort-on-alert`` poller stops the burst at fire time), the
    healed fleet resolves it, and the fire→resolve pair is visible in
    the ``/metrics/history`` cursor pull, the flight recorder and a
    ``veles-tpu watch`` dashboard snapshot."""
    from veles_tpu.telemetry import WATCH_COUNTERS, timeseries
    from veles_tpu.telemetry.alerts import render_firing
    from veles_tpu.telemetry.counters import DESCRIPTIONS, counters
    failures = []
    for name in WATCH_COUNTERS + ("veles_loadgen_alert_aborts_total",):
        if name not in DESCRIPTIONS:
            failures.append(
                "watch: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("watch")
        if not sec or sec.get("enabled"):
            continue
        for key, value in sec.items():
            if key != "enabled" and value:
                failures.append(
                    "watch: %s doc has %s=%s — the watch sampler/"
                    "alert engine moved with the knob off" %
                    (tag, key, value))
    # the frozen-off check must precede the live drill: every gate
    # above served, routed and load-generated for real with the
    # watchtower off, so a live store, a rendered veles_alert_firing
    # row or a moved counter here means off is not off
    if timeseries.store() is not None \
            or timeseries.alert_engine() is not None:
        failures.append(
            "watch: a live SeriesStore/AlertEngine exists before the "
            "drill — maybe_start leaked with the knob off")
    if render_firing() != "":
        failures.append(
            "watch: /metrics would render veles_alert_firing rows "
            "with the watchtower off")
    for name in WATCH_COUNTERS + ("veles_loadgen_alert_aborts_total",):
        value = counters.get(name)
        if value:
            failures.append(
                "watch: %s = %s before the watchtower ever ran in "
                "this process" % (name, value))
    proof_failures, metrics = _watch_proof()
    if metrics:
        print("watch proof: decode-delay storm burned the %.0fms "
              "TTFT SLO on a 2-replica fleet — slo_ttft_burn fired "
              "%.2fs after the first bad sample (fast window %.0fs), "
              "loadgen --abort-on-alert stopped the burst after "
              "%d/%d requests, the healed fleet resolved it; "
              "fire→resolve visible in /metrics/history (%d samples, "
              "%d transition records), the flight recorder and the "
              "`veles-tpu watch` snapshot"
              % (metrics["slo_ttft_ms"], metrics["fired_after_s"],
                 metrics["fast_window_s"], metrics["aborted_after"],
                 metrics["offered"], metrics["samples"],
                 metrics["transition_records"]))
    return failures + proof_failures


def _watch_proof():
    """THE watchtower drill, live on this process's backend.

    A 2-replica char_lm fleet behind a FleetRouter runs with the
    watchtower ON (short windows: period 0.25 s, fast 2 s / slow 6 s,
    TTFT SLO 250 ms, burn factor 2 over a 0.95 objective). An
    open-loop loadgen burst rides a ``serve.decode_step:delay`` chaos
    storm, so queue wait blows the TTFT SLO and the burn-rate rule
    must fire — within its fast window of the first bad sample
    landing in the ring — while the harness's ``--abort-on-alert``
    poller stops dispatching at fire time. The storm then heals
    (StormPlan restores the fault plane) and a clean burst must
    resolve the alert through the rule's hysteresis. The fire→resolve
    pair must be observable everywhere an operator would look: the
    ``/metrics/history`` cursor pull over HTTP (ordered with the
    samples that caused it, detection latency computed from those
    same records), the flight recorder, and a live ``veles-tpu watch
    --once`` dashboard snapshot taken while the alert was firing.

    Returns (failures, metrics) so the caller can gate and stamp."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import io
    import urllib.request
    from contextlib import redirect_stdout
    import char_lm
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.config import root as vt_root
    from veles_tpu.loadgen import ChaosStorm, LoadGen, Workload
    from veles_tpu.serving.router import FleetRouter
    from veles_tpu.telemetry import timeseries
    from veles_tpu.telemetry.counters import counters as _ctrs
    from veles_tpu.telemetry.recorder import flight
    from veles_tpu.telemetry.timeseries import parse_history

    failures = []
    metrics = {}
    prng.seed_all(6464)
    wf = char_lm.build_workflow(epochs=1, minibatch_size=32,
                                n_blocks=1, dim=32, n_train=64,
                                n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))

    PERIOD, FAST, SLOW, SLO_MS = 0.25, 2.0, 6.0, 250.0
    watch = vt_root.common.telemetry.watch
    # drill-sized knobs, restored to the shipped defaults in the
    # finally below; e2e/queue/shed rules are parked out of range so
    # the drill exercises exactly the TTFT burn-rate pair
    overrides = {"enabled": True, "period": PERIOD,
                 "retention": 120.0, "fast_window": FAST,
                 "slow_window": SLOW, "burn_factor": 2.0,
                 "objective": 0.95, "slo_ttft_ms": SLO_MS,
                 "slo_e2e_ms": 600000.0,
                 "queue_depth_limit": 100000.0,
                 "shed_rate_limit": 100000.0}
    defaults = {"enabled": False, "period": 1.0, "retention": 300.0,
                "fast_window": 30.0, "slow_window": 120.0,
                "burn_factor": 6.0, "objective": 0.99,
                "slo_ttft_ms": 500.0, "slo_e2e_ms": 5000.0,
                "queue_depth_limit": 64.0, "shed_rate_limit": 5.0}
    saved = {k: watch.get(k, defaults[k]) for k in overrides}
    for key, value in overrides.items():
        setattr(watch, key, value)

    def workload(n, rate, seed):
        return Workload(n_requests=n, rate=rate, shape="steady",
                        min_prompt=4, max_prompt=8, n_new=4,
                        vocab=char_lm.VOCAB, batch_fraction=0.0,
                        stream_fraction=0.0, sample_fraction=0.0,
                        shared_fraction=0.0, seed=seed)

    def alert_events():
        store = timeseries.store()
        return [] if store is None else [
            e for e in store.records("watch.alert")
            if e.get("rule") == "slo_ttft_burn"]

    apis, router = [], None
    try:
        apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                                 max_slots=2, buckets=(8,),
                                 max_context=24,
                                 name="watch_bench_%d" % i)
                for i in range(2)]
        for api in apis:
            api.initialize()
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=3,
            retry_budget=2, attempt_timeout=60.0,
            request_timeout=120.0, name="watch_bench.router").start()
        url = "http://127.0.0.1:%d" % router.port
        if timeseries.store() is None:
            failures.append(
                "watch: the sampler never started with the knob ON")
            return failures, {}
        # -- storm phase: burn the TTFT budget until the alert fires.
        # Every decode step sleeps 50 ms for the whole burst, so
        # queue wait (and the cold compiles) push TTFT far over the
        # 250 ms SLO; the abort poller must stop the burst mid-flight
        storm = ChaosStorm("serve.decode_step", "delay",
                           window=(0, 1000000))
        offered = 80
        report = LoadGen(url, workload(offered, 8.0, seed=5),
                         storms=[storm], timeout=120.0,
                         abort_on_alert=True, alert_poll=0.2,
                         name="bench.watch_storm").run()
        aborted = report.get("aborted_on_alert")
        if not aborted:
            failures.append(
                "watch: the storm burst ran all %d requests to "
                "completion without the --abort-on-alert poller "
                "tripping — no rule fired while load was offered"
                % offered)
        if int(_ctrs.get("veles_loadgen_alert_aborts_total")) != 1:
            failures.append(
                "watch: veles_loadgen_alert_aborts_total = %s after "
                "one aborted burst"
                % _ctrs.get("veles_loadgen_alert_aborts_total"))
        deadline = time.time() + 30
        fire_ev = None
        while time.time() < deadline and fire_ev is None:
            fire_ev = next((e for e in alert_events()
                            if e.get("state") == "firing"), None)
            if fire_ev is None:
                time.sleep(0.2)
        if fire_ev is None:
            failures.append(
                "watch: slo_ttft_burn never fired under the "
                "decode-delay storm")
            return failures, {}
        # -- dashboard snapshot while firing: the operator view must
        # show the alert (served over HTTP by the live router)
        from veles_tpu.__main__ import _watch_cli
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = _watch_cli([url, "--once", "--no-clear",
                             "--period", "0.5", "--window", "5"])
        frame = buf.getvalue()
        if rc != 0:
            failures.append(
                "watch: `veles-tpu watch --once` exited %d against "
                "the live fleet" % rc)
        if "slo_ttft_burn" not in frame or "FIRING" not in frame:
            failures.append(
                "watch: the dashboard snapshot does not show the "
                "firing slo_ttft_burn alert")
        # -- heal phase: the storm is gone (StormPlan restored the
        # fault plane when the burst returned); clean traffic must
        # walk the rule back to ok through its resolve hysteresis
        resolve_ev = None
        for round_ in range(4):
            LoadGen(url, workload(40, 12.0, seed=6 + round_),
                    timeout=120.0,
                    name="bench.watch_heal_%d" % round_).run()
            resolve_ev = next(
                (e for e in alert_events()
                 if e.get("state") == "resolved"
                 and e.get("ts", 0) > fire_ev["ts"]), None)
            if resolve_ev is not None:
                break
        if resolve_ev is None:
            failures.append(
                "watch: slo_ttft_burn never resolved after the storm "
                "healed (%d clean requests served)" % (4 * 40))
        # -- the operator pull: one HTTP cursor pull must carry the
        # whole story — samples AND both transitions, in order
        with urllib.request.urlopen(url + "/metrics/history?since=0",
                                    timeout=10) as resp:
            header, records = parse_history(resp.read().decode())
        if not header or not header.get("enabled"):
            failures.append(
                "watch: /metrics/history header does not report the "
                "watchtower live")
        samples = [r for r in records
                   if r.get("kind") == "watch.sample"]
        transitions = [r for r in records
                       if r.get("kind") == "watch.alert"
                       and r.get("rule") == "slo_ttft_burn"]
        states = [r.get("state") for r in transitions]
        if "firing" not in states or "resolved" not in states:
            failures.append(
                "watch: the /metrics/history pull is missing the "
                "slo_ttft_burn firing/resolved transitions (saw %s)"
                % states)
        # detection latency, computed from the SAME pulled records an
        # operator would read: first sample whose TTFT histogram grew
        # a bucket above the SLO, to the firing transition. Must land
        # within the fast window (+ two sample periods of eval grace)
        fired_after = None
        prev_bad = None
        for rec in samples:
            h = (rec.get("hist") or {}).get(
                "veles_serving_ttft_seconds")
            if not h:
                continue
            good = sum(c for b, c in zip(h["bounds"], h["counts"])
                       if float(b) * 1000.0 <= SLO_MS)
            bad = int(h.get("count", 0)) - good
            if prev_bad is not None and bad > prev_bad \
                    and rec.get("ts", 0) <= fire_ev["ts"]:
                fired_after = fire_ev["ts"] - rec["ts"]
                break
            prev_bad = bad
        if fired_after is None:
            failures.append(
                "watch: the pulled samples never show a TTFT "
                "observation over the SLO before the firing "
                "transition")
        elif fired_after > FAST + 2 * PERIOD:
            failures.append(
                "watch: slo_ttft_burn took %.2fs after the first bad "
                "sample to fire — outside the %.1fs fast window"
                % (fired_after, FAST))
        # -- the flight recorder holds the same transitions (what
        # `veles-tpu blackbox inspect` prints after a crash)
        if flight.enabled():
            seen = [(r.get("rule"), r.get("state"))
                    for r in flight.records()
                    if r.get("kind") == "alert"]
            for state in ("firing", "resolved"):
                if ("slo_ttft_burn", state) not in seen:
                    failures.append(
                        "watch: flight recorder is missing the "
                        "slo_ttft_burn %s transition" % state)
        if not int(_ctrs.get("veles_watch_samples_total")):
            failures.append("watch: the sampler counted zero samples "
                            "over the whole drill")
        if not int(_ctrs.get("veles_watch_pulls_total")):
            failures.append("watch: the /metrics/history pull was "
                            "not counted")
        metrics = {
            "slo_ttft_ms": SLO_MS,
            "fast_window_s": FAST,
            "fired_after_s": round(fired_after or -1.0, 2),
            "aborted_after": int((aborted or {}).get(
                "after_requests", offered)),
            "offered": offered,
            "samples": len(samples),
            "transition_records": len(transitions),
        }
    finally:
        try:
            if router is not None:
                router.stop()
        finally:
            for api in apis:
                api.stop()
            timeseries.stop_watch()
            for key, value in saved.items():
                setattr(watch, key, value)
    if failures:
        metrics = {}
    return failures, metrics


def gate_tensormon(baseline_doc=None, current_doc=None):
    """``tensormon`` gate section: (1) the model-health counters must
    be registered; (2) a monitoring-OFF bench document must carry ZERO
    tensormon samples/NaN detections — taps leaking into an
    unmonitored step would break the bit-identical-off contract;
    (3) live proof that the flight recorder's per-event overhead stays
    under budget (it sits on the span-close and counter hot paths)."""
    from veles_tpu.telemetry import TENSORMON_COUNTERS
    from veles_tpu.telemetry.counters import DESCRIPTIONS
    failures = []
    for name in TENSORMON_COUNTERS:
        if name not in DESCRIPTIONS:
            failures.append(
                "tensormon: counter %s not registered in telemetry "
                "DESCRIPTIONS" % name)
    for tag, doc in (("baseline", baseline_doc),
                     ("current", current_doc)):
        sec = (doc or {}).get("tensormon")
        if not sec or sec.get("enabled"):
            continue
        for key in ("samples", "nan_total"):
            if sec.get(key):
                failures.append(
                    "tensormon: %s doc has %s=%s with monitoring OFF "
                    "— taps leaked into the unmonitored step"
                    % (tag, key, sec[key]))
    return failures + _recorder_overhead_proof()


def _recorder_overhead_proof():
    """Fill a private full-capacity flight-recorder ring and check the
    per-event cost: 4096 small-dict appends must land well under 1 s
    (~244 µs/event — a deque append measures ~1 µs, so the budget
    carries >100x scheduler-jitter margin). Ring semantics checked
    too: capacity respected, newest events win."""
    import time as _t
    from veles_tpu.config import root as vt_root
    from veles_tpu.telemetry.recorder import FlightRecorder
    n = 4096
    # follow_config=True: measure the SHIPPED per-event path (enabled
    # + capacity lookups included), not a cheaper private variant
    rec = FlightRecorder(capacity=n, follow_config=True)
    if not rec.enabled():
        return []            # recorder disabled by config: no budget
    prev_cap = vt_root.common.telemetry.recorder.get("capacity", n)
    vt_root.common.telemetry.recorder.capacity = n
    try:
        t0 = _t.time()
        for i in range(n + 8):
            rec.note("bench", i=i)
        elapsed = _t.time() - t0
    finally:
        vt_root.common.telemetry.recorder.capacity = prev_cap
    failures = []
    stats = rec.stats()
    if stats["buffered"] != n:
        failures.append(
            "tensormon: recorder ring holds %d events at capacity %d"
            % (stats["buffered"], n))
    recs = rec.records()
    if not recs or recs[-1].get("i") != n + 7:
        failures.append(
            "tensormon: recorder ring did not keep the newest events")
    if elapsed > 1.0:
        failures.append(
            "tensormon: recorder overhead %.3fs for %d events exceeds "
            "the 1.0s budget (%.1f us/event)"
            % (elapsed, n + 8, 1e6 * elapsed / (n + 8)))
    return failures


def _gate_main(argv):
    """``python bench.py gate BASELINE.json CURRENT.json`` — exit 1 on
    any counter regression, device-time regression beyond the stated
    tolerance (wall-clock only as the counted legacy fallback),
    resilience-counter leakage, overlap stall
    regression/leakage, tensormon-off leakage, recorder overhead
    overrun, serving-counter leakage or a continuous-batching engine
    that fails to beat the window-coalescing baseline."""
    if len(argv) != 2:
        print("usage: bench.py gate BASELINE.json CURRENT.json",
              file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        baseline = json.load(f)
    with open(argv[1]) as f:
        current = json.load(f)
    failures = (gate_docs(baseline, current)
                + gate_devtime(baseline, current)
                + gate_resilience()
                + gate_elastic(baseline, current)
                + gate_overlap(baseline, current)
                + gate_tensormon(baseline, current)
                + gate_serving(baseline, current)
                + gate_fleet(baseline, current)
                # AFTER gate_fleet: its dying-gasp failovers
                # legitimately move the resume counters, so the
                # lossless gate asserts deltas, never process zeros
                + gate_lossless(baseline, current)
                # AFTER the fleet/lossless drills: their request
                # spans legitimately live in the ring, so the tracing
                # gate asserts doc leakage + its own live proof
                + gate_tracing(baseline, current)
                # AFTER every serving drill: prefix leakage is a
                # DOCUMENT assertion + its own live share/stream/
                # stall proof
                + gate_prefix(baseline, current)
                + gate_quant(baseline, current)
                # the O(1)-state drill serves its own private pool,
                # so like the others it runs after the doc-leakage
                # assertions above
                + gate_o1state(baseline, current)
                # the linalg drill runs its own blocked kernels and
                # solver (moving veles_linalg_* in THIS process), so
                # like the other live proofs it runs after every
                # doc-leakage assertion above
                + gate_linalg(baseline, current)
                # the tp drill runs in its OWN subprocess (the CPU
                # virtual mesh needs TPU_VISIBLE_CHIPS before jax
                # init), so it moves no counter in this process —
                # only its doc-leakage assertions run here
                + gate_tp(baseline, current)
                # the overload drill preempts, throttles and
                # load-generates for real — its own zero-before-proof
                # check must see a process no earlier QoS work
                # touched, and it legitimately moves the serving/
                # router counters every gate above already proved
                + gate_overload(baseline, current)
                # LAST: the watchtower drill turns the sampler ON —
                # its frozen-off check must see a process where every
                # earlier drill served/routed/loadgened with the
                # knob off and no veles_watch_*/veles_alert_* counter
                # ever moved (and gate_overload's own
                # zero-before-proof already ran)
                + gate_watch(baseline, current))
    for failure in failures:
        print("GATE FAIL %s" % failure, file=sys.stderr)
    if failures:
        return 1
    from veles_tpu.telemetry.counters import counters as _counters
    legacy = int(_counters.get("veles_bench_legacy_sections_total"))
    print("counter gate OK (%s vs %s; device-time gate passed%s, "
          "resilience counters clean, elastic counters clean + "
          "reshard in budget, "
          "overlap stall proof passed, tensormon clean, recorder "
          "overhead in budget, serving counters + SLO histograms "
          "clean + continuous "
          "batching beats the window baseline, fleet counters clean "
          "+ 2-replica failover drill exactly-once, lossless clean "
          "+ journaled resume id-exact and cheaper than redo, "
          "tracing clean + router-path dispatch lock + one merged "
          "fleet trace across a replica death, prefix clean + "
          "share-ratio FLOP bound + streamed TTFT + chunk stall "
          "bound, quant "
          "clean + int8 greedy token-exact + artifact serves with "
          "zero compiles, o1state clean + pooled scan/recurrent "
          "id-exact + flat state bytes + equal-HBM slot multiplier, "
          "linalg clean + blocked matmul/Cholesky within dense "
          "tolerance + CG converged and re-verified + f32-peak MFU "
          "stamped, tp clean + sharded decode id-exact on a 2-chip "
          "virtual mesh + logical page gauges shard-agnostic + "
          "per-chip throughput above bar, "
          "overload clean + preempted batch id-exact + interactive "
          "lossless under a 2x burst + exactly-once terminals, "
          "watch frozen-off clean + storm-fired burn-rate alert "
          "within its fast window + resolved after heal + "
          "transitions visible on every surface)"
          % (argv[1], argv[0],
             " — %d legacy section(s) compared on wall-clock" % legacy
             if legacy else ""))
    return 0


def main():
    """One process, one chip: take the device through the strict
    ``Device_for("tpu")``, run the three training sections, print ONE
    JSON line. Without a chip — or when any section fails — the exit
    code is non-zero and no metric line is printed: a host number never
    goes under ``mnist784_train_samples_per_sec_per_chip``."""
    import veles_tpu as vt
    try:
        dev = vt.Device_for("tpu")
    except vt.VelesError as e:
        print("bench: %s" % e, file=sys.stderr)
        return 1
    import jax
    n_chips = dev.device_count
    mnist = bench_mnist(dev, n_chips)
    ae = bench_conv_ae(dev, n_chips)
    lm = bench_lm(dev, n_chips)
    print(json.dumps(_assemble(
        mnist, ae, lm, dev.platform, str(jax.devices()[0].device_kind),
        allow_rebaseline=True)))
    return 0


def _quant_main():
    """``python bench.py quant`` — run the fp-vs-int8 + AOT-artifact
    serving measurement standalone and print its metrics as one JSON
    line (the numbers docs/perf.md's quant rows cite)."""
    failures, metrics = _quant_serving_proof()
    for failure in failures:
        print("QUANT FAIL %s" % failure, file=sys.stderr)
    print(json.dumps(dict(metrics, failures=len(failures))))
    return 1 if failures else 0


def _linalg_main():
    """``python bench.py linalg`` — run the distributed linear-algebra
    drill standalone (blocked-vs-dense residuals, CG convergence,
    f32-peak MFU, SUMMA prediction) and print its metrics as one JSON
    line (the numbers docs/perf.md's linalg row cites)."""
    failures, metrics = _linalg_proof()
    for failure in failures:
        print("LINALG FAIL %s" % failure, file=sys.stderr)
    print(json.dumps(dict(metrics, linalg_bench=True,
                          failures=len(failures))))
    return 1 if failures else 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "gate":
        sys.exit(_gate_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "quant":
        sys.exit(_quant_main())
    if len(sys.argv) > 1 and sys.argv[1] == "linalg":
        sys.exit(_linalg_main())
    if len(sys.argv) > 1 and sys.argv[1] == "tp":
        sys.exit(_tp_main())
    if len(sys.argv) > 1 and sys.argv[1] == "--tp-child":
        sys.exit(_tp_child_main())
    sys.exit(main())
