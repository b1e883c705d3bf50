"""Device self-time: the measurement plane behind the perf gates.

Every perf claim before this module keyed off wall-clock medians,
which were measured swinging up to 7.6× between windows on a shared
machine. Device *self-time* — the seconds the compute stream actually
spent executing programs — is immune to host scheduling, queue depth
and noisy neighbours, so ``bench.py`` stamps it
per section and ``bench.py gate`` compares IT, with wall-clock only as
a counted legacy fallback. Two sources, in preference order:

1. **Profiler capture** (``jax.profiler.start_trace``/``stop_trace``):
   the profiler writes a Chrome trace-event stream
   (``plugins/profile/<run>/<host>.trace.json.gz``) whose *processes*
   include one per device (``/device:TPU:0`` …) with per-stream
   threads ("XLA Ops"). :func:`device_self_time` interval-unions those
   device-stream events — nested/overlapping events never double
   count — and :func:`attribute_spans` maps the device intervals onto
   the telemetry span records (:mod:`~veles_tpu.telemetry.spans`) by
   time overlap, so the operator view (``veles-tpu trace self-time``)
   and the gate read the same numbers.
2. **Host-sync fallback**: on backends where the capture yields no
   device streams (the CPU CI backend traces only ``/host:CPU``), or
   where the profiler is unavailable, the fallback times the caller's
   ``lax``-loop harness (the fused epoch/decode programs — one
   dispatch each) bracketed by the caller's sync (``bench.py
   host_sync``: ``jax.block_until_ready``). Sync-to-sync wall time
   of a single-dispatch program is device time plus one host round
   trip — an upper bound, stamped ``source="host_sync"`` and counted
   (``veles_devtime_fallbacks_total``) so a gate reading fallback
   numbers knows it.

The comparison arithmetic (:func:`compare_sections`) lives here too so
the gate's tolerance math is a pure, testable function: device-time
medians may grow ``DEVTIME_TOLERANCE`` (noise), legacy wall-clock
sections (pre-devtime ``BENCH_*.json``) are compared at
``LEGACY_TOLERANCE`` (the measured wall-clock swing) with a counted
``veles_bench_legacy_sections_total`` warning instead of a crash.
"""

from __future__ import annotations

import gzip
import json
import logging
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from .counters import inc

log = logging.getLogger("veles_tpu.telemetry")

#: the measurement plane's counters — registered with HELP strings in
#: counters.DESCRIPTIONS; capture/fallback counts surface on both
#: /metrics surfaces through the shared registry renderer
DEVTIME_COUNTERS = (
    "veles_devtime_captures_total",
    "veles_devtime_fallbacks_total",
    "veles_bench_legacy_sections_total",
)

#: max allowed growth of device_time_per_epoch between two bench
#: documents — the stated noise tolerance of the device-time gate.
#: Device self-time is host-noise-immune but not jitter-free (compiler
#: autotuning, HBM refresh alignment); measured drift on repeated
#: chip sections sits well under 10 %, so 25 % headroom never flaps
#: while a real regression (a lost fusion, an extra pass) is a ≥2×
#: move.
DEVTIME_TOLERANCE = 1.25

#: wall-clock fallback tolerance for LEGACY sections (documents
#: stamped before the device-time format): wall clock was measured
#: swinging up to 7.6× between windows on a shared machine, so anything
#: tighter would flap — this bound only catches collapse, and every
#: legacy comparison is counted so the format migration is visible.
LEGACY_TOLERANCE = 8.0


# -- trace-event stream parsing ---------------------------------------------

def load_trace_events(path: str) -> List[Dict[str, Any]]:
    """Load a Chrome trace-event file (``.json`` or ``.json.gz``;
    either a ``{"traceEvents": [...]}`` document or a bare event
    list). A torn/truncated file — a capture killed mid-write — is
    salvaged event by event with ONE counted warning instead of
    raising, mirroring ``spans.read_jsonl``'s hardening: a partial
    trace must still summarize."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read().decode("utf-8", errors="replace")
    try:
        doc = json.loads(raw)
    except ValueError:
        return _salvage_events(raw, path)
    if isinstance(doc, list):
        return [e for e in doc if isinstance(e, dict)]
    if isinstance(doc, dict):
        evs = doc.get("traceEvents", [])
        return [e for e in evs if isinstance(e, dict)]
    raise ValueError("not a trace-event document: %s" % path)


def _salvage_events(raw: str, path: str) -> List[Dict[str, Any]]:
    """Recover the complete event prefix of a truncated trace: scan
    the ``traceEvents`` array (or a bare list) object by object with
    an incremental decoder; stop at the first undecodable tail."""
    start = raw.find("[", max(0, raw.find('"traceEvents"')))
    if start < 0:
        raise ValueError("no traceEvents array found in %s" % path)
    decoder = json.JSONDecoder()
    out: List[Dict[str, Any]] = []
    i = start + 1
    n = len(raw)
    while i < n:
        while i < n and raw[i] in " \t\r\n,":
            i += 1
        if i >= n or raw[i] == "]":
            break
        try:
            obj, end = decoder.raw_decode(raw, i)
        except ValueError:
            break
        if isinstance(obj, dict):
            out.append(obj)
        i = end
    log.warning(
        "salvaged %d complete trace event(s) from torn trace %s "
        "(mid-write truncated tail skipped)", len(out), path)
    return out


def load_profile_dir(logdir: str) -> List[Dict[str, Any]]:
    """Events of the newest trace under a ``jax.profiler`` log
    directory (``plugins/profile/<run>/*.trace.json[.gz]``)."""
    import glob as _glob
    pats = [os.path.join(logdir, "plugins", "profile", "*",
                         "*.trace.json.gz"),
            os.path.join(logdir, "plugins", "profile", "*",
                         "*.trace.json")]
    paths = [p for pat in pats for p in _glob.glob(pat)]
    if not paths:
        raise ValueError("no *.trace.json[.gz] under %s" % logdir)
    return load_trace_events(max(paths, key=os.path.getmtime))


def _metadata(events: Iterable[Dict[str, Any]]
              ) -> Tuple[Dict[Any, str], Dict[Tuple[Any, Any], str]]:
    """(process names by pid, thread names by (pid, tid)) from the
    ``ph == "M"`` metadata events (which may trail the data events)."""
    procs: Dict[Any, str] = {}
    threads: Dict[Tuple[Any, Any], str] = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        name = ev.get("name")
        args = ev.get("args") or {}
        if name == "process_name":
            procs[ev.get("pid")] = str(args.get("name", ""))
        elif name == "thread_name":
            threads[(ev.get("pid"), ev.get("tid"))] = \
                str(args.get("name", ""))
    return procs, threads


def _is_device_process(name: str) -> bool:
    """XLA's trace names one process per accelerator
    (``/device:TPU:0``, ``/device:GPU:0 …``); the host shows as
    ``/host:CPU`` plus python/runtime processes. Only the former are
    compute streams."""
    n = name.lower()
    return "/device:" in n and "cpu" not in n


def _interval_union_us(intervals: List[Tuple[float, float]]) -> float:
    """Total covered microseconds of possibly nested/overlapping
    ``(start, end)`` intervals — THE self-time primitive: an op event
    nested inside a fusion event (or two overlapping sub-streams of
    one stream) must count its covered time once, not twice."""
    total = 0.0
    end_prev = None
    start_prev = None
    for start, end in sorted(intervals):
        if end_prev is None or start > end_prev:
            if end_prev is not None:
                total += end_prev - start_prev
            start_prev, end_prev = start, end
        elif end > end_prev:
            end_prev = end
    if end_prev is not None:
        total += end_prev - start_prev
    return total


def device_events(events: Iterable[Dict[str, Any]]
                  ) -> List[Dict[str, Any]]:
    """The complete (``ph == "X"``) events that ran on device-stream
    threads. Within a device process, when any thread is named
    "XLA Ops" only those threads count — the other lanes ("XLA
    Modules", "Steps") are ENVELOPES around the same ops and would
    double the self-time."""
    events = list(events)
    procs, threads = _metadata(events)
    dev_pids = {pid for pid, name in procs.items()
                if _is_device_process(name)}
    ops_tids = {key for key, name in threads.items()
                if key[0] in dev_pids and "xla ops" in name.lower()}
    ops_pids = {pid for pid, _tid in ops_tids}
    out = []
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in dev_pids:
            continue
        if ev.get("pid") in ops_pids \
                and (ev.get("pid"), ev.get("tid")) not in ops_tids:
            continue
        out.append(ev)
    return out


def device_self_time(events: Iterable[Dict[str, Any]]
                     ) -> Dict[str, Any]:
    """Per-stream and total device self-time of a trace-event stream:
    ``{"device_time_s", "by_stream": {label: seconds}, "n_events"}``.
    Streams are (device process, thread) pairs; each stream's
    self-time is the interval union of its events, so nesting inside
    one stream never double counts (concurrent streams DO sum — two
    busy cores are two cores' worth of self-time)."""
    events = list(events)
    procs, threads = _metadata(events)
    per: Dict[Tuple[Any, Any], List[Tuple[float, float]]] = {}
    n = 0
    for ev in device_events(events):
        ts = float(ev.get("ts", 0.0))
        dur = float(ev.get("dur", 0.0))
        per.setdefault((ev.get("pid"), ev.get("tid")), []).append(
            (ts, ts + dur))
        n += 1
    by_stream = {}
    total = 0.0
    for (pid, tid), ivals in sorted(per.items(), key=lambda kv: str(kv[0])):
        us = _interval_union_us(ivals)
        label = "%s/%s" % (procs.get(pid, "pid%s" % pid),
                           threads.get((pid, tid), "tid%s" % tid))
        by_stream[label] = by_stream.get(label, 0.0) + us / 1e6
        total += us
    return {"device_time_s": total / 1e6, "by_stream": by_stream,
            "n_events": n}


def attribute_spans(events: Iterable[Dict[str, Any]],
                    span_records: Iterable[Dict[str, Any]],
                    offset_us: Optional[float] = None
                    ) -> Dict[str, Dict[str, float]]:
    """Device self-time per telemetry span NAME: for every span record
    (``{"name", "ts" (epoch s), "dur" (s)}`` — the
    :mod:`~veles_tpu.telemetry.spans` schema), the interval union of
    device-stream events overlapping the span's window, clipped to it.

    The two clocks differ: spans carry host epoch seconds, profiler
    events carry trace-clock microseconds. ``offset_us`` is
    ``device_ts − host_ts·1e6`` for one common instant; when None it
    is estimated by aligning the earliest device event to the
    earliest span start — exact enough when the capture brackets the
    spans (how :func:`measure` uses it), stated here because it IS an
    approximation. Same-name spans aggregate; a parent span's window
    includes its children's (self-time here is *device* self-time per
    span window, not host-tree-exclusive time)."""
    span_records = [r for r in span_records
                    if "name" in r and "ts" in r]
    devs = [(float(e.get("ts", 0.0)),
             float(e.get("ts", 0.0)) + float(e.get("dur", 0.0)))
            for e in device_events(events)]
    if offset_us is None:
        if not devs or not span_records:
            return {}
        offset_us = (min(s for s, _ in devs)
                     - min(float(r["ts"]) for r in span_records) * 1e6)
    out: Dict[str, Dict[str, float]] = {}
    for rec in span_records:
        s0 = float(rec["ts"]) * 1e6 + offset_us
        s1 = s0 + float(rec.get("dur", 0.0)) * 1e6
        clipped = [(max(a, s0), min(b, s1)) for a, b in devs
                   if b > s0 and a < s1]
        row = out.setdefault(rec["name"],
                             {"device_time_s": 0.0, "spans": 0,
                              "events": 0})
        row["device_time_s"] += _interval_union_us(clipped) / 1e6
        row["spans"] += 1
        row["events"] += len(clipped)
    return out


# -- capture ------------------------------------------------------------------

#: process-wide profiler state: "auto" probes once and remembers — a
#: backend whose captures carry no device streams (CPU CI) or whose
#: profiler errors must not pay capture overhead on every window.
_prof_state = {"disabled": False, "reason": None}


def _profiler_mode() -> str:
    """``root.common.telemetry.devtime.profiler``: "auto" (default —
    try once, remember failure), "on" (always try), "off"."""
    try:
        from ..config import root
        mode = root.common.telemetry.devtime.get("profiler", "auto")
        return str(mode) if mode else "auto"
    except Exception:            # noqa: BLE001 — config not importable
        return "auto"


def _disable_profiler(reason: str) -> None:
    if not _prof_state["disabled"]:
        _prof_state.update(disabled=True, reason=reason)
        log.info("devtime: profiler capture disabled for this process "
                 "(%s) — falling back to host-sync timing", reason)


def profiler_usable() -> bool:
    mode = _profiler_mode()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return not _prof_state["disabled"]


def measure(fn: Callable[[], Any], sync: Callable[[], Any],
            calls: int = 1,
            span_records: Optional[List[Dict[str, Any]]] = None
            ) -> Dict[str, Any]:
    """ONE device-time measurement: run ``fn`` ``calls`` times between
    scalar-fetch syncs. Returns::

        {"device_time_s", "wall_time_s", "calls",
         "device_time_per_call", "source": "profiler" | "host_sync"
         [, "by_stream"] [, "spans"]}

    Profiler path (when usable): the run is captured with
    ``jax.profiler``, the trace-event stream parsed for device-stream
    self-time (``veles_devtime_captures_total``) and attributed onto
    the telemetry spans that closed inside the window
    (``span_records``; default: the global span recorder's records
    from the capture window) under ``out["spans"]``. A capture with no
    device streams disables the profiler for the process and falls
    back. Fallback: the synced wall time IS the device-time estimate
    (upper bound by one host round trip per call —
    ``fn`` is expected to be a ``lax``-loop harness dispatching one
    fused program per call), counted
    ``veles_devtime_fallbacks_total``."""
    sync()
    t0_epoch = time.time()
    started = False
    tmpdir = None
    if profiler_usable():
        import jax
        tmpdir = tempfile.mkdtemp(prefix="veles_devtime_")
        try:
            jax.profiler.start_trace(tmpdir)
            started = True
        except Exception as e:           # noqa: BLE001 — any profiler
            _disable_profiler("start_trace failed: %s" % e)
            shutil.rmtree(tmpdir, ignore_errors=True)
            tmpdir = None
    t0 = time.time()
    try:
        for _ in range(max(1, int(calls))):
            fn()
        sync()
    finally:
        wall = time.time() - t0
        parsed = None
        if started:
            import jax
            try:
                jax.profiler.stop_trace()
                events = load_profile_dir(tmpdir)
                parsed = device_self_time(events)
            except Exception as e:       # noqa: BLE001
                _disable_profiler("capture parse failed: %s" % e)
                events = None
            if tmpdir:
                shutil.rmtree(tmpdir, ignore_errors=True)
    calls = max(1, int(calls))
    if parsed is not None and parsed["device_time_s"] > 0:
        inc("veles_devtime_captures_total")
        out = {"device_time_s": parsed["device_time_s"],
               "wall_time_s": wall, "calls": calls,
               "device_time_per_call": parsed["device_time_s"] / calls,
               "source": "profiler",
               "by_stream": parsed["by_stream"]}
        if span_records is None:
            # attribute onto the telemetry spans that closed inside
            # THIS window — the existing span names are the section
            # vocabulary the gate and `trace self-time` share
            from .spans import recorder as _span_recorder
            span_records = [r for r in _span_recorder.records()
                            if r.get("ts", 0) >= t0_epoch]
        if span_records:
            out["spans"] = attribute_spans(events, span_records)
        return out
    if started:
        _disable_profiler("capture carried no device-stream events "
                          "(host-only backend)")
    inc("veles_devtime_fallbacks_total")
    return {"device_time_s": wall, "wall_time_s": wall, "calls": calls,
            "device_time_per_call": wall / calls,
            "source": "host_sync"}


# -- gate arithmetic ----------------------------------------------------------

def section_invariants(name: str, sec: Dict[str, Any]) -> List[str]:
    """Harness invariants every devtime section record must satisfy —
    what the gate proves on CPU CI, where timing ratios are
    meaningless: fields present, positive device time, wall ≥ device
    (minus float slack), a known source."""
    failures = []
    for key in ("device_time_s", "wall_time_s", "source",
                "device_time_per_epoch"):
        if key not in sec:
            failures.append("%s: devtime record lacks %s" % (name, key))
    if failures:
        return failures
    if not sec["device_time_s"] > 0:
        failures.append("%s: device_time_s = %r (must be > 0)"
                        % (name, sec["device_time_s"]))
    if sec["wall_time_s"] < sec["device_time_s"] * 0.999:
        failures.append(
            "%s: wall_time_s %.6f < device_time_s %.6f — device "
            "self-time cannot exceed the synced wall window"
            % (name, sec["wall_time_s"], sec["device_time_s"]))
    if sec["source"] not in ("profiler", "host_sync"):
        failures.append("%s: unknown devtime source %r"
                        % (name, sec["source"]))
    return failures


def compare_sections(name: str, base: Optional[Dict[str, Any]],
                     cur: Optional[Dict[str, Any]],
                     base_rate: Optional[float] = None,
                     cur_rate: Optional[float] = None,
                     timing: bool = True,
                     tolerance: float = DEVTIME_TOLERANCE) -> List[str]:
    """The device-time gate for one section pair; returns failure
    strings (empty = pass).

    - both carry devtime records → harness invariants always; the
      ``device_time_per_epoch`` ratio may not exceed ``tolerance``
      when ``timing`` (False on CPU/smoke documents, where the gate
      proves invariants only);
    - the CURRENT doc lost the record while the baseline has it →
      fail (format regression);
    - a LEGACY side (pre-devtime ``BENCH_*.json``) → counted
      ``veles_bench_legacy_sections_total`` warning and a wall-clock
      rate comparison at :data:`LEGACY_TOLERANCE` (throughput may not
      collapse below baseline/tolerance), so old baselines neither
      crash the gate nor silently stop gating."""
    failures: List[str] = []
    if cur is not None:
        failures += section_invariants(name, cur)
    if base is None or cur is None:
        if base is not None and cur is None:
            failures.append(
                "%s: current document lost its devtime record while "
                "the baseline has one — the device-time format must "
                "not regress" % name)
            return failures
        # legacy pairing: count + wall-clock fallback
        inc("veles_bench_legacy_sections_total")
        log.warning(
            "devtime gate: section %s compared on wall-clock only "
            "(legacy document without device_time_s)", name)
        if base_rate and cur_rate is not None \
                and cur_rate < base_rate / tolerance_legacy():
            failures.append(
                "%s: legacy wall-clock rate collapsed %.1f -> %.1f "
                "(> %.1fx, beyond any measured wall-clock swing)"
                % (name, base_rate, cur_rate, tolerance_legacy()))
        return failures
    if failures or not timing:
        return failures
    b = base.get("device_time_per_epoch")
    c = cur.get("device_time_per_epoch")
    if not b or c is None:
        return failures
    ratio = float(c) / float(b)
    if ratio > tolerance + 1e-9:
        failures.append(
            "%s: device_time_per_epoch regressed %.6fs -> %.6fs "
            "(%.3fx > %.2fx tolerance)" % (name, b, c, ratio, tolerance))
    return failures


def tolerance_legacy() -> float:
    return LEGACY_TOLERANCE
