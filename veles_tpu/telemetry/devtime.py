"""Device self-time: what a profiler capture says the chip did.

Device *self-time* — the seconds the compute stream actually spent
executing programs — is immune to host scheduling, queue depth and
noisy neighbours. A profiler capture
(``jax.profiler.start_trace``/``stop_trace``) writes a Chrome
trace-event stream (``plugins/profile/<run>/<host>.trace.json.gz``)
whose *processes* include one per device (``/device:TPU:0`` …) with
per-stream threads ("XLA Ops"). :func:`device_self_time`
interval-unions those device-stream events — nested/overlapping events
never double count — and :func:`attribute_spans` maps the device
intervals onto the telemetry span records
(:mod:`~veles_tpu.telemetry.spans`) by time overlap; the capture's
``.xplane.pb`` is read by :func:`load_capture` and cut into the three
tables of :func:`summarize_capture`. Both are the operator's view,
``veles-tpu trace self-time``. A backend whose capture yields no device
streams (the CPU traces only ``/host:CPU``) has no device time: the
tables are empty, and no host clock stands in for it.
"""

from __future__ import annotations

import gzip
import json
import logging
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

log = logging.getLogger("veles_tpu.telemetry")


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


#: the names the program's own spans carry (telemetry/spans.py enters a
#: ``jax.profiler.TraceAnnotation`` of the span's name, so a capture's
#: host plane holds them on the device operations' own timeline)
SPAN_PREFIXES = ("serving.", "train_step.", "unit.", "workflow.")


def annotation_spans(events: Iterable[Dict[str, Any]]
                     ) -> List[Dict[str, Any]]:
    """The program's spans as a Chrome trace holds them: complete
    events off the device processes whose name carries one of
    :data:`SPAN_PREFIXES`, as span records on the trace's own clock
    (``ts`` and ``dur`` in seconds)."""
    events = list(events)
    procs, _ = _metadata(events)
    dev_pids = {pid for pid, name in procs.items()
                if _is_device_process(name)}
    return [{"name": ev["name"], "ts": float(ev.get("ts", 0.0)) / 1e6,
             "dur": float(ev.get("dur", 0.0)) / 1e6}
            for ev in events
            if ev.get("ph") == "X" and ev.get("pid") not in dev_pids
            and str(ev.get("name", "")).startswith(SPAN_PREFIXES)]


def attribute_spans(events: Iterable[Dict[str, Any]],
                    span_records: Optional[Iterable[Dict[str, Any]]]
                    = None, offset_us: float = 0.0
                    ) -> Dict[str, Dict[str, float]]:
    """Device self-time per telemetry span NAME: for every span record
    (``{"name", "ts" (s), "dur" (s)}``), the interval union of
    device-stream events overlapping the span's window, clipped to it.

    ``span_records`` None: the spans are the capture's own
    (:func:`annotation_spans`), put on the profiler's clock by the
    profiler itself, so nothing is estimated. Records from another
    clock (the span ring's epoch seconds) need the explicit
    ``offset_us`` = ``device_ts − host_ts·1e6`` of one common instant.
    Same-name spans aggregate; a parent span's window includes its
    children's (self-time here is *device* self-time per span window,
    not host-tree-exclusive time)."""
    events = list(events)
    if span_records is None:
        span_records = annotation_spans(events)
    span_records = [r for r in span_records
                    if "name" in r and "ts" in r]
    devs = [(float(e.get("ts", 0.0)),
             float(e.get("ts", 0.0)) + float(e.get("dur", 0.0)))
            for e in device_events(events)]
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


# -- reading a profiler capture (.xplane.pb) ---------------------------------
#
# A capture is handled in a plain form, a list of planes ``{"name",
# "lines": [{"name", "events": [(name, start_ns, duration_ns, tag)]}]}``:
# for a device plane the "XLA Ops" and "XLA Modules" lines, ``tag`` an
# operation's HLO metadata ``op_name`` (the jax name stack, scopes
# included); for the host plane one line a thread holding only the
# program's own spans (:data:`SPAN_PREFIXES`), ``tag`` unused.

DEVICE_PLANE = "/device:"
HOST_PLANE = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the event stats that carry an operation's ``op_name`` metadata, in
#: the order tried (``tf_op`` in the planes the TPU profiler writes)
OP_NAME_STATS = ("tf_op", "op_name", "long_name")
NO_SCOPE = "(no scope)"
NO_SPAN = "(no span)"
#: name-stack segments that are control flow, not scopes
_STRUCTURAL = frozenset(("while", "body", "cond", "closed_call",
                         "checkpoint", "rematted_computation",
                         "custom_jvp_call", "custom_vjp_call",
                         "custom_vjp_call_jaxpr", "core_call",
                         "remat", "pjit", "shard_map"))
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def find_capture(logdir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory
    (or one of its run directories), None when there is none."""
    import glob as _glob
    paths = [p for pat in (
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"),
        os.path.join(logdir, "*.xplane.pb"))
        for p in _glob.glob(pat)]
    return max(paths, key=os.path.getmtime) if paths else None


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of one protobuf message in ``buf[start:
    end]``: a varint's value, or the (start, end) of a length-delimited
    field; fixed-width fields are skipped."""
    i = start
    while i < end:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError("not an xplane: wire type %d" % kind)
        yield tag >> 3, value


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, plane: Tuple[int, int], field: int):
    """The value messages of a ``map<int64, Message>`` field of an
    XPlane: [(start, end)]."""
    for number, entry in _fields(buf, *plane):
        if number == field:
            for key, value in _fields(buf, *entry):
                if key == 2:
                    yield value


def load_capture(path: str) -> List[Dict[str, Any]]:
    """An ``.xplane.pb`` in the plain form. ``jax.profiler.ProfileData``
    shows an event's own stats only, and an operation's ``op_name`` is a
    stat (``tf_op``) of its event *metadata*, so the file's few message
    kinds (XSpace, XPlane, XLine, XEvent, XEventMetadata, XStat,
    XStatMetadata of tsl's ``xplane.proto``) are read here directly."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name = next((_text(buf, v) for n, v in _fields(buf, *plane)
                     if n == 2), "")
        device = name.startswith(DEVICE_PLANE)
        if not device and not name.startswith(HOST_PLANE):
            continue
        stat_names = {}
        for meta in _map_entries(buf, plane, 5):
            row = dict(_fields(buf, *meta))
            if 1 in row and 2 in row:
                stat_names[row[1]] = _text(buf, row[2])
        wanted = {i for i, n in stat_names.items() if n in OP_NAME_STATS}
        # event metadata: id -> (name, op_name); on the host only the
        # program's own spans are kept
        events_meta = {}
        for meta in _map_entries(buf, plane, 4):
            ident, label, tag = None, "", ""
            for n, v in _fields(buf, *meta):
                if n == 1:
                    ident = v
                elif n == 2:
                    label = _text(buf, v)
                elif n == 5 and device:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in wanted and 5 in stat:
                        tag = _text(buf, stat[5])
            if device or label.startswith(SPAN_PREFIXES):
                events_meta[ident] = (label, tag)
        lines = []
        for number, line in _fields(buf, *plane):
            if number != 3:
                continue
            head, raw = {}, []
            for n, v in _fields(buf, *line):
                if n == 4:
                    raw.append(v)
                else:
                    head[n] = v
            line_name = _text(buf, head[2]) if 2 in head else ""
            if device and line_name not in (OPS_LINE, MODULES_LINE):
                continue
            base_ps = head.get(3, 0) * 1000
            events = []
            for v in raw:
                ev = dict(_fields(buf, *v))
                meta = events_meta.get(ev.get(1))
                if meta is not None:
                    events.append((meta[0],
                                   (base_ps + ev.get(2, 0)) // 1000,
                                   ev.get(3, 0) // 1000, meta[1]))
            if events:
                lines.append({"name": line_name, "events": events})
        if lines:
            planes.append({"name": name, "lines": lines})
    return planes


def _segments(op_name: str) -> List[str]:
    """An ``op_name`` split at the slashes outside parentheses."""
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def scope_of(op_name: str, depth: int = 2) -> str:
    """The named scopes of an operation's ``op_name`` metadata, cut to
    ``depth`` levels: ``jit(step)/while/body/closed_call/vmap(blk3)/
    ffn/dot_general`` is ``blk3/ffn``. Function names (``jit(..)``),
    control flow and the primitive at the end are no scopes; a
    transform around a scope (``vmap(blk3)``, ``jvp(forward)``) is
    peeled off, and the backward pass, which jax marks
    ``transpose(jvp(forward))``, reads ``backward``. Where XLA merged
    two operations' names (``a;b``) the first stands."""
    scopes, backward = [], False
    for seg in _segments(op_name.split(";")[0])[:-1]:
        transforms = []
        while seg.endswith(")") and "(" in seg:
            head, _, seg = seg.partition("(")
            seg = seg[:-1]
            transforms.append(head)
        if not seg or seg in _STRUCTURAL \
                or any(t in ("jit", "pjit") for t in transforms):
            continue
        backward = backward or "transpose" in transforms
        scopes.append(seg)
    if backward:
        scopes = ["backward"] + scopes[scopes[:1] == ["forward"]:]
    return "/".join(scopes[:depth]) or NO_SCOPE


def _program(module_event: str) -> str:
    """``jit_step(9886478132021696463)`` is ``jit_step``."""
    return module_event.split("(")[0].strip()


def _op_label(event_name: str) -> str:
    """An operation's event name is its whole HLO line on the TPU:
    keep the result's name (``%fusion.17 = ...`` is ``fusion.17``)."""
    return event_name.partition(" = ")[0].lstrip("%")[:80]


def _innermost(spans):
    """[(time_ns, name or None)] in time order: from each instant on,
    the innermost of one thread's (properly nested) spans."""
    marks = []
    for name, start, dur, _ in spans:
        marks.append((start, 1, name))
        marks.append((start + dur, 0, name))
    # at one instant ends go before starts; a parent starts before its
    # child and ends after it
    marks.sort(key=lambda m: (m[0], m[1]))
    out, stack = [], []
    for t, is_start, name in marks:
        if is_start:
            stack.append(name)
        elif stack:
            stack.pop()
        out.append((t, stack[-1] if stack else None))
    return out


def summarize_capture(planes: List[Dict[str, Any]], depth: int = 2
                      ) -> Dict[str, Any]:
    """Three tables of a capture in the plain form: device time by
    program (``programs``: name -> [calls, seconds]); by scope within
    each program (``scopes``: (program, scope) -> seconds; a Pallas
    call counts under its kernel's name; ``unnamed``: operation ->
    seconds of what no scope names); and the device's idle gaps by the
    host span that covers each gap's middle (``gaps``: span name ->
    [gaps, seconds]), the spans being those of the thread that recorded
    the most, which is the dispatching one (a server's handler threads
    each write one event a step). Seconds are summed over the device
    planes; ``busy_s`` and ``window_s`` likewise."""
    import bisect
    host_lines = [ln["events"] for p in planes
                  if p["name"].startswith(HOST_PLANE)
                  for ln in p["lines"]]
    marks = _innermost(max(host_lines, key=len)) if host_lines else []
    mark_times = [t for t, _ in marks]
    programs: Dict[str, List[float]] = {}
    scopes: Dict[Tuple[str, str], float] = {}
    unnamed: Dict[str, float] = {}
    gaps: Dict[str, List[float]] = {}
    busy = window = 0.0
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        mods = sorted(lines.get(MODULES_LINE, ()), key=lambda e: e[1])
        mod_starts = [e[1] for e in mods]
        for name, _, dur, _ in mods:
            row = programs.setdefault(_program(name), [0, 0.0])
            row[0] += 1
            row[1] += dur / 1e9
        ops = sorted(lines.get(OPS_LINE, ()), key=lambda e: e[1])
        cur_end = None
        for name, start, dur, op_name in ops:
            j = bisect.bisect_right(mod_starts, start) - 1
            program = (_program(mods[j][0])
                       if j >= 0 and start < mods[j][1] + mods[j][2]
                       else "(no program)")
            if KERNEL_TARGET in name:
                # a Pallas call: its kernel's name is the result's
                scope = _op_label(name).rsplit(".", 1)[0]
            else:
                scope = scope_of(op_name, depth)
            if scope == NO_SCOPE:
                label = _op_label(name)
                unnamed[label] = unnamed.get(label, 0.0) + dur / 1e9
            key = (program, scope)
            scopes[key] = scopes.get(key, 0.0) + dur / 1e9
            if cur_end is not None and start > cur_end:
                mid = (cur_end + start) // 2
                i = bisect.bisect_right(mark_times, mid) - 1
                span = (marks[i][1] if i >= 0 else None) or NO_SPAN
                row = gaps.setdefault(span, [0, 0.0])
                row[0] += 1
                row[1] += (start - cur_end) / 1e9
            if cur_end is None or start + dur > cur_end:
                covered = start if cur_end is None else max(start, cur_end)
                busy += (start + dur - covered) / 1e9
                cur_end = start + dur
        if ops:
            window += (cur_end - ops[0][1]) / 1e9
    return {"programs": programs, "scopes": scopes, "unnamed": unnamed,
            "gaps": gaps, "busy_s": busy, "window_s": window}


def format_capture(summary: Dict[str, Any], top: int = 12) -> List[str]:
    """The three tables of :func:`summarize_capture` as text lines."""
    busy, window = summary["busy_s"], summary["window_s"]
    out = ["device busy %.6f s of %.6f s between its first and last "
           "operation (idle %.2f %%)"
           % (busy, window, 100.0 * (1.0 - busy / window) if window
              else 0.0)]
    programs = summary["programs"]
    out.append("device time by program:")
    for name, (calls, secs) in sorted(programs.items(),
                                      key=lambda kv: -kv[1][1])[:top]:
        out.append("  %-44s %6d call(s) %10.6f s %9.3f ms/call"
                   % (name, calls, secs, 1000.0 * secs / calls))
    out.append("device time by scope (ms a call of its program; "
               "Pallas calls by kernel name):")
    by_program: Dict[str, List[Tuple[str, float]]] = {}
    for (program, scope), secs in summary["scopes"].items():
        by_program.setdefault(program, []).append((scope, secs))
    named = total = 0.0
    for program, rows in sorted(by_program.items(),
                                key=lambda kv: -sum(r[1] for r in kv[1])):
        calls = programs.get(program, [0, 0.0])[0] or 1
        whole = sum(secs for _, secs in rows)
        out.append("  %s (%.6f s in operations):" % (program, whole))
        for scope, secs in sorted(rows, key=lambda r: -r[1])[:top]:
            out.append("    %-42s %10.6f s %9.3f ms/call %5.1f %%"
                       % (scope, secs, 1000.0 * secs / calls,
                          100.0 * secs / whole if whole else 0.0))
        total += whole
        named += sum(secs for scope, secs in rows if scope != NO_SCOPE)
    if total:
        out.append("  %.1f %% of device time is under a named scope or "
                   "kernel; the rest by operation:"
                   % (100.0 * named / total))
        for label, secs in sorted(summary["unnamed"].items(),
                                  key=lambda kv: -kv[1])[:top]:
            out.append("    %-42s %10.6f s" % (label, secs))
    out.append("idle gaps by host span (the innermost span of the "
               "dispatching thread over each gap's middle):")
    idle = sum(secs for _, secs in summary["gaps"].values())
    for span, (count, secs) in sorted(summary["gaps"].items(),
                                      key=lambda kv: -kv[1][1])[:top]:
        out.append("  %-44s %6d gap(s) %10.6f s %5.1f %%"
                   % (span, count, secs,
                      100.0 * secs / idle if idle else 0.0))
    return out
