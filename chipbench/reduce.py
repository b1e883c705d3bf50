"""From samples, spans and a profiler trace to numbers. No jax at import.

A trace is handled in a plain form, a list of planes ``{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns, op_name], ...]}]}``,
which ``load_xplane`` makes from the profiler's ``.xplane.pb`` and which
the tests keep small recorded copies of under ``chipbench/testdata``. An
operation's ``op_name`` is jax's name stack for it (``jit(step)/blk3/ffn/
dot_general``), the named scopes included; an event without the fourth
entry, or with an empty one, has no scope.
"""

import bisect
import glob
import math
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the stats of an event's metadata that carry its ``op_name``, in the
#: order tried
OP_NAME_STATS = ("tf_op", "op_name")
NO_SCOPE = "(no scope)"


class NoDeviceStreams(Exception):
    """The trace holds no device plane: there is no fallback to host time."""


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, as numpy's default; ``values`` must not be empty."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latency_samples(requests, key):
    """``requests``: [{"due", "first", "last", "tokens", "ok"}] in seconds
    on one clock. ``key`` "ttft": first token minus the instant the request
    was due; "tpot": (last - first) / (tokens - 1). A failed or refused
    request has no sample and counts as missing (see ``tail_ms``)."""
    out = []
    for r in requests:
        if not r["ok"]:
            out.append(None)
        elif key == "ttft":
            out.append(r["first"] - r["due"])
        elif r["tokens"] > 1:
            out.append((r["last"] - r["first"]) / (r["tokens"] - 1))
    return out


def tail_ms(samples, q=95.0):
    """Percentile in milliseconds over all requests; one that failed is
    missing, which is worse than any that came: it counts as infinite."""
    values = [math.inf if s is None else s for s in samples]
    p = percentile(values, q) if values else math.nan
    return p * 1000.0


def counters_rise(before, after):
    """{series: rise} between two readings of the program's ``/metrics``
    series ({name: value}): every counter, and a histogram as its ``_sum``
    and ``_count``. A slice's and a window's ``counters`` are this, in a
    cell that serves and in one that trains."""
    return {k: after[k] - before.get(k, 0.0) for k in after
            if k.endswith(("_total", "_sum", "_count"))}


def interval_union(intervals):
    """Total length covered by [start, end) intervals, overlaps once."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals):
    """[(gap_ns, end_of_gap_ns)] between merged busy intervals."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((s - cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of the protobuf message in ``buf[start:end]``:
    a varint's value, or the (start, end) of a length-delimited field;
    fixed-width fields are skipped."""
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


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, plane, field):
    """The value messages of a ``map<int64, Message>`` field of an XPlane."""
    for number, entry in _fields(buf, *plane):
        if number == field:
            for key, value in _fields(buf, *entry):
                if key == 2:
                    yield value


def read_xplane(path):
    """The device planes of one ``.xplane.pb`` in the plain form.
    ``jax.profiler.ProfileData`` shows an event's own stats, and an
    operation's ``op_name`` is a stat of its event *metadata* (``tf_op`` in
    the planes the TPU's profiler writes, else ``op_name``), so the few
    messages of tsl's ``xplane.proto`` that matter (XSpace 1: planes;
    XPlane 2: name, 3: lines, 4: event metadata, 5: stat metadata; XLine 2:
    name, 3: timestamp_ns, 4: events; XEvent 1: metadata id, 2: offset_ps,
    3: duration_ps; XEventMetadata 1: id, 2: name, 5: stats; XStat 1:
    metadata id, 5: str value) are read here directly."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name = next((_text(buf, v) for n, v in _fields(buf, *plane)
                     if n == 2), "")
        if not name.startswith(DEVICE_PLANE):
            continue
        stat_ids = {}
        for meta in _map_values(buf, plane, 5):
            row = dict(_fields(buf, *meta))
            if 1 in row and 2 in row:
                stat_ids[_text(buf, row[2])] = row[1]
        wanted = [stat_ids[n] for n in OP_NAME_STATS if n in stat_ids]
        events_meta = {}
        for meta in _map_values(buf, plane, 4):
            ident, label, tags = None, "", {}
            for n, v in _fields(buf, *meta):
                if n == 1:
                    ident = v
                elif n == 2:
                    label = _text(buf, v)
                elif n == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in wanted and 5 in stat:
                        tags[stat[1]] = _text(buf, stat[5])
            events_meta[ident] = (label, next(
                (tags[i] for i in wanted if i in tags), ""))
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
            if line_name not in (OPS_LINE, MODULES_LINE):
                continue
            base_ps = head.get(3, 0) * 1000
            events = []
            for v in raw:
                ev = dict(_fields(buf, *v))
                label, tag = events_meta.get(ev.get(1), ("", ""))
                events.append([label, (base_ps + ev.get(2, 0)) // 1000,
                               ev.get(3, 0) // 1000, tag])
            lines.append({"name": line_name, "events": events})
        planes.append({"name": name, "lines": lines})
    return planes


def load_xplane(logdir):
    """The newest ``.xplane.pb`` under ``logdir`` in the plain form, device
    planes only (host threads are not read by any metric yet)."""
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise NoDeviceStreams("the profiler wrote no xplane under %s"
                              % logdir)
    return read_xplane(paths[-1])


#: name-stack segments that are control flow, not scopes
STRUCTURAL = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "core_call", "remat", "pjit", "shard_map"))


def _segments(op_name):
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


def scope_of(op_name, depth=2):
    """The named scopes of an operation's ``op_name``, cut to ``depth``
    levels: ``jit(step)/while/body/closed_call/vmap(blk3)/ffn/dot_general``
    is ``blk3/ffn``. Function names (``jit(..)``), control flow and the
    primitive at the end are no scopes; a transform around a scope
    (``vmap(blk3)``, ``jvp(forward)``) is peeled off, and the backward
    pass, which jax marks ``transpose(jvp(forward))``, reads ``backward``.
    Where XLA merged two operations' names (``a;b``) the first stands.
    The program's ``telemetry/devtime.scope_of`` cuts the same way; a test
    holds the two together on a recorded capture."""
    scopes, backward = [], False
    for seg in _segments(op_name.split(";")[0])[:-1]:
        transforms = []
        while seg.endswith(")") and "(" in seg:
            head, _, seg = seg.partition("(")
            seg = seg[:-1]
            transforms.append(head)
        if not seg or seg in STRUCTURAL \
                or any(t in ("jit", "pjit") for t in transforms):
            continue
        backward = backward or "transpose" in transforms
        scopes.append(seg)
    if backward:
        scopes = ["backward"] + scopes[scopes[:1] == ["forward"]:]
    return "/".join(scopes[:depth]) or NO_SCOPE


def reduce_trace(planes, window_s):
    """Busy time, per-program, per-operation and per-scope device time of a
    traced window, averaged over the device planes. ``kernels`` holds the
    Pallas calls with the array types each reads and writes. ``scopes`` is
    ``{program: {scope: [count, seconds]}}``: every operation under the
    program whose interval holds its start and the scope of its
    ``op_name`` (a Pallas call under its kernel's name), or None where no
    event of the capture carries an ``op_name``; an operation with a scope
    is ``scope: operation`` in ``ops``. Raises ``NoDeviceStreams`` where no
    operation ran on a device."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE)]
    busy, ops, modules, gaps, kernels, scopes = [], {}, {}, [], {}, {}
    named, cut = False, {}      # a step's few hundred names come every step
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS_LINE, [])
        busy.append(interval_union(
            [(e[1], e[1] + e[2]) for e in op_events]) / 1e9)
        mod_events = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        starts = [e[1] for e in mod_events]
        for event in op_events:
            name, start, d = event[:3]
            op_name = event[3] if len(event) > 3 else ""
            named = named or bool(op_name)
            label = op_label(name)
            if KERNEL_TARGET in name:
                k = kernels.setdefault(label, dict(
                    kernel_types(name), count=0, seconds=0.0))
                k["count"] += 1
                k["seconds"] += d / 1e9 / len(devices)
                scope = label.split(" ")[0].rsplit(".", 1)[0]
            else:
                scope = cut.get(op_name)
                if scope is None:
                    scope = cut[op_name] = scope_of(op_name)
                if scope != NO_SCOPE:
                    label = "%s: %s" % (scope, label)
            c = ops.setdefault(label, [0, 0.0])
            c[0] += 1
            c[1] += d / 1e9
            j = bisect.bisect_right(starts, start) - 1
            program = (program_name(mod_events[j][0]) if j >= 0
                       and start < starts[j] + mod_events[j][2]
                       else "(no program)")
            c = scopes.setdefault(program, {}).setdefault(scope, [0, 0.0])
            c[0] += 1
            c[1] += d / 1e9
        for name, _, d in (e[:3] for e in mod_events):
            c = modules.setdefault(program_name(name), [0, 0.0])
            c[0] += 1
            c[1] += d / 1e9
        for gap, end in _gaps([(e[1], e[1] + e[2]) for e in op_events]):
            # a gap that ends where a program starts was spent waiting for
            # that program to be dispatched; any other lies inside one
            j = bisect.bisect_right(starts, end + 1000) - 1
            if j < 0:
                label = "before the first program"
            else:
                name = program_name(mod_events[j][0])
                label = ("before " if end - starts[j] <= 1000
                         else "inside ") + name
            gaps.append((label, gap / 1e9))
    if not devices or not any(b > 0 for b in busy):
        raise NoDeviceStreams(
            "no operation ran on a device in the traced window (planes: %s)"
            % [p["name"] for p in planes])
    n = len(devices)
    by_host = {}
    for name, g in gaps:
        by_host[name] = by_host.get(name, 0.0) + g
    return {
        "window_s": window_s, "busy_s": sum(busy) / n, "devices": n,
        "ops": {k: [c, s / n] for k, (c, s) in ops.items()},
        "modules": {k: [c, s / n] for k, (c, s) in modules.items()},
        "kernels": kernels,
        "scopes": {p: {k: [c, s / n] for k, (c, s) in rows.items()}
                   for p, rows in scopes.items()} if named else None,
        "idle_gaps": sorted(([k, v / n] for k, v in by_host.items()),
                            key=lambda kv: -kv[1])[:10],
    }


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(event_name):
    """An operation's event name is its whole HLO line; keep the result's
    name, the opcode and a custom call's target: ``jvp__.10 custom-call
    tpu_custom_call``. Any other name is kept, cut to 120 characters."""
    lhs, sep, rhs = event_name.partition(" = ")
    if not sep:
        return event_name[:120]
    opcode = _OPCODE.search(" " + rhs)
    target = _TARGET.search(rhs)
    return " ".join(x for x in (
        lhs.lstrip("%"), opcode.group(1) if opcode else "",
        target.group(1) if target else "") if x)


KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_TYPE = re.compile(r"\b([a-z][a-z0-9]*\[[0-9,]*\])")


def kernel_types(event_name):
    """The array types a Pallas call reads and writes, from its HLO line:
    ``%o = (f32[32,4096,128]{..}, ..) custom-call(f32[32,4096,128]{..} %q,
    ..), custom_call_target=..`` gives {"results": ["f32[32,4096,128]",
    ..], "operands": [..]}."""
    lhs, _, rhs = event_name.partition(" custom-call(")
    return {"results": _TYPE.findall(lhs.partition(" = ")[2]),
            "operands": _TYPE.findall(rhs.partition("), custom_call_target")[0])}


def program_name(event_name):
    """``jit_train(1234)`` and ``jit_train`` are one program."""
    return event_name.split("(")[0].strip()


def top_ops(trace, n=10):
    return sorted(([k, v[1]] for k, v in trace["ops"].items()),
                  key=lambda kv: -kv[1])[:n]


def scope_lines(trace, top=8, least=0.01):
    """For a reader: each program that takes ``least`` of the device's busy
    time or more, its milliseconds a call by scope, blocks summed
    (``blk3/ffn`` and ``blk4/ffn`` are ``blkN/ffn``), the ``top`` largest
    named and all scopes summed beside the program's own time a call."""
    out = []
    for program, rows in sorted((trace.get("scopes") or {}).items()):
        calls, secs = trace["modules"].get(program, (0, 0.0))
        if not calls or secs < least * trace["busy_s"]:
            continue
        merged = {}
        for scope, (_, s) in rows.items():
            key = re.sub(r"\d+", "N", scope)
            merged[key] = merged.get(key, 0.0) + s
        order = sorted(merged.items(), key=lambda kv: -kv[1])
        out.append("%s: %d calls of %.3f ms; by scope, ms a call: %s; all "
                   "scopes %.3f" % (
                       program, calls, 1000.0 * secs / calls,
                       ", ".join("%s %.3f" % (k, 1000.0 * v / calls)
                                 for k, v in order[:top]),
                       1000.0 * sum(merged.values()) / calls))
    return out


def matching(table, needles):
    """(count, seconds) summed over the entries of ``table`` whose name
    holds any of ``needles``."""
    count, secs = 0, 0.0
    for name, (c, s) in table.items():
        if any(n in name for n in needles):
            count += c
            secs += s
    return count, secs
