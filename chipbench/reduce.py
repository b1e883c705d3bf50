"""From samples, spans and a profiler trace to numbers. No jax at import.

A trace is handled in a plain form, a list of planes ``{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}``, which
``load_xplane`` makes from the profiler's ``.xplane.pb`` and which the
tests keep a small recorded copy of under ``chipbench/testdata``.
"""

import bisect
import glob
import math
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


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


def load_xplane(logdir):
    """The newest ``.xplane.pb`` under ``logdir`` in the plain form, device
    planes only (host threads are not read by any metric yet)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise NoDeviceStreams("the profiler wrote no xplane under %s"
                              % logdir)
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        planes.append({"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]}
            for line in plane.lines
            if line.name in (OPS_LINE, MODULES_LINE)]})
    return planes


def reduce_trace(planes, window_s):
    """Busy time, per-program and per-operation device time of a traced
    window, averaged over the device planes; ``kernels`` holds the Pallas
    calls with the array types each reads and writes. Raises
    ``NoDeviceStreams`` where no operation ran on a device."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE)]
    busy, ops, modules, gaps, kernels = [], {}, {}, [], {}
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS_LINE, [])
        busy.append(interval_union(
            [(s, s + d) for _, s, d in op_events]) / 1e9)
        for name, _, d in op_events:
            c = ops.setdefault(op_label(name), [0, 0.0])
            c[0] += 1
            c[1] += d / 1e9
            if KERNEL_TARGET in name:
                k = kernels.setdefault(op_label(name), dict(
                    kernel_types(name), count=0, seconds=0.0))
                k["count"] += 1
                k["seconds"] += d / 1e9 / len(devices)
        mod_events = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        for name, _, d in mod_events:
            c = modules.setdefault(program_name(name), [0, 0.0])
            c[0] += 1
            c[1] += d / 1e9
        starts = [s for _, s, _ in mod_events]
        for gap, end in _gaps([(s, s + d) for _, s, d in op_events]):
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


def matching(table, needles):
    """(count, seconds) summed over the entries of ``table`` whose name
    holds any of ``needles``."""
    count, secs = 0, 0.0
    for name, (c, s) in table.items():
        if any(n in name for n in needles):
            count += c
            secs += s
    return count, secs
