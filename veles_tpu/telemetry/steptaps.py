"""Counters that a unit computes inside the jitted train step.

A forward unit that counts something of its own work (a sparse-expert
layer: the assignments it made, those it holds, its fullest expert's
load) calls ``emit(key, scalar)`` while the step is traced. The train
step collects what was emitted into the metric accumulators it already
carries (``TrainStep._make_zero_accum``; the unit names its keys in
``step_taps()``), so they are summed on the device and reach the host
with the per-epoch metric drain that happens anyway, exactly as
``tensormon``'s taps do: no further dispatch and no further sync.
``publish`` then adds each drained sum to the counter or histogram its
key names. Outside a collecting step ``emit`` does nothing.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict

from .counters import counters, histograms

#: key prefix of the taps among the step's accumulator entries; the
#: train step strips them from the drained metrics before the Decision
PREFIX = "tap_"

_active = threading.local()


def counter_key(name: str) -> str:
    return "%sc/%s" % (PREFIX, name)


def histogram_key(name: str, part) -> str:
    """``part``: "sum", or a bucket's index (the last is +Inf)."""
    return "%sh/%s/%s" % (PREFIX, name, part)


@contextlib.contextmanager
def collecting():
    """The taps emitted while the body is traced, as {key: scalar}."""
    prev = getattr(_active, "taps", None)
    _active.taps = got = {}
    try:
        yield got
    finally:
        _active.taps = prev


def emit(key: str, value) -> None:
    got = getattr(_active, "taps", None)
    if got is not None:
        got[key] = got[key] + value if key in got else value


def extract(entries, train_cls: int) -> Dict[str, float]:
    """Pop the taps out of drained per-epoch metric dicts (in place) and
    return their sums."""
    out: Dict[str, float] = {}
    for entry in entries:
        metrics = entry.get(train_cls) or {}
        for k in [k for k in metrics if k.startswith(PREFIX)]:
            out[k] = out.get(k, 0.0) + metrics.pop(k)
    return out


def publish(taps: Dict[str, float]) -> None:
    """Add drained sums to the series their keys name."""
    buckets: Dict[str, Dict[str, float]] = {}
    for key, value in taps.items():
        kind, _, rest = key[len(PREFIX):].partition("/")
        if kind == "c":
            if value:
                counters.inc(rest, value)
        else:
            name, _, part = rest.rpartition("/")
            buckets.setdefault(name, {})[part] = value
    for name, parts in buckets.items():
        total = parts.pop("sum", 0.0)
        histograms.add(name, {int(i): int(round(n))
                              for i, n in parts.items() if n}, total)
