"""Telemetry: deterministic performance accounting for every run.

The reference shipped live observability as a first-class layer (ZeroMQ
graphics server + tornado web status, veles/graphics_server.py:73 +
veles/web_status.py:113); this build has the endpoints but, until this
subsystem, no *deterministic* accounting behind them — every perf gate
keyed off wall-clock medians, measured swinging up to 7.6× between
windows on a shared machine, and MFU claims were hand-derived in docs rather than measured by the
framework. This package closes that gap with four pieces, none of which
depend on wall-clock:

- :mod:`counters` — process-global, thread-safe counter registry
  (dispatches, compiles, cache hits, bytes moved) with a
  Prometheus-style text rendering served at ``/metrics`` by
  ``web_status.py`` and ``restful_api.py``;
- :mod:`spans` — context-manager/decorator span API wired into
  ``Unit.run`` dispatch and the fused train step, recording nesting
  and counter deltas (device dispatches, transfer bytes) per span,
  emitted as JSONL;
- :mod:`cost` — a :class:`~veles_tpu.telemetry.cost.CostModel`
  extracting FLOPs / bytes-accessed / peak-memory from lowered XLA
  computations (``jax.stages.Compiled.cost_analysis()``) with an
  analytic fallback table for the Pallas kernels (which report
  nothing), so measured MFU comes from the framework, not from docs;
- :mod:`chrome_trace` — span-JSONL → Chrome ``trace_event`` export
  (``veles-tpu trace export run.jsonl trace.json``) for Perfetto.

Counter-based perf gates live in :func:`gate_counters`: bench.py
records ``{flops, bytes, dispatches, compiles}`` alongside wall-clock
and the gate fails on counter regressions (extra dispatches per token,
unexpected recompiles) — meaningful CI even when the host is noisy.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from .counters import (counters, describe_counter, inc,          # noqa: F401
                       prometheus_text, snapshot)
# the flight-recorder MODULE must import before the span-recorder
# INSTANCE below: loading a submodule binds the package attribute
# ``recorder`` to the module; the next line deliberately rebinds it to
# the SpanRecorder instance (the long-standing export). Import the
# flight recorder by full path: veles_tpu.telemetry.recorder
from .recorder import FlightRecorder, flight                      # noqa: F401
from .spans import span, spanned, SpanRecorder, recorder          # noqa: F401
from .cost import Cost, CostModel, peak_bf16_flops                # noqa: F401
from .tensormon import (ModelHealthError, TensorMonitor,          # noqa: F401
                        monitor)

#: every counter the model-health plane increments — registered with
#: HELP strings in counters.DESCRIPTIONS and asserted zero in
#: monitoring-off runs by ``python bench.py gate``'s tensormon section
TENSORMON_COUNTERS = (
    "veles_tensormon_samples_total",
    "veles_model_nan_total",
    "veles_model_health_errors_total",
    "veles_blackbox_dumps_total",
)

#: every counter the watchtower plane increments (SeriesStore
#: samples, /metrics/history pulls, alert-rule sweeps/transitions,
#: critical-unready hooks) — registered with HELP strings in
#: counters.DESCRIPTIONS and asserted zero in watch-off runs by
#: ``python bench.py gate``'s watch section
WATCH_COUNTERS = (
    "veles_watch_samples_total",
    "veles_watch_pulls_total",
    "veles_alert_evals_total",
    "veles_alert_transitions_total",
    "veles_alert_critical_unready_total",
)

#: every counter the fleet-tracing plane increments (span-ring pulls,
#: trace-file rotations, cross-process merges) — registered with HELP
#: strings in counters.DESCRIPTIONS and asserted zero in non-fleet
#: runs by ``python bench.py gate``'s tracing section
TRACE_COUNTERS = (
    "veles_trace_rotations_total",
    "veles_trace_span_pulls_total",
    "veles_trace_fleet_merges_total",
)

#: default gate rules: counter key → max allowed current/baseline
#: ratio; 1.0 means "may not grow at all". Only WINDOW-INDEPENDENT
#: quantities are gated: bench windows are time-boxed, so raw deltas
#: (total dispatches, total flops) scale with how many epochs fit the
#: window — exactly the wall-clock noise this gate exists to
#: escape. Per-epoch / per-dispatch rates and steady-state compile
#: counts are invariants of the program, not of the wall clock.
GATE_RULES = {
    "dispatches_per_epoch": 1.0,
    "compiles": 1.0,
    "flops_per_dispatch": 1.05,
    "bytes_per_dispatch": 1.05,
    # baseline-relative: a decode that degenerates from one program
    # per generate (1/n_new per token) to one per token shows as an
    # n_new× ratio here — the absolute <= 1 ceiling alone would pass
    # the batch=1 degenerate case at exactly 1.0
    "dispatches_per_token": 1.0,
}


def gate_counters(current: Dict[str, Any],
                  baseline: Dict[str, Any],
                  rules: Optional[Dict[str, float]] = None,
                  max_dispatches_per_token: Optional[float] = None,
                  ) -> List[str]:
    """Compare a benchmark's counter record against a baseline record;
    return a list of human-readable failure strings (empty = pass).

    Unlike the wall-clock gates, these comparisons are exact: a decode
    that suddenly dispatches twice per token, or a step that recompiles
    where it used to hit the jit cache, fails deterministically no
    matter what host noise does to the timings. The default
    rules gate only normalized quantities (see GATE_RULES) — raw
    window totals scale with wall clock and are recorded for
    information, not gated.

    ``max_dispatches_per_token`` additionally enforces an absolute
    ceiling on ``current["dispatches_per_token"]`` (the round-5
    speculative finding was ultimately this number) independent of any
    baseline.
    """
    failures: List[str] = []
    for key, max_ratio in (rules or GATE_RULES).items():
        cur, base = current.get(key), baseline.get(key)
        if cur is None or base is None:
            continue
        if base == 0:
            if cur > 0:
                failures.append("%s regressed: 0 -> %s" % (key, cur))
            continue
        ratio = float(cur) / float(base)
        if ratio > max_ratio + 1e-9:
            failures.append(
                "%s regressed: %s -> %s (%.3fx > %.2fx allowed)"
                % (key, base, cur, ratio, max_ratio))
    if max_dispatches_per_token is not None:
        dpt = current.get("dispatches_per_token")
        if dpt is not None and float(dpt) > max_dispatches_per_token:
            failures.append(
                "dispatches_per_token %.3f exceeds ceiling %.3f"
                % (float(dpt), max_dispatches_per_token))
    return failures
