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
"""

from __future__ import annotations

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
#: monitoring-off runs by tests/test_telemetry.py
#: test_feature_off_counters_stay_zero
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
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
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
#: runs by tests/test_telemetry.py test_feature_off_counters_stay_zero
TRACE_COUNTERS = (
    "veles_trace_rotations_total",
    "veles_trace_span_pulls_total",
    "veles_trace_fleet_merges_total",
)
