"""Resilience subsystem: failures injected, retried, survived, counted.

The reference VELES treated recovery as a first-class feature — slave
death re-served jobs or restarted from a checkpoint, and
``--slave-death-probability`` existed precisely to prove it. This
package is that story rebuilt for the SPMD runtime (docs/resilience.md
is the operator guide):

- :mod:`faults` — deterministic, PRNG-seeded fault-injection plane:
  named points (``snapshot.write``, ``loader.batch``, ``dispatch``,
  ``download``, ``serve.request``, ``distributed.init``, …) armed by a
  ``VELES_FAULTS`` / ``root.common.resilience.faults`` spec;
- :mod:`retry` — :class:`~veles_tpu.resilience.retry.RetryPolicy`
  (exponential backoff + full jitter, attempt cap, deadline,
  retryable predicates) applied to downloads, the multi-host join,
  forge client calls and snapshot DB export;
- :mod:`checkpoint_chain` — crash-safe snapshots: fsync'd commits,
  SHA-256 sidecar manifests, verification at load, newest-valid
  restore past quarantined ``*.corrupt`` files, ``keep_last`` pruning;
- :mod:`health` — heartbeat registry + readiness marks behind the
  ``/healthz`` / ``/readyz`` endpoints, and 503 + ``Retry-After`` load
  shedding for the bounded serving queues.

Everything observable lands in the PR-1 telemetry counters
(:data:`RESILIENCE_COUNTERS`); ``tests/test_telemetry.py``
(``test_feature_off_counters_stay_zero``) asserts they read zero in
clean (no-spec) runs.
"""

from __future__ import annotations

from .faults import (FaultInjected, FaultPlane, fire,     # noqa: F401
                     list_points, parse_spec, plane, register_point)
from .retry import RetryPolicy, TransientError            # noqa: F401
from .checkpoint_chain import (SnapshotCorruptError,      # noqa: F401
                               chain, cursor_of, latest_cursor,
                               load_latest, prune, quarantine,
                               restore_latest, verify)
from .health import (heartbeats, mark_draining,           # noqa: F401
                     mark_ready, mark_unready, shed)
from .elastic import (ELASTIC_COUNTERS,                   # noqa: F401
                      ElasticController, GENERATION_EXIT_CODE,
                      HostLostError, Supervisor, generation_barrier,
                      predict_step_time, psum_bytes_per_step)

#: every counter this subsystem increments — registered with HELP
#: strings in telemetry.counters.DESCRIPTIONS and asserted zero in
#: clean runs by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
#: (the elastic generation counters have their own tuple and case:
#: resilience.elastic.ELASTIC_COUNTERS)
RESILIENCE_COUNTERS = (
    "veles_faults_injected_total",
    "veles_retries_total",
    "veles_shed_requests_total",
    "veles_watchdog_trips_total",
    "veles_snapshots_quarantined_total",
    "veles_manifest_cursor_defaults_total",
)
