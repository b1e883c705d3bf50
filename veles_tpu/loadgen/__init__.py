"""Fleet load harness: the millions-of-users testbed (ROADMAP item 5).

``veles-tpu loadgen`` drives a real serving fleet OPEN-LOOP — arrivals
follow the offered-load schedule whatever the fleet's latency does, so
overload is actually offered, not self-throttled away like a
closed-loop client would. The pieces:

- :class:`~veles_tpu.loadgen.workload.Workload` — deterministic
  (seeded) request synthesis: Zipf-distributed prompt lengths,
  shared-prefix mixes, interactive/batch QoS labels, streaming and
  buffered clients, steady/burst/diurnal arrival shapes;
- :class:`~veles_tpu.loadgen.storm.ChaosStorm` — timed fault storms
  expressed as plain ``window=T0:T1`` fault specs over the existing
  injection points (``serve.replica_death``,
  ``router.replica_request``, ``serve.page_alloc``, ...);
- :class:`~veles_tpu.loadgen.harness.LoadGen` — the driver: dispatch
  at the scheduled instants, record per-request outcomes client-side,
  and emit an SLO VERDICT merging the client's view with the serving
  histograms (veles_serving_ttft_seconds et al., PR 11).

Operator guide: docs/services.md "Overload & QoS".
"""

from .workload import Workload                          # noqa: F401
from .storm import (ChaosStorm, StormPlan,              # noqa: F401
                    parse_storm)
from .harness import (LoadGen, aggregate,               # noqa: F401
                      percentile, verdict)

#: every counter the load harness increments — registered in
#: telemetry/counters.py DESCRIPTIONS and asserted zero in
#: non-loadgen runs by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
LOADGEN_COUNTERS = (
    "veles_loadgen_requests_total",
    "veles_loadgen_shed_total",
    "veles_loadgen_errors_total",
    "veles_loadgen_storms_total",
    "veles_loadgen_alert_aborts_total",
)
