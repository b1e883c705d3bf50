"""Overlap engine: keep the device busy while the host does I/O.

The deterministic serial scheduler (veles_tpu/workflow.py) is correct
but leaves snapshot fsyncs, plot rendering, publisher uploads and host
batch staging inline with the jitted step — the accelerator idles
while Python touches disks and sockets. This package overlaps that
host work with device compute **without touching the deterministic
compute path** (docs/overlap.md is the operator guide):

- :mod:`executor` — :class:`~veles_tpu.overlap.executor.SidePlane`, a
  bounded worker pool with named ordered lanes (FIFO within a lane,
  lanes concurrent), explicit ``drain()`` barriers, and errors routed
  into resilience health + telemetry counters. Units that declare
  ``side_effect_only = True`` (plotters, publishers) are
  dispatched here by ``Workflow.run`` instead of running inline;
- :mod:`prefetch` — :class:`~veles_tpu.overlap.prefetch.Prefetcher`,
  an N-deep background staging queue (optionally including
  ``jax.device_put``) with backpressure and clean shutdown; ``Loader``
  wires it via ``prefetch_depth`` so the next minibatch's gather runs
  while the current step computes;
- non-blocking checkpoints: ``Snapshotter(async_mode=True)`` collects
  the state tree on the main thread (the cheap device→host copy) and
  commits+fsyncs+hashes on the ``checkpoint`` lane, preserving the
  chain's crash-safety invariants (per-lane commit order, quarantine
  on verify failure).

The contract, locked by tests/test_overlap.py: train/decode results
are **bit-identical** with overlap on vs. off. Enable with
``--overlap`` (CLI) or ``root.common.overlap.enabled = True``; tune
``queue_depth``, ``async_snapshots`` and ``prefetch_depth`` under
``root.common.overlap``.
"""

from __future__ import annotations

from .executor import (SidePlane, SidePlaneError,       # noqa: F401
                       enabled, plane)
from .prefetch import Prefetcher                        # noqa: F401

#: every counter this subsystem increments — registered with HELP
#: strings in telemetry.counters.DESCRIPTIONS;
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
#: asserts they read zero in overlap-off runs
OVERLAP_COUNTERS = (
    "veles_sideplane_tasks_total",
    "veles_sideplane_errors_total",
    "veles_sideplane_stall_seconds_total",
    "veles_prefetch_batches_total",
    "veles_prefetch_hits_total",
    "veles_prefetch_misses_total",
    "veles_prefetch_stall_seconds_total",
)
