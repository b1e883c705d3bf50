"""Continuous-batching serving plane.

The window-coalescing worker in ``restful_api.GenerationAPI`` only
batches requests that arrive within 20 ms of each other AND share an
exact shape key — stochastic decodes never batch, every distinct
prompt length compiles a fresh program, and a batch runs to its
longest member's ``n_new`` before anyone is answered. This package
replaces that with iteration-level scheduling over a persistent slot
pool (the shape-stable cached-decode formulation of PAPERS.md's
"Compiler-First State Space Duality and Portable O(1) Autoregressive
Caching for Inference"):

- :mod:`engine` — :class:`~veles_tpu.serving.engine.ContinuousEngine`:
  ONE fixed-shape jitted decode step over a ``max_slots``-row KV-cache
  pool (``nn/sampling.py``'s ``_block_prefill``/``_block_step`` cache
  layout, padded to ``max_context``), prefill padded to a small set of
  length buckets so the jit cache is bounded by ``len(buckets)`` and
  the step's 1 or 2 view lengths (``programs_bound()``) — not by
  distinct prompt lengths;
- :mod:`scheduler` — :class:`~veles_tpu.serving.scheduler.SlotScheduler`:
  admits queued requests into free slots at step boundaries, retires a
  row the moment it emits ``eos_id`` or reaches its own ``n_new``, and
  answers tickets older than their deadline with 503 + Retry-After
  instead of letting them rot in the queue.

Per-slot PRNG streams derive each row's noise purely from
``(seed, request)`` — a request's tokens are independent of which
strangers share the batch, so ``mode=sample`` batches too (the same
id-exactness bar the greedy CI gate sets).

Operator guide: docs/services.md "Continuous batching".
"""

from __future__ import annotations

import threading
from typing import Dict

from .scheduler import (SlotScheduler, Ticket,        # noqa: F401
                        new_request_id,
                        request_tracing_enabled)
from .engine import (ContinuousEngine,                # noqa: F401
                     advanced_prng_key, fold_resume)
from .pages import PagePool, PrefixCache, StateCache  # noqa: F401
from .recurrent import (RecurrentEngine,               # noqa: F401
                        generate_recurrent,
                        split_recurrent_stack)
from .journal import RequestJournal                   # noqa: F401
from .router import (CircuitBreaker, FleetRouter,     # noqa: F401
                     ROUTER_COUNTERS, Replica, ReplicaSupervisor)
from .overload import (AIMDController,                # noqa: F401
                       BrownoutLadder, OverloadGovernor,
                       QOS_PRIORITIES, RetryTokenBucket,
                       dynamic_retry_after, governor_from_config,
                       request_priority, retry_after_hint)

#: every counter the lossless request plane increments (durable
#: journal + token-level failover resume + drain-by-handoff) —
#: registered with HELP strings in telemetry/counters.py DESCRIPTIONS
#: and asserted zero in non-fleet runs by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
LOSSLESS_COUNTERS = (
    "veles_journal_appends_total",
    "veles_journal_replayed_total",
    "veles_journal_salvaged_total",
    "veles_journal_compactions_total",
    "veles_resume_attempts_total",
    "veles_resume_tokens_total",
    "veles_handoff_requests_total",
)

#: every counter the prefix-sharing request plane increments (radix
#: prefix cache + copy-on-write + LRU eviction over the page pool) —
#: registered with HELP strings in telemetry/counters.py DESCRIPTIONS
#: and asserted zero with the prefix cache off by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
PREFIX_COUNTERS = (
    "veles_prefix_hits_total",
    "veles_prefix_misses_total",
    "veles_prefix_shared_pages_total",
    "veles_prefix_cow_copies_total",
    "veles_prefix_evictions_total",
)

#: every counter the serving plane increments — registered with HELP
#: strings in telemetry/counters.py DESCRIPTIONS and asserted zero
#: after a training-only run by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
SERVING_COUNTERS = (
    "veles_serving_admitted_total",
    "veles_serving_retired_total",
    "veles_serving_prefill_dispatches_total",
    "veles_serving_prefill_positions_total",
    "veles_serving_unfed_late_reads_total",
    "veles_serving_decode_dispatches_total",
    "veles_serving_view_positions_total",
    "veles_serving_steps_ahead_total",
    "veles_serving_tokens_total",
    "veles_serving_expired_total",
    "veles_serving_compile_seconds_total",
    "veles_serving_pages_alloc_total",
    "veles_serving_pages_free_total",
    "veles_serving_pages_exhausted_total",
    "veles_serving_spec_rounds_total",
    "veles_serving_beam_steps_total",
)

#: the counters of token streaming (serving/engine.py ``_push_tokens``:
#: how often a step's tokens reach the streams under the next
#: dispatch) — registered with HELP strings in telemetry/counters.py
#: DESCRIPTIONS and asserted zero with no streaming request by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
STREAM_COUNTERS = (
    "veles_serving_token_pushes_total",
    "veles_serving_token_pushes_overlapped_total",
)

#: every counter the O(1)-state serving lane increments (recurrent
#: slot pool + state-checkpoint prefix cache, serving/recurrent.py) —
#: registered with HELP strings in telemetry/counters.py DESCRIPTIONS
#: and asserted zero in non-recurrent runs by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
O1_COUNTERS = (
    "veles_o1_state_checkpoints_total",
    "veles_o1_state_restores_total",
    "veles_o1_state_restored_tokens_total",
    "veles_o1_state_rescans_total",
    "veles_o1_state_evictions_total",
)

#: every counter the tensor-parallel serving plane increments
#: (shard_mapped decode/prefill/pagecopy over the ("model",) mesh
#: slice, engine.py ``tp=`` knob) — registered with HELP strings in
#: telemetry/counters.py DESCRIPTIONS and asserted zero in tp=1 runs
#: by tests/test_telemetry.py test_feature_off_counters_stay_zero
TP_COUNTERS = (
    "veles_tp_engines_total",
    "veles_tp_dispatches_total",
)

#: every counter the overload-hardened request plane increments (QoS
#: preempt-and-resume + AIMD admission + brownout ladder + retry
#: storm control, serving/overload.py) — registered with HELP strings
#: in telemetry/counters.py DESCRIPTIONS and asserted zero in QoS-off
#: runs by tests/test_telemetry.py test_feature_off_counters_stay_zero
QOS_COUNTERS = (
    "veles_qos_preemptions_total",
    "veles_qos_preempted_tokens_total",
    "veles_qos_batch_deferrals_total",
    "veles_qos_throttled_total",
    "veles_qos_brownout_transitions_total",
    "veles_qos_degraded_requests_total",
    "veles_qos_retry_denied_total",
)

#: every latency histogram the request-plane SLO layer records
#: (serving/scheduler.py Ticket: each wait where it ends) and the
#: engine's account of the chip unfed — registered
#: with HELP + bucket bounds in telemetry/counters.py HISTOGRAMS and
#: asserted ZERO samples after a training-only run by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
SERVING_HISTOGRAMS = (
    "veles_serving_queue_wait_seconds",
    "veles_serving_prefill_wait_seconds",
    "veles_serving_ttft_seconds",
    "veles_serving_first_write_seconds",
    "veles_serving_tpot_seconds",
    "veles_serving_e2e_seconds",
    "veles_serving_unfed_first_token_seconds",
    "veles_serving_unfed_drain_seconds",
)

#: process-global registry of live engines (web_status /metrics renders
#: one occupancy gauge set per engine, like the side-plane lanes)
_engines: Dict[str, "ContinuousEngine"] = {}
_engines_lock = threading.Lock()


def register_engine(engine: "ContinuousEngine") -> None:
    with _engines_lock:
        _engines[engine.name] = engine


def unregister_engine(engine: "ContinuousEngine") -> None:
    with _engines_lock:
        if _engines.get(engine.name) is engine:
            del _engines[engine.name]


def engines() -> Dict[str, "ContinuousEngine"]:
    """name → live engine snapshot (web_status gauge rendering)."""
    with _engines_lock:
        return dict(_engines)


def parse_buckets(spec) -> tuple:
    """Prefill bucket lengths from config/CLI: a sequence of ints or a
    comma-separated string ("16,32,64"); sorted, deduplicated."""
    if isinstance(spec, str):
        spec = [s for s in (part.strip() for part in spec.split(","))
                if s]
    buckets = sorted({int(b) for b in spec})
    if not buckets or buckets[0] < 1:
        from ..error import VelesError
        raise VelesError("serving buckets must be positive ints, got %r"
                         % (spec,))
    return tuple(buckets)
