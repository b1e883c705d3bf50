"""Process-global, thread-safe performance counter registry.

The deterministic backbone of the telemetry subsystem: counters count
*events the framework itself causes* — device dispatches, XLA
compiles, jit-cache hits, host↔device bytes, serving retries — so a
perf gate on them is exact regardless of host noise (wall clock was
measured swinging 7.6× between windows on a shared machine). The HTTP
services render :func:`prometheus_text` at
``/metrics`` (web_status.py, restful_api.py).

Naming follows the Prometheus convention: ``veles_<what>_total`` for
monotonic counters, snake_case, unit suffix where applicable
(``_bytes_total``). The registry is flat name → float; callers use the
module-level :func:`inc` / :func:`snapshot` / :func:`delta` helpers on
the singleton :data:`counters`.
"""

from __future__ import annotations

import bisect
import threading
from typing import Dict, List, Optional, Tuple

#: canonical counter names with HELP strings (also the /metrics HELP
#: lines). Ad-hoc names are allowed, but instrumented code sticks to
#: these so dashboards and gates agree on spelling.
DESCRIPTIONS = {
    "veles_dispatches_total":
        "Jitted device program executions (one per jitted call)",
    "veles_compiles_total":
        "XLA compilations observed (jit cache misses at call time)",
    "veles_h2d_bytes_total":
        "Bytes explicitly transferred host to device",
    "veles_d2h_bytes_total":
        "Bytes explicitly fetched device to host",
    "veles_decode_tokens_total":
        "Tokens emitted by the generation stack",
    "veles_decode_dispatches_total":
        "Device dispatches spent producing those tokens",
    "veles_flash_attention_traces_total":
        "Programs (re)built containing the flash-attention kernel",
    "veles_flash_attention_interpret_traces_total":
        "Of those, programs whose kernel runs in Pallas interpret mode "
        "(test harness only; 0 on any chip run)",
    "veles_flash_default_blocks_traces_total":
        "Flash-attention block lookups on a TPU that found no measured "
        "row in kernel_tuning.json and fell back to the 128x128 default "
        "tiles (each about a microsecond of grid overhead a tile; "
        "0 once the shape is swept)",
    "veles_moe_assignments_total":
        "Token-to-expert assignments the sparse-expert layers' routers "
        "made (tokens x experts a token, a layer a step): in training "
        "counted inside the step and drained with the epoch's metrics, "
        "in a served decode step the live rows' alone, read with the "
        "step's tokens",
    "veles_moe_assignments_held_total":
        "Of those, assignments to an expert this process holds: the "
        "rows its grouped expert products multiply",
    "veles_moe_experts_touched_total":
        "Held experts that got at least one row, summed over the "
        "sparse-expert layers of a served decode step (live rows only; "
        "prefill's routing is not counted): the experts whose matrices "
        "the step had to read",
    # resilience subsystem (veles_tpu/resilience/): these exist so
    # chaos runs are countable; tests/test_telemetry.py
    # test_feature_off_counters_stay_zero asserts they read 0 in clean
    # (no fault spec) runs, as it does for every family below that is
    # said to read 0 with its subsystem off
    "veles_faults_injected_total":
        "Faults fired by the deterministic injection plane",
    "veles_retries_total":
        "Operations retried by a RetryPolicy (backoff performed)",
    "veles_shed_requests_total":
        "Serving requests shed with 503 + Retry-After",
    "veles_watchdog_trips_total":
        "step_watchdog threshold trips (possible hangs)",
    "veles_snapshots_quarantined_total":
        "Corrupt snapshots renamed *.corrupt during chain restore",
    # elastic training plane (veles_tpu/resilience/elastic.py):
    # the generation counters read 0 in non-elastic runs
    "veles_elastic_generations_total":
        "Elastic training generations started (first generation "
        "included)",
    "veles_elastic_preemptions_total":
        "Host-loss events that ended a generation (heartbeat lapse, "
        "join failure, or an injected distributed.host_loss fault)",
    "veles_elastic_reshard_seconds_total":
        "Seconds spent restoring + resharding state at elastic "
        "generation handoffs",
    "veles_elastic_barrier_timeouts_total":
        "Elastic survivor barriers that failed or timed out",
    "veles_manifest_cursor_defaults_total":
        "Snapshot manifests read without an {epoch, step, world_size} "
        "cursor (pre-elastic manifests; defaulted, never a crash)",
    # overlap subsystem (veles_tpu/overlap/): the side-plane/prefetch
    # counters read 0 in overlap-off runs
    "veles_sideplane_tasks_total":
        "Tasks executed by side-plane lane workers",
    "veles_sideplane_errors_total":
        "Side-plane tasks that raised (routed to drain + health)",
    "veles_sideplane_stall_seconds_total":
        "Seconds the main thread blocked on side-plane backpressure "
        "or drain barriers",
    "veles_prefetch_batches_total":
        "Batches staged ahead by the data-plane prefetcher",
    "veles_prefetch_hits_total":
        "Prefetcher gets served without waiting (batch was ready)",
    "veles_prefetch_misses_total":
        "Prefetcher gets that had to wait for the producer",
    "veles_prefetch_stall_seconds_total":
        "Seconds consumers waited on the prefetch queue",
    # continuous-batching serving engine (veles_tpu/serving/):
    # these read 0 in non-serving runs
    "veles_serving_admitted_total":
        "Requests admitted into continuous-batching KV-cache slots",
    "veles_serving_retired_total":
        "Slot rows retired (eos_id emitted or own n_new reached)",
    "veles_serving_prefill_dispatches_total":
        "Bucketed prefill programs dispatched by the serving engine",
    "veles_serving_prefill_positions_total":
        "Prompt positions those dispatches of the target model ran "
        "(a prefill's bucket, a chunk's length; the draft's prefill "
        "counts none): over the decode dispatches, the prefill work "
        "that a tick carries beside its step",
    "veles_serving_unfed_late_reads_total":
        "Blocking reads that opened an unfed interval (the two "
        "veles_serving_unfed_* histograms) and returned within 50 us: "
        "the result was ready, so the chip may have stood idle before "
        "the host looked and that interval is a lower bound",
    "veles_serving_decode_dispatches_total":
        "Pooled fixed-shape decode steps dispatched by the serving "
        "engine",
    "veles_serving_view_positions_total":
        "Cache positions a slot that those dispatches gathered (the "
        "view's length); over the dispatches, the mean view",
    "veles_serving_steps_ahead_total":
        "Those of the decode dispatches issued while the step before "
        "was still unread: the device did not wait for the host "
        "between the two",
    "veles_serving_tokens_total":
        "Tokens emitted by the continuous-batching engine",
    "veles_serving_token_pushes_total":
        "Stream events of decode-step tokens the serving engine "
        "queued for the HTTP handler threads",
    "veles_serving_token_pushes_overlapped_total":
        "Those of them queued while a dispatch was in flight, so the "
        "handlers write while the device works",
    "veles_serving_expired_total":
        "Queued generation requests answered 503 past their deadline",
    "veles_serving_pages_alloc_total":
        "KV-cache pages allocated from the paged serving pool "
        "(admission prefills + decode-time growth)",
    "veles_serving_pages_free_total":
        "KV-cache pages returned to the paged serving pool at row "
        "retirement",
    "veles_serving_pages_exhausted_total":
        "Page allocations refused by an exhausted pool (admission "
        "waits; decode-time growth sheds 503 + Retry-After)",
    "veles_serving_spec_rounds_total":
        "On-device draft/verify speculation rounds run over slot-pool "
        "rows",
    "veles_serving_beam_steps_total":
        "Fixed-shape beam top-k steps run over slot-pool hypothesis "
        "groups",
    "veles_serving_compile_seconds_total":
        "Seconds the serving engine spent jit-tracing/compiling its "
        "live decode/prefill programs (0 in AOT-artifact mode)",
    # quantization subsystem (veles_tpu/quant/): the quant/artifact
    # counters read 0 in quant-off, artifact-off runs
    "veles_quant_params_total":
        "Parameter tensors quantized to int8 (per-channel symmetric)",
    "veles_quant_bytes_saved_total":
        "Bytes saved by int8 weight quantization (float minus "
        "int8+scale storage)",
    "veles_quant_calibrations_total":
        "Weight-quantization calibration passes (amax scale scans)",
    "veles_artifact_loads_total":
        "AOT serve-artifacts loaded by the serving engine",
    "veles_artifact_load_failures_total":
        "AOT serve-artifact loads that failed and fell back to "
        "live jit (corrupt/mismatched/injected)",
    # tensor-parallel serving (serving/engine.py tp= knob): shard_map
    # over the ("model",) mesh slice — these read 0 in tp=1 runs
    "veles_tp_engines_total":
        "Serving engines started in tensor-parallel mode (one per "
        "mesh slice, however many chips the slice spans)",
    "veles_tp_dispatches_total":
        "Fixed-shape serving programs dispatched as shard_mapped "
        "mesh programs (decode steps, bucketed prefills, chunks, "
        "page copies)",
    # kernel autotune DB provenance (ops/autotune.py): stale-entry
    # lookups — measured under a different jax than the running one
    "veles_autotune_stale_total":
        "kernel_tuning.json hits whose recorded jax version differs "
        "from (or predates) the running toolchain — reused, but due "
        "a re-sweep",
    # model-health observability (telemetry/tensormon.py +
    # telemetry/recorder.py): the sample/NaN counters read 0 in
    # tensormon-off runs
    "veles_tensormon_samples_total":
        "Tensor-statistics samples drained from the jitted train step",
    "veles_model_nan_total":
        "Non-finite (NaN/Inf) values detected in gradients, loss or "
        "activations by the tensormon taps",
    "veles_model_health_errors_total":
        "ModelHealthError raised by the NaN sentinel (halt policies)",
    "veles_blackbox_dumps_total":
        "Flight-recorder black-box dumps written",
    # request-plane SLO layer (serving/scheduler.py Ticket accounting
    # + the metrics_text renderer below)
    "veles_metrics_name_collisions_total":
        "Caller-supplied /metrics gauges dropped because their name "
        "shadowed an already-rendered counter/histogram series "
        "(duplicate names are invalid Prometheus exposition)",
    # serving fleet router (serving/router.py): these read 0 in
    # non-fleet runs
    "veles_router_requests_total":
        "Requests admitted by the fleet router's HTTP front",
    "veles_router_attempts_total":
        "Replica attempts the router proxied (first tries + "
        "failover retries)",
    "veles_router_failovers_total":
        "Requests retried on another replica after a failed attempt "
        "(crash, timeout, 5xx)",
    "veles_router_replica_errors_total":
        "Failed replica attempts the router observed (connection "
        "errors, timeouts, 5xx answers)",
    "veles_router_breaker_opens_total":
        "Circuit-breaker transitions to open (threshold consecutive "
        "failures, or a failed half-open probe)",
    "veles_router_duplicate_answers_total":
        "Late replica answers dropped by the exactly-once latch (a "
        "slow-then-successful attempt whose request was already "
        "answered by a failover)",
    "veles_router_respawns_total":
        "Dead serving replicas respawned by the ReplicaSupervisor",
    # lossless request plane (serving/journal.py + token-level
    # failover resume + drain-by-handoff): these read 0 in non-fleet
    # runs
    "veles_journal_appends_total":
        "Records durably appended to the router's request journal "
        "(admissions + terminals, fsync'd before dispatch/reply)",
    "veles_journal_replayed_total":
        "Journaled requests re-dispatched by a restarted router "
        "(admitted before a crash, unanswered at restart)",
    "veles_journal_salvaged_total":
        "Torn or corrupt journal records quarantined with a warning "
        "at replay (mid-write truncation, bitrot, injected "
        "router.journal corruption) — never a refused start",
    "veles_journal_compactions_total":
        "Journal rotations that rewrote the live (unanswered) "
        "entries into a fresh fsync'd segment and dropped the rest",
    "veles_resume_attempts_total":
        "Failover attempts dispatched with resume_tokens (the retry "
        "continues from tokens_done instead of re-decoding)",
    "veles_resume_tokens_total":
        "Tokens carried into a resumed decode instead of being "
        "re-decoded (the failover savings, summed over resumes)",
    "veles_handoff_requests_total":
        "In-flight requests a draining replica handed back with "
        "progress (503 + resume) instead of aborting or riding out "
        "the full generation",
    # prefix-sharing paged KV cache (serving/pages.py PrefixCache +
    # engine adoption/COW): these read 0 in prefix-cache-off runs
    "veles_prefix_hits_total":
        "Admissions that adopted at least one shared prefix block "
        "from the radix prefix cache (prefill covers only the "
        "unmatched suffix)",
    "veles_prefix_misses_total":
        "Prefix-eligible admissions (>= 1 full token block) that "
        "matched nothing in the prefix cache and prefilled fully",
    "veles_prefix_shared_pages_total":
        "KV-cache pages adopted READ-ONLY into admitting slots from "
        "the prefix cache (each adoption takes one refcount share)",
    "veles_prefix_cow_copies_total":
        "Copy-on-write page copies: a write had to land inside a "
        "shared page (full-prompt match re-computing its last "
        "position), so its content moved to a private page first",
    "veles_prefix_evictions_total":
        "Prefix-cache blocks dropped by LRU leaf eviction (allocator "
        "pressure or the soft block budget)",
    # O(1)-state serving lane (serving/recurrent.py RecurrentEngine +
    # serving/pages.py StateCache): these read 0 in non-recurrent
    # runs
    "veles_o1_state_checkpoints_total":
        "Recurrent state snapshots cached at page_size-token block "
        "boundaries after a prefill scan (the state lane's prefix-"
        "cache writes)",
    "veles_o1_state_restores_total":
        "Admissions that adopted a cached state checkpoint copy-on-"
        "write and scanned only the unmatched prompt suffix",
    "veles_o1_state_restored_tokens_total":
        "Prompt tokens skipped by adopting state checkpoints instead "
        "of re-scanning them (the restore savings, summed)",
    "veles_o1_state_rescans_total":
        "State restores degraded to a full re-scan from zeros "
        "(injected serve.state_restore checkpoint loss; answers stay "
        "correct, only the scan work is repaid)",
    "veles_o1_state_evictions_total":
        "State-cache checkpoint blocks dropped by LRU leaf eviction "
        "(the soft max_blocks budget)",
    # fleet-wide distributed tracing (telemetry/spans.py ring pulls +
    # telemetry/fleet.py cross-process assembly): these read 0 in
    # non-fleet runs
    "veles_trace_rotations_total":
        "JSONL --trace-file rotations (the sink grew past "
        "root.common.trace.rotate_bytes; the previous segment is "
        "kept as <path>.1, older ones dropped)",
    "veles_trace_span_pulls_total":
        "Span-ring pulls served over GET /trace/spans (router + "
        "serving APIs; the fleet trace assembler's read path)",
    "veles_trace_fleet_merges_total":
        "Cross-process fleet traces assembled (span pulls merged "
        "onto one clock, one Chrome-trace lane per process)",
    # overload-hardened request plane (serving/overload.py QoS +
    # brownout governor, engine preempt-and-resume): these read 0 in
    # QoS-off runs
    "veles_qos_preemptions_total":
        "Batch decode rows preempted at a step boundary to free "
        "slots for waiting interactive requests (the row requeues "
        "with its emitted tokens and resumes bit-identical)",
    "veles_qos_preempted_tokens_total":
        "Tokens already decoded by preempted batch rows at the "
        "moment of preemption (all carried through the resume, none "
        "re-decoded)",
    "veles_qos_batch_deferrals_total":
        "Queued batch requests jumped by interactive arrivals in the "
        "priority-aware admission order (each deferral counts once "
        "per sweep it was overtaken in)",
    "veles_qos_throttled_total":
        "Batch requests refused admission by the router's AIMD "
        "controller or brownout ladder (503 + scaled Retry-After; "
        "interactive is never throttled)",
    "veles_qos_brownout_transitions_total":
        "Brownout ladder level changes in either direction "
        "(normal -> cap_n_new -> no_spec -> shed_batch and back)",
    "veles_qos_degraded_requests_total":
        "Admitted requests degraded by the brownout ladder (n_new "
        "capped or speculative decoding stripped)",
    "veles_qos_retry_denied_total":
        "Failover retries denied by the router-wide retry token "
        "bucket (storm control: failed first attempts still answer, "
        "they just do not amplify)",
    # load/chaos harness (veles_tpu/loadgen/): these read 0 in
    # non-loadgen runs
    "veles_loadgen_requests_total":
        "Requests dispatched open-loop by the load harness",
    "veles_loadgen_shed_total":
        "Load-harness requests answered 503 (shed/throttled/expired "
        "by the fleet under test)",
    "veles_loadgen_errors_total":
        "Load-harness requests that failed for any non-shed reason "
        "(transport errors, non-503 HTTP errors, timeouts)",
    "veles_loadgen_storms_total":
        "Timed chaos storms armed on the fault plane by the load "
        "harness (one per storm clause per run)",
    # distributed linear-algebra family (veles_tpu/linalg/): these
    # read 0 in non-linalg runs
    "veles_linalg_block_ops_total":
        "Host-side blocked linear-algebra dispatches (k-panel dots, "
        "potrf/trsm panels, SUMMA launches) — the linalg.block_op "
        "fault chokepoint",
    "veles_linalg_matmuls_total":
        "Blocked matmuls completed (single-device panel loop or "
        "SUMMA over the 2D mesh)",
    "veles_linalg_factorizations_total":
        "Blocked Cholesky factorizations completed",
    "veles_linalg_solves_total":
        "Linear solves completed (cholesky_solve calls and CG "
        "workflow finishes)",
    "veles_linalg_iterations_total":
        "Conjugate-gradient iterations run (CGStep executions)",
    "veles_linalg_residual_checks_total":
        "verify_residual trusted-path checks performed (|b-Ax|/|b| "
        "against the stated bound)",
    "veles_linalg_residual_failures_total":
        "Residual checks FAILED — the solve raised instead of "
        "returning a silently-wrong answer (chaos corrupt lands here)",
    # watchtower plane (telemetry/timeseries.py + telemetry/
    # alerts.py): these read 0 in watch-off runs — the sampler
    # thread and rule engine must not exist at all
    # unless root.common.telemetry.watch.enabled
    "veles_watch_samples_total":
        "Metric time-series samples taken by the watchtower "
        "SeriesStore ring (one per sampler period)",
    "veles_watch_pulls_total":
        "Watchtower history pulls served over GET /metrics/history "
        "(router + serving APIs + web status)",
    "veles_alert_evals_total":
        "Alert rule-set evaluation sweeps run by the watchtower "
        "(one per sample)",
    "veles_alert_transitions_total":
        "Alert rule state transitions in either direction "
        "(ok -> firing and firing -> resolved)",
    "veles_alert_critical_unready_total":
        "Critical-severity alert firings that marked this process "
        "unready and dumped the flight-recorder black box",
    "veles_loadgen_alert_aborts_total":
        "Load-harness runs aborted at alert fire time "
        "(--abort-on-alert saw a firing watchtower rule and stopped "
        "offering load)",
}


#: buckets of the span-fed histograms below (telemetry/spans.py
#: SPAN_HISTOGRAMS): 50 us to 1 s, since a phase of a serving tick is
#: tens of microseconds when it has nothing to do and the device wait
#: tens of milliseconds
SPAN_BUCKETS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)

#: canonical histogram names: HELP string + FIXED bucket upper bounds
#: (seconds). Same registration discipline as DESCRIPTIONS — every
#: ``observe("veles_*")`` call site must appear here with HELP and
#: bounds (scripts/check_counters.py fails CI otherwise). Fixed
#: buckets keep fleet aggregation exact: summing the same bounds
#: across N /metrics endpoints is lossless, which per-process
#: quantile sketches would not be.
HISTOGRAMS = {
    # request-plane serving SLOs (serving/scheduler.py Ticket
    # accounting): ZERO samples in non-serving runs
    # (tests/test_telemetry.py test_feature_off_counters_stay_zero)
    "veles_serving_queue_wait_seconds": {
        "help": "Seconds a serving request waited from its arrival, "
                "through its body's parse and the queue, to its "
                "admission, observed at the admission "
                "(deadline-shed/expired requests record their full "
                "wait at the terminal)",
        "buckets": (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                    0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
    },
    "veles_serving_ttft_seconds": {
        "help": "Time to first token: a request's arrival (the "
                "handler has its headers) to the host's read of the "
                "first generated token (prefill output), per request, "
                "observed at that read",
        "buckets": (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                    0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
    },
    "veles_serving_prefill_wait_seconds": {
        "help": "Admission to the host's read of the first token, "
                "per request: the step in flight that the prefill "
                "queues behind, the prefills ahead of it in the same "
                "tick, its own program (queue wait + this = TTFT)",
        "buckets": (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                    0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
    },
    "veles_serving_first_write_seconds": {
        "help": "The first token's way out, per streamed request: "
                "the host's read of it to the end of the handler "
                "thread's write of the first SSE event that carries "
                "a token (the push, the handler's wake under the "
                "interpreter lock, the write)",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_tpot_seconds": {
        "help": "Time per output token after the first (decode "
                "steady-state), per retired request",
        "buckets": (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                    0.05, 0.1, 0.25, 0.5, 1.0),
    },
    "veles_serving_e2e_seconds": {
        "help": "End-to-end serving latency: request enqueue to the "
                "answered ticket, per retired request",
        "buckets": (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                    5.0, 10.0, 30.0, 60.0, 120.0),
    },
    # the serving tick from inside (serving/engine.py _tick, one span
    # a phase; observed on span close, telemetry/spans.py
    # SPAN_HISTOGRAMS). Unlabelled, one sample a tick and phase, and
    # no second is in two of them: their _sum series add up to the
    # tick thread's time
    "veles_serving_tick_admit_seconds": {
        "help": "Serving tick, admission phase: QoS preemption, "
                "taking admissions off the queue, shedding expired "
                "requests",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_tick_prefill_seconds": {
        "help": "Serving tick, prefill phase: admitting the taken "
                "requests (each one's prefill dispatch and first "
                "token) and one chunk of every chunk-prefilling row",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_tick_prepare_seconds": {
        "help": "Serving tick, host preparation: the parameter "
                "snapshot and pool check at the tick's start, then "
                "page growth, the slot mask and the uploads of the "
                "step's host arrays before each dispatch",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_tick_dispatch_seconds": {
        "help": "Serving tick, dispatch: the call of the decode "
                "(speculative, beam) program until it returns",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_tick_device_seconds": {
        "help": "Serving tick, device wait: the host blocked "
                "fetching the step's tokens from the device",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_tick_emit_seconds": {
        "help": "Serving tick, emission: recording the step's tokens "
                "per slot, pushing them to the streams, retiring "
                "finished requests",
        "buckets": SPAN_BUCKETS,
    },
    # the chip known to be unfed (serving/engine.py _unfed): from a
    # blocking read that returned on the NEWEST program dispatched, so
    # that the device has nothing queued, until the engine's next call
    # of a compiled program has returned; by the sync that emptied it.
    # Not named veles_serving_tick_*: these seconds lie inside the
    # phases above
    "veles_serving_unfed_first_token_seconds": {
        "help": "Chip unfed after a first token's read: the prefill "
                "was the newest dispatch, and the rest of the "
                "admission, further admissions' preparation and the "
                "decode step's prepare ran with nothing queued",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_unfed_drain_seconds": {
        "help": "Chip unfed after a drained step's read: the step "
                "landed was the newest dispatch (growth past a "
                "reservation, no plain row left, a speculative or "
                "beam pool, a change of weights); the loop's idle "
                "wait closes the interval unobserved",
        "buckets": SPAN_BUCKETS,
    },
    "veles_serving_loop_wait_seconds": {
        "help": "Serving loop idle: the tick thread waiting for work "
                "between ticks (zero when saturated)",
        "buckets": SPAN_BUCKETS,
    },
    "veles_moe_peak_load_tokens": {
        "help": "Assignments of the fullest held expert of a "
                "sparse-expert layer in one train step or one served "
                "decode step (one sample a layer a step), bucketed "
                "inside the step",
        "buckets": (16, 32, 64, 96, 128, 160, 192, 224, 256, 320, 384,
                    512, 768, 1024, 2048, 4096, 16384, 65536),
    },
    "veles_serving_stream_write_seconds": {
        "help": "Serialising and writing one SSE event of a streamed "
                "reply, per event, all handler threads together",
        "buckets": SPAN_BUCKETS,
    },
}

#: bounds for ad-hoc (unregistered) histogram names — they still
#: record, but check_counters.py fails CI on them, like counters
DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                   10.0, 60.0)

#: the bucket-derived quantiles metrics_text exposes as gauges
QUANTILE_GAUGES = ((0.5, "p50"), (0.9, "p90"), (0.99, "p99"))


def describe_histogram(name: str) -> str:
    entry = HISTOGRAMS.get(name)
    return entry["help"] if entry else "veles_tpu histogram"


def histogram_buckets(name: str) -> Tuple[float, ...]:
    entry = HISTOGRAMS.get(name)
    return tuple(entry["buckets"]) if entry else DEFAULT_BUCKETS


def histogram_quantile(bounds, counts, q: float) -> Optional[float]:
    """Prometheus ``histogram_quantile`` estimation from fixed
    buckets: ``counts[i]`` is the NON-cumulative count of bucket
    ``bounds[i]`` (``counts[-1]`` the +Inf overflow). Linear
    interpolation inside the winning bucket; values landing in the
    overflow bucket report the largest finite bound (the histogram
    cannot see past it). None when the histogram is empty — shared
    by the live registry and fleet aggregation so both surfaces
    answer 'what is p99' with the same arithmetic."""
    total = sum(counts)
    if total <= 0:
        return None
    rank = q * total
    cum = 0.0
    for i, cnt in enumerate(counts):
        prev = cum
        cum += cnt
        if cum >= rank and cnt > 0:
            if i >= len(bounds):            # +Inf overflow bucket
                return float(bounds[-1]) if bounds else None
            lower = float(bounds[i - 1]) if i > 0 else 0.0
            upper = float(bounds[i])
            return lower + (upper - lower) * (rank - prev) / cnt
    return float(bounds[-1]) if bounds else None


class HistogramRegistry:
    """Thread-safe fixed-bucket histograms (the latency twin of
    :class:`CounterRegistry`): flat name → (bucket counts, sum).
    Entries appear on first ``observe`` — an idle process renders no
    histogram rows at all, so non-serving /metrics pages stay
    exactly as before."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: name -> per-bucket counts, len(bounds) + 1 (+Inf overflow)
        self._counts: Dict[str, List[int]] = {}
        self._sums: Dict[str, float] = {}
        self._bounds: Dict[str, Tuple[float, ...]] = {}

    def observe(self, name: str, value: float) -> None:
        """Record one sample into ``name``'s fixed buckets."""
        value = float(value)
        with self._lock:
            counts = self._counts.get(name)
            if counts is None:
                bounds = histogram_buckets(name)
                self._bounds[name] = bounds
                counts = self._counts[name] = [0] * (len(bounds) + 1)
                self._sums[name] = 0.0
            counts[bisect.bisect_left(self._bounds[name], value)] += 1
            self._sums[name] += value

    def add(self, name: str, bucket_counts: Dict[int, int],
            total: float) -> None:
        """Samples that were bucketed elsewhere (telemetry/steptaps.py:
        inside the train step, by ``observe``'s own rule):
        ``bucket_counts`` is {bucket index: samples}, ``total`` their
        sum of values."""
        if not bucket_counts:
            return
        with self._lock:
            counts = self._counts.get(name)
            if counts is None:
                bounds = histogram_buckets(name)
                self._bounds[name] = bounds
                counts = self._counts[name] = [0] * (len(bounds) + 1)
                self._sums[name] = 0.0
            for i, n in bucket_counts.items():
                counts[i] += n
            self._sums[name] += float(total)

    def count(self, name: str) -> int:
        with self._lock:
            return sum(self._counts.get(name, ()))

    def sum(self, name: str) -> float:
        with self._lock:
            return self._sums.get(name, 0.0)

    def snapshot(self) -> Dict[str, Dict]:
        """{name: {bounds, counts, sum, count}} — one instant."""
        with self._lock:
            return {
                name: {"bounds": self._bounds[name],
                       "counts": tuple(counts),
                       "sum": self._sums[name],
                       "count": sum(counts)}
                for name, counts in self._counts.items()}

    def quantile(self, name: str, q: float) -> Optional[float]:
        """Bucket-interpolated quantile; None when no samples."""
        with self._lock:
            counts = self._counts.get(name)
            if counts is None:
                return None
            bounds, counts = self._bounds[name], tuple(counts)
        return histogram_quantile(bounds, counts, q)

    def reset(self) -> None:
        """Zero everything — tests only
        (same contract as :meth:`CounterRegistry.reset`)."""
        with self._lock:
            self._counts.clear()
            self._sums.clear()
            self._bounds.clear()

    def prometheus_text(self, snap: Optional[Dict] = None) -> str:
        """Prometheus histogram exposition: cumulative ``_bucket{le=}``
        series plus ``_sum``/``_count`` per recorded histogram."""
        snap = self.snapshot() if snap is None else snap
        lines = []
        for name in sorted(snap):
            h = snap[name]
            lines.append("# HELP %s %s"
                         % (name, describe_histogram(name)))
            lines.append("# TYPE %s histogram" % name)
            cum = 0
            for bound, cnt in zip(h["bounds"], h["counts"]):
                cum += cnt
                lines.append('%s_bucket{le="%s"} %d'
                             % (name, format(float(bound), "g"), cum))
            lines.append('%s_bucket{le="+Inf"} %d'
                         % (name, h["count"]))
            s = float(h["sum"])
            lines.append("%s_sum %s"
                         % (name, int(s) if s.is_integer() else
                            round(s, 9)))
            lines.append("%s_count %d" % (name, h["count"]))
        return "\n".join(lines) + "\n" if lines else ""


#: THE process-global histogram registry (mirrors ``counters``).
histograms = HistogramRegistry()


def observe(name: str, value: float) -> None:
    histograms.observe(name, value)


def describe_counter(name: str) -> str:
    return DESCRIPTIONS.get(name, "veles_tpu counter")


#: increment observers installed by the flight recorder
#: (telemetry/recorder.py): called as ``hook(name, value, new_total)``
#: AFTER the registry lock is released, exceptions swallowed — an
#: observer can never deadlock or take an instrumented call site down.
_inc_hooks = []


def add_inc_hook(fn) -> None:
    if fn not in _inc_hooks:
        _inc_hooks.append(fn)


class CounterRegistry:
    """Flat, thread-safe name → value map of monotonic counters."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._values: Dict[str, float] = {}

    def inc(self, name: str, value: float = 1) -> float:
        """Add ``value`` (default 1) to ``name``; returns the new total."""
        with self._lock:
            new = self._values.get(name, 0) + value
            self._values[name] = new
        for hook in _inc_hooks:
            try:
                hook(name, value, new)
            except Exception:       # noqa: BLE001 — observers only
                pass
        return new

    def get(self, name: str) -> float:
        with self._lock:
            return self._values.get(name, 0)

    def read(self, names: Tuple[str, ...]) -> List[float]:
        """The values of ``names`` and nothing else of the registry (a
        span reads its four counters on begin and on end:
        telemetry/spans.py). Each value is read whole; the lock that
        would make the four one instant's is not taken, since a span's
        deltas are of what ran inside it on its own thread."""
        get = self._values.get
        return [get(k, 0) for k in names]

    def snapshot(self) -> Dict[str, float]:
        """Point-in-time copy of every counter."""
        with self._lock:
            return dict(self._values)

    def delta(self, before: Dict[str, float],
              names: Optional[tuple] = None) -> Dict[str, float]:
        """Per-counter growth since a :meth:`snapshot`; zero-growth
        counters are omitted so span records stay small."""
        now = self.snapshot()
        keys = names if names is not None else now.keys()
        out = {}
        for k in keys:
            d = now.get(k, 0) - before.get(k, 0)
            if d:
                out[k] = d
        return out

    def reset(self) -> None:
        """Zero everything — tests only
        (production counters are monotonic for the life of the
        process, as Prometheus scraping expects)."""
        with self._lock:
            self._values.clear()

    def prometheus_text(self) -> str:
        """Prometheus exposition format (text/plain; version 0.0.4).
        One snapshot renders the whole page — names and values from
        the same instant."""
        lines = []
        for name, val in sorted(self.snapshot().items()):
            lines.append("# HELP %s %s" % (name, describe_counter(name)))
            lines.append("# TYPE %s counter" % name)
            # integral counters print without a trailing .0 (scrapers
            # accept both; humans diff these files)
            lines.append("%s %s" % (
                name, int(val) if float(val).is_integer() else val))
        return "\n".join(lines) + "\n"


#: THE process-global registry every instrumented call site uses.
counters = CounterRegistry()


#: Content-Type every /metrics endpoint replies with
METRICS_CONTENT_TYPE = "text/plain; version=0.0.4"


def metrics_text(gauges: Optional[dict] = None) -> str:
    """The full /metrics page: the counter registry, the histogram
    registry (with bucket-derived p50/p90/p99 quantile gauges per
    recorded histogram), then the caller's service gauges — THE one
    renderer behind every /metrics endpoint (web_status, RESTfulAPI,
    GenerationAPI), so format changes happen in one place. ``gauges``:
    name → value (or (value, help) tuple). A caller gauge whose name
    shadows an already-rendered series is DROPPED and counted
    (``veles_metrics_name_collisions_total``) — duplicate metric
    names are invalid exposition and would break every scraper; the
    collision counter itself lands on the next scrape (this page's
    counter section is already snapshotted)."""
    text = counters.prometheus_text()
    taken = set(counters.snapshot())
    hsnap = histograms.snapshot()
    text += histograms.prometheus_text(hsnap)
    for name in sorted(hsnap):
        taken.update((name, name + "_bucket", name + "_sum",
                      name + "_count"))
        h = hsnap[name]
        if not h["count"]:
            continue
        for q, label in QUANTILE_GAUGES:
            value = histogram_quantile(h["bounds"], h["counts"], q)
            gname = "%s_%s" % (name, label)
            text += gauge_text(
                gname, round(value, 9),
                "Bucket-estimated %s of %s" % (label, name))
            taken.add(gname)
    for name, val in (gauges or {}).items():
        if name in taken:
            counters.inc("veles_metrics_name_collisions_total")
            continue
        help_text = None
        if isinstance(val, tuple):
            val, help_text = val
        text += gauge_text(name, val, help_text)
        taken.add(name)
    return text


def gauge_text(name: str, value, help_text: Optional[str] = None) -> str:
    """One Prometheus gauge in exposition format — the shared renderer
    for the ad-hoc service gauges every /metrics endpoint appends after
    :func:`prometheus_text` (web_status, RESTfulAPI, GenerationAPI)."""
    lines = []
    if help_text:
        lines.append("# HELP %s %s" % (name, help_text))
    lines.append("# TYPE %s gauge" % name)
    val = float(value)
    lines.append("%s %s" % (name, int(val) if val.is_integer() else val))
    return "\n".join(lines) + "\n"


def inc(name: str, value: float = 1) -> float:
    return counters.inc(name, value)


def snapshot() -> Dict[str, float]:
    return counters.snapshot()


def prometheus_text() -> str:
    return counters.prometheus_text()
