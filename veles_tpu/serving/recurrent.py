"""O(1)-state serving lane: a recurrent slot pool for SSM/linear-
attention and LSTM stacks.

The paged engine's unit of per-slot memory is a page table over an
O(context) KV pool. A recurrent stack (``nn/ssm.py``'s SSMBlock,
``nn/rnn.py``'s LSTM/RNN) needs neither: its whole past is a FIXED
per-slot state tensor (per head an ``e x e`` matrix, or an LSTM's
``(h, c)`` pair), so a slot costs constant HBM whatever the context —
the "portable O(1) autoregressive caching" half of PAPERS.md's
"Compiler-First State Space Duality and Portable O(1) Autoregressive
Caching for Inference". This module hosts that lane on the SAME
request plane as :class:`~veles_tpu.serving.engine.ContinuousEngine`:

- **two proven-equivalent modes, ONE step body**: prefill runs the
  chunked parallel scan (``lax.scan`` of ``step_state`` over
  ``page_size``-token chunks), decode runs the single application of
  the same body — bit-identity between the modes is structural (see
  nn/ssm.py), so a scanned prompt and a decoded continuation cannot
  drift;
- **pageless slots**: the :class:`SlotScheduler` runs with
  ``page_pool=None`` (``slot_kind="state"``) — admission never
  reserves pages, decode can never shed on page exhaustion, and the
  pool's HBM is ``max_slots x state_bytes_per_slot``, constant in
  sequence length. At equal HBM this serves a multiple of the paged
  transformer pool's concurrent slots;
- **state-checkpoint prefix cache**: the prefix-cache analog for a
  lane with no pages. Prefill snapshots the slot's state at every
  ``page_size``-token block boundary into a radix
  :class:`~veles_tpu.serving.pages.StateCache`; a later admission
  sharing the prefix adopts the deepest snapshot COPY-ON-WRITE (one
  host→device row upload) and scans only the suffix — a shared
  system prompt costs one snapshot, not a re-scan per request;
- **the whole request plane rides along**: SSE streaming
  (``Ticket.push_tokens`` at every step boundary), token-level
  failover resume (``fold_resume`` + ``advanced_prng_key`` — restore
  the nearest checkpoint, re-scan the gap, id-exact), drain-by-
  handoff, the ``serve.replica_death`` / ``serve.decode_step`` chaos
  sites plus the lane's own ``serve.state_restore`` /
  ``serve.state_checkpoint`` fault points, and the AOT serve-artifact
  (labels ``rscan``/``rstep``, ARTIFACT_VERSION 4) for a zero-compile
  cold start.

Exactly TWO fixed-shape jitted programs serve the lane — the chunk
scan and the decode step — co-tenant with (and shaped like) the paged
tick, so the jit cache stays bounded however long the prompts get.

Operator guide: docs/services.md "O(1)-state serving".
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy

from ..error import VelesError
from ..logger import Logger
from ..nn.sampling import (_embed_prompt, _head_logits,
                           _split_rows, params_of)
from ..nn.ssm import mask_keep
from ..resilience import health
from ..resilience.faults import FaultInjected, fire as fire_fault
from ..telemetry.counters import inc
from ..telemetry.spans import span
from .engine import (advanced_prng_key, fold_resume,   # noqa: F401
                     _TEMP_EPS, _STEP_MODES)
from .pages import StateCache


def split_recurrent_stack(forwards) -> Dict:
    """Partition a workflow's forwards into the recurrent serving
    stack: ``Embedding`` → recurrent units (anything exposing the
    ``init_state``/``step_state``/``scan_state`` protocol — SSMBlock,
    LSTM, RNN) → ``LMHead``. Raises :class:`VelesError` on any other
    shape — notably a ``PositionalEmbedding`` anywhere in the chain:
    a constant-size state carries no notion of absolute position, so
    a position-dependent stack cannot ride the O(1) lane."""
    from ..nn.transformer import Embedding, LMHead
    units = list(forwards or ())
    names = [type(u).__name__ for u in units]

    def reject():
        raise VelesError(
            "O(1)-state serving supports Embedding → "
            "(SSMBlock|LSTM|RNN)* → LMHead chains; found %s"
            % (names or "no forwards"))

    if len(units) < 2 or not isinstance(units[0], Embedding) \
            or not isinstance(units[-1], LMHead):
        reject()
    blocks = units[1:-1]
    for blk in blocks:
        if not (hasattr(blk, "step_state")
                and hasattr(blk, "init_state")
                and hasattr(blk, "scan_state")):
            reject()
    return {"stem": units[0], "blocks": blocks, "head": units[-1]}


class RecurrentEngine(Logger):
    """In-flight batching over a persistent fixed-size state pool.

    ``wf`` is a recurrent generation workflow (``Embedding`` →
    recurrent units → ``LMHead``, validated at construction).
    ``page_size`` is the lane's CHECKPOINT INTERVAL: prefill scans in
    ``page_size``-token chunks and snapshots the state at each full
    chunk's boundary — the same knob that sizes the paged pool's
    blocks keeps the two lanes' prefix granularity comparable.
    ``decode_block`` fuses that many decode steps into one dispatch
    (``lax.scan``), exactly like the paged tick.
    """

    def __init__(self, wf, max_slots: int = 8,
                 max_context: int = 640, decode_block: int = 1,
                 page_size: Optional[int] = None,
                 state_cache: Optional[bool] = None,
                 artifact: Optional[str] = None,
                 name: str = "serving") -> None:
        super().__init__()
        from ..config import root
        from .scheduler import SlotScheduler
        self.wf = wf
        self.name = name
        serving_cfg = root.common.serving
        self.artifact = str(
            serving_cfg.get("artifact", "")
            if artifact is None else (artifact or ""))
        self.artifact_mode = False
        self.compiled_live = 0
        # raises VelesError on anything but a recurrent generation
        # stack — the GenerationAPI fallback chain keys off this
        self.stack = split_recurrent_stack(
            list(getattr(wf, "forwards", ()) or ()))
        self.max_slots = int(max_slots)
        self.max_context = int(max_context)
        self.decode_block = max(1, int(decode_block))
        # wire defaults for the /generate parser: the O(1) lane has no
        # speculative/beam programs, but clients omitting gamma/beam
        # must still parse — accepts() then rejects those modes to the
        # window worker
        self.spec_gamma = int(serving_cfg.get("spec_gamma", 4))
        self.beam_width = int(serving_cfg.get("beam_width", 4))
        self.page_size = int(
            serving_cfg.get("page_size", 16)
            if page_size is None else page_size)
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        want_cache = bool(
            serving_cfg.get("state_cache", False)
            if state_cache is None else state_cache)
        self.state_cache: Optional[StateCache] = (
            StateCache(self.page_size,
                       serving_cfg.get("state_cache_blocks", None))
            if want_cache else None)
        # pageless admission: no page pool, so the scheduler's ledger
        # paths are structurally inert — admission is on free SLOTS
        # only and page exhaustion cannot exist on this lane. One
        # bucket (= max_context): chunked scanning serves any prompt
        # length, so there is no prefill-program count to bound with
        # a bucket ladder
        self.scheduler = SlotScheduler(self.max_slots,
                                       (self.max_context,),
                                       self.max_context,
                                       page_pool=None,
                                       slot_kind="state")
        #: QoS plane (docs/services.md "Overload & QoS"): off by
        #: default — the feature-off lock keeps admission strict FIFO
        #: and the preemption path structurally unreachable
        self.qos = bool(serving_cfg.get("qos", False))
        self.scheduler.qos = self.qos
        self._pressure_fn = lambda: (self.scheduler.queue_depth(),
                                     max(8, self.max_slots * 8))
        self.preemptions = 0
        self.preempted_tokens = 0
        self._progs: Dict = {}
        self._params = None
        self._states = None
        self._keys = None
        self._tok = numpy.zeros(self.max_slots, numpy.int32)
        self._pos = numpy.zeros(self.max_slots, numpy.int32)
        self._temp = numpy.zeros(self.max_slots, numpy.float32)
        self._thread: Optional[threading.Thread] = None
        self._closing = False
        self._handoff: Optional[Tuple] = None
        #: replica-death hook (set by GenerationAPI) — same contract
        #: as the paged engine's
        self.on_death = None
        self.admitted = 0
        self.retired = 0
        self.peak_slots = 0
        #: requests that adopted a state checkpoint / chunk dispatches
        #: run / lane counters mirrored as gauges for stats()
        self.prefix_requests = 0
        self.chunk_dispatches = 0
        self.state_restores = 0
        self.state_rescans = 0
        self.state_checkpoints = 0

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "RecurrentEngine":
        if self._thread is not None:
            return self
        if self.artifact and not self.artifact_mode:
            self._load_artifact()
        if self.qos:
            from .overload import set_pressure_provider
            set_pressure_provider(self._pressure_fn)
        self._closing = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=self.name + ".engine")
        self._thread.start()
        from . import register_engine
        register_engine(self)
        self.info("%s: O(1)-state serving up (slots=%d max_context=%d "
                  "decode_block=%d checkpoint_every=%d%s)",
                  self.name, self.max_slots, self.max_context,
                  self.decode_block, self.page_size,
                  " +state_cache" if self.state_cache is not None
                  else "")
        return self

    def stop(self) -> None:
        with self.scheduler.cv:
            self._closing = True
            self.scheduler.cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        pending_handoff, self._handoff = self._handoff, None
        if pending_handoff is not None:
            pending_handoff[1].set()
        self.scheduler.drain("server shutting down")
        self._abort_active("server shutting down", code=503,
                           retry_after=5.0, count_shed=False)
        if self.state_cache is not None:
            self.state_cache.clear()
        from .overload import clear_pressure_provider
        clear_pressure_provider(self._pressure_fn)
        from . import unregister_engine
        unregister_engine(self)

    # -- intake --------------------------------------------------------------
    def accepts(self, req: Dict) -> Optional[str]:
        """None when the state pool can serve ``req``; otherwise the
        reason (caller falls back to the window-coalescing path)."""
        t_p, n_new = len(req["prompt"]), int(req["n_new"])
        mode = str(req.get("mode", "greedy"))
        if mode not in _STEP_MODES:
            # fail CLOSED like the paged engine: an unknown (or
            # spec/beam) mode has no fixed-shape program here
            return ("O(1)-state pool serves greedy/sample only "
                    "(mode=%s)" % mode)
        if t_p < 1:
            return "empty prompt"
        reason = self.scheduler.reject_reason(t_p, n_new, mode=mode)
        if reason:
            return reason
        if 0 < float(req.get("temperature", 0.0)) < _TEMP_EPS:
            return ("temperature %g below the engine's %g resolution"
                    % (req["temperature"], _TEMP_EPS))
        return None

    def submit(self, req: Dict, ticket,
               max_queue: Optional[int] = None,
               checked: bool = False) -> bool:
        """Enqueue one request; False = queue bound hit or closing
        (caller sheds). Same contract as the paged engine's."""
        if not checked:
            reason = self.accepts(req)
            if reason is not None:
                ticket.fail(reason, code=400)
                return True
        with self.scheduler.cv:
            if self._closing:
                return False
            return self.scheduler.push(req, ticket, max_queue)

    def serve(self, reqs: List[Dict], timeout: float = 300.0
              ) -> List[List[int]]:
        """Synchronous convenience (tests): submit every
        request, wait, return each token list; raises on any error."""
        from .scheduler import Ticket
        tickets = [Ticket() for _ in reqs]
        for req, ticket in zip(reqs, tickets):
            if not self.submit(req, ticket):
                raise VelesError("serving queue full")
        out = []
        for req, ticket in zip(reqs, tickets):
            if not ticket.event.wait(timeout):
                raise VelesError("serving timed out for %r" % (req,))
            if ticket.error is not None:
                raise VelesError("serving failed: %s" % ticket.error)
            out.append(ticket.result["tokens"])
        return out

    # -- observability -------------------------------------------------------
    def state_bytes_per_slot(self) -> int:
        """HBM one slot's recurrent state occupies — CONSTANT in
        sequence length (the lane's whole point;
        tests/test_o1_serving.py holds it flat vs token count)."""
        if self._states is not None:
            return sum(int(leaf.nbytes) for st in self._states
                       for leaf in st.values()) // self.max_slots
        import jax.numpy as jnp
        dtype = jnp.dtype(jnp.float32)
        total = 0
        for blk in self.stack["blocks"]:
            for shape in blk.state_shapes(1).values():
                total += int(numpy.prod(shape)) * dtype.itemsize
        return total

    def stats(self) -> Dict[str, float]:
        pool_bytes = (0 if self._states is None else
                      sum(int(leaf.nbytes) for st in self._states
                          for leaf in st.values()))
        cache_stats = (self.state_cache.stats()
                       if self.state_cache is not None
                       else {"blocks": 0, "bytes": 0})
        return {
            "slots": self.max_slots,
            "slots_busy": self.scheduler.busy_count(),
            "peak_slots": self.peak_slots,
            "queue_depth": self.scheduler.queue_depth(),
            "admitted": self.admitted,
            "retired": self.retired,
            "qos": int(self.qos),
            "preemptions": self.preemptions,
            "preempted_tokens": self.preempted_tokens,
            "programs": len(self._progs),
            # the slot-kind discriminator: /metrics renders
            # veles_serving_pages_* rows ONLY for paged engines, so a
            # pageless replica can never skew the fleet's page math
            # (the router ranks on slot occupancy, comparable across
            # kinds)
            "slot_kind": "state",
            "pages_total": 0,
            "pages_in_use": 0,
            "page_size": self.page_size,
            "page_fragmentation": 0.0,
            "prefix_cache": int(self.state_cache is not None),
            "prefix_blocks": cache_stats["blocks"],
            "prefix_requests": self.prefix_requests,
            "prefill_chunk": self.page_size,
            "chunk_dispatches": self.chunk_dispatches,
            "prefilling": 0,
            "prefill_stall_seconds": 0.0,
            "artifact_mode": int(self.artifact_mode),
            "quant_weights": 0,
            "quant_kv": 0,
            "compiled_live": self.compiled_live,
            # the O(1) claim as a gauge: per-slot state HBM, constant
            # however long each slot has decoded
            "kv_pool_bytes": pool_bytes,
            "state_bytes_per_slot": self.state_bytes_per_slot(),
            "state_cache_blocks": cache_stats["blocks"],
            "state_cache_bytes": cache_stats["bytes"],
            "state_checkpoints": self.state_checkpoints,
            "state_restores": self.state_restores,
            "state_rescans": self.state_rescans,
        }

    @property
    def closing(self) -> bool:
        return self._closing

    @property
    def programs_built(self) -> int:
        return len(self._progs)

    def programs_bound(self) -> int:
        """The hard ceiling on :attr:`programs_built`: the chunk scan
        and the decode step. TWO, whatever the traffic — chunked
        scanning needs no bucket ladder."""
        return 2

    # -- worker --------------------------------------------------------------
    def _loop(self) -> None:
        hb = "serving.%s" % self.name
        fail_streak = 0
        try:
            while True:
                with self.scheduler.cv:
                    while (not self.scheduler._queue
                           and self.scheduler.busy_count() == 0
                           and self._handoff is None
                           and not self._closing):
                        self.scheduler.cv.wait(timeout=5.0)
                        if not self._closing:
                            health.heartbeats.beat(hb)
                    if self._closing:
                        return
                health.heartbeats.beat(hb)
                try:
                    self._tick()
                    fail_streak = 0
                except Exception:     # noqa: BLE001 — serve, don't die
                    fail_streak += 1
                    self.exception("%s: serving tick failed", self.name)
                    self._abort_active("internal serving error",
                                       code=500, count_shed=False)
                    self._reset_pool()
                    from .scheduler import shed_expired
                    shed_expired(self.scheduler.expire_queued())
                    if not self._closing:
                        time.sleep(min(1.0, 0.05 * (2 ** fail_streak)))
        finally:
            health.heartbeats.unregister(hb)

    def _reset_pool(self) -> None:
        self._states = self._keys = None
        self._params = None

    def _tick(self) -> None:
        """One step boundary: admit into free slots (each admission
        scans its whole prompt chunk-by-chunk), then advance every
        busy row by one fixed-shape decode dispatch."""
        pending_handoff = self._handoff
        if pending_handoff is not None:
            self._handoff = None
            reason, done, box = pending_handoff
            try:
                box["count"] = self._do_handoff(reason)
            finally:
                done.set()
            return
        if self.scheduler.busy_count():
            try:
                fire_fault("serve.replica_death")
            except FaultInjected:
                self.warning("%s: injected replica death mid-decode — "
                             "settling in-flight tickets with resume "
                             "progress and tearing the front down",
                             self.name)
                self._abort_active(
                    "replica died mid-decode", code=503,
                    retry_after=1.0, count_shed=False)
                death = self.on_death
                if death is not None:
                    death()
                return
        params = self._params
        if params is None or self.scheduler.busy_count() == 0:
            params = self._params = params_of(self.wf)
        self._ensure_pool(params)
        from .scheduler import shed_expired
        if self.qos:
            self._preempt_for_interactive()
        admissions, expired = self.scheduler.take_admissions()
        shed_expired(expired)
        for slot in admissions:
            if self.scheduler.slots[slot.idx] is not slot:
                continue
            try:
                self._admit(params, slot)
            except Exception as e:    # noqa: BLE001 — answer, don't die
                self._retire_slot(slot)
                slot.ticket.fail("%s: %s" % (type(e).__name__, e),
                                 code=500)
                # the chunk program DONATES the state pool: a dead
                # dispatch may have consumed the co-tenants' rows
                # with it — shed and rebuild rather than decode on
                # possibly-dead buffers
                self.exception("%s: admission failed; resetting the "
                               "state pool", self.name)
                self._abort_active("serving pool reset after a failed "
                                   "admission", code=503,
                                   retry_after=1.0)
                self._reset_pool()
                return
        self.peak_slots = max(self.peak_slots,
                              self.scheduler.busy_count())
        try:
            if self.scheduler.active():
                self._decode(params)
        except FaultInjected as e:
            self._abort_active(str(e), code=503, retry_after=1.0)

    # -- QoS preemption --------------------------------------------------------
    @staticmethod
    def _emitted(slot) -> List[int]:
        """Every token the client's ORIGINAL request has produced so
        far: internally-folded preempt prefixes plus this admission's
        tokens. All progress/result reporting goes through this so
        preemption stays invisible to the wire."""
        return list(slot.req.get("_qos_prefix", ())) + list(slot.tokens)

    def _preempt_victims(self, need: int) -> List:
        from .overload import request_priority
        victims = [s for s in self.scheduler.active()
                   if s.group is None and s.mode in _STEP_MODES
                   and request_priority(s.req) == "batch"
                   and s.prefilled is None and s.tokens
                   and len(s.tokens) < s.n_new]
        # evict the least-invested first (fewest tokens to re-fold)
        victims.sort(key=lambda s: (len(s.tokens), s.idx))
        return victims[:max(0, need)]

    def _preempt_for_interactive(self) -> None:
        """Free state slots for queued interactive requests by
        requeueing batch rows at this step boundary with their resume
        payload — same fold_resume/advanced_prng_key machinery as
        failover, so the preempted decode finishes bit-identical."""
        from .overload import qos_preempt_enabled, request_priority
        if not qos_preempt_enabled():
            return
        with self.scheduler.cv:
            waiting = sum(1 for req, _t in self.scheduler._queue
                          if request_priority(req) == "interactive")
            free = len(self.scheduler._free)
        if waiting <= free:
            return
        for slot in self._preempt_victims(waiting - free):
            emitted = self._emitted(slot)
            resumed = fold_resume(slot.req, slot.tokens)
            # fold_resume records only THIS fold's length; the PRNG
            # re-entry point is every token ever emitted, so a twice-
            # preempted request must accumulate
            resumed["resume_k"] = (int(slot.req.get("resume_k", 0)
                                       or 0) + len(slot.tokens))
            resumed["_qos_prefix"] = emitted
            resumed["_requeued"] = True
            slot.ticket.set_progress(emitted)
            self._retire_slot(slot)
            self.scheduler.push(resumed, slot.ticket)
            self.preemptions += 1
            self.preempted_tokens += len(slot.tokens)
            inc("veles_qos_preemptions_total")
            inc("veles_qos_preempted_tokens_total", len(slot.tokens))
            self.debug("%s: preempted batch slot %d at %d tokens for "
                       "an interactive admission (request %s)",
                       self.name, slot.idx, len(slot.tokens),
                       slot.ticket.request_id)

    def _ensure_pool(self, params) -> None:
        if self._states is not None:
            return
        import jax.numpy as jnp
        stem = self.stack["stem"]
        dtype = params[stem.name]["table"].dtype
        self._states = tuple(blk.init_state(self.max_slots, dtype)
                             for blk in self.stack["blocks"])
        self._keys = jnp.zeros((self.max_slots, 2), jnp.uint32)

    def _set_state_row(self, idx: int, snap) -> None:
        """Initialize one slot's state row: zeros for a cold scan, or
        an adopted checkpoint snapshot (COPY-ON-WRITE: the host
        snapshot is uploaded, never aliased — the cache's copy stays
        bit-untouched however the slot decodes on)."""
        import jax.numpy as jnp
        new = []
        for bi, st in enumerate(self._states):
            row = {}
            for k, leaf in st.items():
                if snap is None:
                    val = jnp.zeros(leaf.shape[1:], leaf.dtype)
                else:
                    val = jnp.asarray(snap[bi][k][0], leaf.dtype)
                row[k] = leaf.at[idx].set(val)
            new.append(row)
        self._states = tuple(new)

    # -- admission ------------------------------------------------------------
    def _admit(self, params, slot) -> None:
        import jax
        import jax.numpy as jnp
        prompt = slot.req["prompt"]
        t_p = slot.t_p
        C = self.page_size
        # -- checkpoint restore (the prefix-cache analog) ------------
        # match over prompt[:-1]: at least one token must scan (the
        # final chunk emits the first token's logits), so a full-
        # prompt match adopts the PREVIOUS boundary's snapshot and
        # re-scans the tail — the state-lane shape of the paged
        # cache's copy-on-write last page
        start, snap = 0, None
        if self.state_cache is not None:
            try:
                # raise = injected checkpoint loss, corrupt = injected
                # index rot: both DEGRADE to a shorter/empty match and
                # a longer re-scan — token equality inside match() is
                # the authority, so a rotten index can never restore a
                # wrong state
                corrupting = fire_fault("serve.state_restore")
                start, snap = self.state_cache.match(
                    prompt[:t_p - 1], corrupt=corrupting)
            except FaultInjected as e:
                self.warning("%s: injected state-restore fault (%s) — "
                             "degrading to a full re-scan",
                             self.name, e)
                start, snap = 0, None
                self.state_rescans += 1
                inc("veles_o1_state_rescans_total")
            if start:
                self.state_restores += 1
                self.prefix_requests += 1
                inc("veles_o1_state_restores_total")
                inc("veles_o1_state_restored_tokens_total", start)
        self._set_state_row(slot.idx, snap)
        resume_k = int(slot.req.get("resume_k", 0) or 0)
        if resume_k:
            inc("veles_resume_tokens_total", resume_k)
        seed = int(slot.req.get("seed", 0))
        # -- chunked scan over the (unmatched) prompt ----------------
        snaps: Dict[int, Tuple] = {}
        p0 = start
        first = None
        with span("serving.prefill", bucket=C, slot=slot.idx,
                  t_p=t_p, mode=slot.mode,
                  request_id=slot.ticket.request_id,
                  trace_id=slot.ticket.trace_id,
                  attempt=slot.ticket.attempt):
            while True:
                n_real = min(C, t_p - p0)
                final = p0 + n_real >= t_p
                ids = numpy.zeros(C, numpy.int32)
                ids[:n_real] = prompt[p0:p0 + n_real]
                # the PRNG carry matters only at the final chunk (it
                # samples the first token); resumed requests re-enter
                # their stream exactly like the paged prefill does
                seed_key = (advanced_prng_key(seed, resume_k)
                            if final and resume_k
                            else jax.random.PRNGKey(seed))
                first, self._keys, self._states, row = \
                    self._program("scan")(
                        params, jnp.asarray(ids), numpy.int32(n_real),
                        numpy.int32(slot.idx),
                        numpy.float32(slot.temperature), seed_key,
                        numpy.int32(1 if final else 0),
                        self._keys, self._states)
                inc("veles_serving_prefill_dispatches_total")
                inc("veles_serving_prefill_positions_total", C)
                self.chunk_dispatches += 1
                boundary = p0 + n_real
                if n_real == C and self.state_cache is not None:
                    # a full chunk ends on a block boundary: snapshot
                    # the row's state host-side — the checkpoint the
                    # next same-prefix admission adopts
                    snaps[boundary // C] = tuple(
                        {k: numpy.asarray(v) for k, v in st.items()}
                        for st in row)
                if final:
                    break
                p0 = boundary
        self._pos[slot.idx] = t_p
        self._temp[slot.idx] = slot.temperature
        if not slot.req.get("_requeued"):
            # a preempt-requeue is the SAME admitted request coming
            # back — count it once, at its first admission
            inc("veles_serving_admitted_total")
            self.admitted += 1
        first = int(first)
        slot.ticket.mark_prefill_done()
        slot.ticket.mark_first_token()
        self._tok[slot.idx] = first
        self._checkpoint_insert(slot, snaps)
        done = slot.record(first)
        slot.ticket.push_tokens([first])
        if done:
            self._finish(slot)

    def _checkpoint_insert(self, slot, snaps: Dict[int, Tuple]) -> None:
        """Cache a freshly scanned prompt's block-boundary snapshots
        so the next admission adopts them. The ``serve.state_checkpoint``
        fault point degrades to NOT caching — the request itself is
        already answered from the live state, so an injected failure
        costs future admissions a re-scan, never correctness."""
        if self.state_cache is None or not snaps:
            return
        n_blocks = slot.t_p // self.page_size
        if not n_blocks:
            return
        try:
            fire_fault("serve.state_checkpoint")
        except FaultInjected as e:
            self.warning("%s: injected state-checkpoint fault (%s) — "
                         "prompt not cached; same-prefix admissions "
                         "re-scan", self.name, e)
            return
        added = self.state_cache.insert(
            slot.req["prompt"][:n_blocks * self.page_size],
            [snaps.get(i + 1) for i in range(n_blocks)])
        if added:
            self.state_checkpoints += added
            inc("veles_o1_state_checkpoints_total", added)

    # -- the decode chunk ------------------------------------------------------
    def _decode(self, params) -> None:
        import jax.numpy as jnp
        active = self.scheduler.active()
        if not active:
            return
        mask = numpy.zeros(self.max_slots, numpy.int32)
        for slot in active:
            mask[slot.idx] = 1
        base_len = {id(s): len(s.tokens) for s in active}
        fire_fault("serve.decode_step")
        with span("serving.decode_step", active=len(active),
                  chunk=self.decode_block):
            toks, self._keys, self._states = self._program("step")(
                params, jnp.asarray(self._tok),
                jnp.asarray(self._temp), jnp.asarray(mask),
                self._keys, self._states)
            toks = numpy.asarray(toks)          # (decode_block, S)
        inc("veles_serving_decode_dispatches_total")
        finished: List = []
        for h in range(toks.shape[0]):
            still = [s for s in active if s not in finished]
            if not still:
                break
            for slot in still:
                token = int(toks[h, slot.idx])
                self._tok[slot.idx] = token
                self._pos[slot.idx] += 1
                if slot.record(token):
                    finished.append(slot)
        for slot in active:
            slot.ticket.push_tokens(slot.tokens[base_len[id(slot)]:])
        for slot in finished:
            self._finish(slot)

    # -- retirement -------------------------------------------------------------
    def _retire_slot(self, slot) -> None:
        """Clear a row's host state and free its slot. The device
        state row is left as-is — the next admission re-initializes
        it (zeros or an adopted checkpoint) before any dispatch reads
        it, and masked rows never update."""
        self._tok[slot.idx] = 0
        self._pos[slot.idx] = 0
        self._temp[slot.idx] = 0.0
        self.scheduler.retire(slot)

    def _finish(self, slot) -> None:
        batched_with = max(0, self.scheduler.busy_count() - 1)
        self._retire_slot(slot)
        tokens = self._emitted(slot)
        result = {"tokens": tokens,
                  "batched_with": batched_with,
                  "engine": "recurrent"}
        if slot.ticket.succeed(result):
            inc("veles_serving_retired_total")
            inc("veles_serving_tokens_total", len(tokens))
            self.retired += 1

    def _abort_active(self, reason: str, code: int = 500,
                      retry_after: Optional[float] = None,
                      count_shed: bool = True) -> None:
        answered = set()
        for slot in self.scheduler.active():
            if slot.mode in _STEP_MODES and (
                    slot.tokens or slot.req.get("_qos_prefix")):
                slot.ticket.set_progress(self._emitted(slot))
            self._retire_slot(slot)
            if id(slot.ticket) not in answered:
                answered.add(id(slot.ticket))
                first = slot.ticket.fail(reason, code=code,
                                         retry_after=retry_after)
                if count_shed and first:
                    inc("veles_shed_requests_total")

    # -- drain-by-handoff ------------------------------------------------------
    def handoff(self, reason: str = "server draining; request handed "
                                    "off with resume progress",
                timeout: float = 30.0) -> int:
        """Hand every in-flight request back with its emitted-token
        prefix at the NEXT step boundary — same contract (and same
        ``serve.handoff`` fault point) as the paged engine's."""
        done = threading.Event()
        box = {"count": 0}
        with self.scheduler.cv:
            if self._closing or self._thread is None:
                return 0
            self._handoff = (reason, done, box)
            self.scheduler.cv.notify_all()
        if not done.wait(timeout):
            self.warning("%s: handoff timed out after %.1fs (tick "
                         "thread wedged?); the drain proceeds to the "
                         "abort path", self.name, timeout)
        return box["count"]

    def _do_handoff(self, reason: str) -> int:
        handed = 0
        answered = set()
        for slot in self.scheduler.active():
            ticket = slot.ticket
            if id(ticket) not in answered:
                answered.add(id(ticket))
                snapshot_ok = True
                try:
                    fire_fault("serve.handoff")
                except FaultInjected as e:
                    snapshot_ok = False
                    self.warning(
                        "%s: progress snapshot failed mid-drain for "
                        "%s (%s) — handing off without resume",
                        self.name, ticket.request_id, e)
                if snapshot_ok and slot.mode in _STEP_MODES:
                    ticket.set_progress(self._emitted(slot))
                if ticket.fail(reason, code=503, retry_after=1.0,
                               outcome="handoff"):
                    if ticket.progress:
                        handed += 1
                        inc("veles_handoff_requests_total")
                    else:
                        inc("veles_shed_requests_total")
            self._retire_slot(slot)
        shed = self.scheduler.drain(reason, code=503, retry_after=1.0)
        if shed:
            inc("veles_shed_requests_total", shed)
        return handed

    # -- jitted programs -------------------------------------------------------
    def _program(self, kind: str):
        key = (kind, None)
        prog = self._progs.get(key)
        if prog is None:
            builders = {"scan": self._build_scan_chunk,
                        "step": self._build_decode}
            prog = self._progs[key] = self._instrument_live(
                builders[kind]())
        return prog

    def _instrument_live(self, jitted):
        """Identical wrapper to the paged engine's: one dispatch
        counter per call, one explicit lower+compile on the first —
        ``veles_serving_compile_seconds_total`` brackets ONLY the
        trace+compile the AOT artifact path exists to delete."""
        box: Dict[str, object] = {}

        def dispatch(*args):
            inc("veles_decode_dispatches_total")
            exe = box.get("exe")
            if exe is None:
                try:
                    t0 = time.time()
                    exe = jitted.lower(*args).compile()
                except AttributeError:      # non-pjit backends
                    exe = jitted
                else:
                    self.compiled_live += 1
                    inc("veles_compiles_total")
                    inc("veles_serving_compile_seconds_total",
                        time.time() - t0)
                box["exe"] = exe
            return exe(*args)

        dispatch._jitted = jitted
        dispatch.compiled = lambda: box.get("exe")
        return dispatch

    # -- AOT artifact (export/serve_artifact.py) ------------------------------
    def stack_signature(self) -> Dict:
        """Geometry the exported recurrent programs are shape-
        committed to: the abstract params spec, every block's state
        leaf shapes at ``max_slots`` rows, and the lane knobs the two
        programs bake in. Same refuse-on-mismatch contract as the
        paged engine's signature."""
        import jax

        def spec(tree):
            return jax.tree_util.tree_map(
                lambda a: [list(a.shape), str(a.dtype)], tree)

        params = params_of(self.wf)
        states = []
        for blk in self.stack["blocks"]:
            states.append(
                {k: list(shape) for k, shape
                 in sorted(blk.state_shapes(self.max_slots).items())})
        return {
            "kind": "recurrent",
            "params": spec(params),
            "states": states,
            "pool_dtype": str(
                params[self.stack["stem"].name]["table"].dtype),
            "max_slots": self.max_slots,
            "max_context": self.max_context,
            "decode_block": self.decode_block,
            "page_size": self.page_size,
            "state_cache": self.state_cache is not None,
        }

    def _load_artifact(self) -> bool:
        from ..export.serve_artifact import load_serve_programs
        try:
            fire_fault("artifact.load")
            programs = load_serve_programs(self.artifact,
                                           self.stack_signature())
        except Exception as e:      # noqa: BLE001 — degrade, don't die
            inc("veles_artifact_load_failures_total")
            self.warning(
                "%s: serve-artifact %s unusable (%s: %s); serving via "
                "live jit", self.name, self.artifact,
                type(e).__name__, e)
            return False
        from ..nn.sampling import _count_decode_dispatches
        for key, call in programs.items():
            self._progs[key] = _count_decode_dispatches(call)
        self.artifact_mode = True
        inc("veles_artifact_loads_total")
        self.info("%s: AOT artifact loaded from %s (%d programs; zero "
                  "jit compiles on the serving path)", self.name,
                  self.artifact, len(programs))
        return True

    # -- program builders ------------------------------------------------------
    def _build_scan_chunk(self):
        """THE prefill program: one ``page_size``-token chunk of ONE
        slot's prompt — slice the slot's state rows, ``lax.scan`` the
        shared step bodies over the chunk (positions past ``n_real``
        length-masked so padding never perturbs the carried state),
        write the rows back, and (final chunk only) sample the first
        token with the paged prefill's exact key convention. Also
        returns the slot's post-chunk state rows for host-side
        checkpointing — full-chunk boundaries ARE the block
        boundaries the StateCache indexes."""
        import jax
        import jax.numpy as jnp
        from ..ops import matmul_precision
        stack = self.stack
        stem, blocks, head = stack["stem"], stack["blocks"], \
            stack["head"]
        prec = matmul_precision()

        @functools.partial(jax.jit, donate_argnums=(7, 8))
        def scan_chunk(params, ids, n_real, slot, temp, seed_key,
                       final, keys, states):
            x = _embed_prompt(stem, None, params, ids[None])  # (1,C,D)
            new_states = []
            rows = []
            for blk, st in zip(blocks, states):
                st_row = jax.tree_util.tree_map(
                    lambda leaf: jax.lax.dynamic_slice(
                        leaf, (slot,) + (0,) * (leaf.ndim - 1),
                        (1,) + leaf.shape[1:]), st)
                x, st_row = blk.scan_state(params[blk.name], x,
                                           st_row, length=n_real)
                rows.append(st_row)
                new_states.append(jax.tree_util.tree_map(
                    lambda leaf, row_leaf: jax.lax.dynamic_update_slice(
                        leaf, row_leaf,
                        (slot,) + (0,) * (leaf.ndim - 1)),
                    st, st_row))
            x_last = jnp.take(x[0], n_real - 1, axis=0, mode="clip")
            logits = _head_logits(head, params, x_last, prec)
            k2 = jax.random.split(seed_key)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            samp = jax.random.categorical(
                k2[1], logits / jnp.maximum(temp, _TEMP_EPS)
            ).astype(jnp.int32)
            first = jnp.where(temp > 0, samp, greedy)
            # the key row advances only at the FINAL chunk — the one
            # that actually sampled (same gate as the paged chunk
            # program)
            upd = jax.lax.dynamic_update_slice(keys, k2[0][None],
                                               (slot, 0))
            keys = jnp.where(final > 0, upd, keys)
            return first, keys, tuple(new_states), tuple(rows)

        return scan_chunk

    def _build_decode(self):
        """THE decode step: ``decode_block`` scan iterations of the
        SAME per-token step bodies the prefill scanned — one fixed
        shape over all ``max_slots`` rows, compiled exactly once.
        Masked-out rows keep their state BIT-UNTOUCHED (``mask_keep``
        per leaf) and their key stream unadvanced, so a row's tokens
        are a pure function of its request whatever strangers share
        the pool — the paged lane's id-exactness contract, kept."""
        import jax
        import jax.numpy as jnp
        from ..ops import matmul_precision
        stack = self.stack
        stem, blocks, head = stack["stem"], stack["blocks"], \
            stack["head"]
        prec = matmul_precision()

        @functools.partial(jax.jit, donate_argnums=(4, 5))
        def step(params, tok, temp, mask, keys, states):

            def body(carry, _):
                tok, keys, states = carry
                x = jnp.take(params[stem.name]["table"],
                             tok.astype(jnp.int32), axis=0,
                             mode="clip")                 # (S, D)
                new_states = []
                for blk, st in zip(blocks, states):
                    x, st2 = blk.step_state(params[blk.name], x, st)
                    st2 = jax.tree_util.tree_map(
                        lambda new, old: mask_keep(mask > 0, new,
                                                   old), st2, st)
                    new_states.append(st2)
                logits = _head_logits(head, params, x, prec)  # (S, V)
                keys2, subs = _split_rows(keys)
                keys = jnp.where(mask[:, None] > 0, keys2, keys)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                samp = jax.vmap(jax.random.categorical)(
                    subs,
                    logits / jnp.maximum(temp, _TEMP_EPS)[:, None]
                ).astype(jnp.int32)
                nxt = jnp.where(temp > 0, samp, greedy)
                nxt = jnp.where(mask > 0, nxt, tok)
                return (nxt, keys, tuple(new_states)), nxt

            (tok, keys, states), toks = jax.lax.scan(
                body, (tok, keys, states), None,
                length=self.decode_block)
            return toks, keys, states

        return step


def generate_recurrent(wf, prompt, n_new, temperature: float = 0.0,
                       seed: int = 0, eos_id=None,
                       mode: str = "greedy") -> List[int]:
    """Solo-decode oracle for the O(1) lane: serve ONE request through
    a private single-slot :class:`RecurrentEngine` and return its
    tokens. Because every program is fixed-shape and every slot's
    noise derives purely from its seed, a pooled request's tokens must
    equal this — the id-exactness bar the o1 serving tests hold the
    shared pool to."""
    from .engine import make_request
    eng = RecurrentEngine(
        wf, max_slots=1,
        max_context=max(16, len(list(prompt)) + int(n_new)),
        name="o1_solo")
    eng.start()
    try:
        return eng.serve([make_request(
            list(prompt), int(n_new), temperature=float(temperature),
            seed=int(seed), eos_id=eos_id, mode=mode)])[0]
    finally:
        eng.stop()
