"""Iteration-level scheduler for the continuous-batching engine.

Pure host-side bookkeeping (no jax): a bounded request queue, the
``max_slots`` slot table, the page-pool admission ledger, prefill-
bucket selection and deadline enforcement. The engine calls
:meth:`SlotScheduler.take_admissions` at every step boundary — queued
requests move into free slots the moment one opens AND the page pool
can hold their prompt, so the chip never idles while the queue is
non-empty, and a ticket older than its deadline is answered 503 +
Retry-After instead of silently sitting in the queue.

Since the paged-pool rework, admission is on PAGE availability, not
raw slot count: a request is admitted when a slot (``beam_width``
slots for ``mode=beam``) is free and the allocator can RESERVE its
own worst case — ``ceil(max(bucket, prompt + n_new [+ gamma + 1]) /
page_size)`` pages per row, never ``max_context`` — so short
requests pack many-to-a-pool and a row cannot hit exhaustion
mid-decode in normal operation. Decode-time growth (:meth:`grow`) is
the engine's accounting safety net; a row it cannot cover (or an
injected ``serve.page_alloc`` fault) is shed with 503 + Retry-After
while everyone else keeps decoding.
"""

from __future__ import annotations

import itertools
import os
import queue as _queue_mod
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ..resilience.faults import FaultInjected, fire as fire_fault
from ..telemetry.counters import inc, observe
from .overload import dynamic_retry_after, request_priority
from .pages import PagePool, pages_for

_request_ids = itertools.count(1)

#: THE decode modes whose emitted-token prefix a failover retry can
#: resume: their per-slot PRNG stream advances exactly one split per
#: emitted token, so a resumed prefill re-enters it mid-decode.
#: Single source of truth — the engine's step plane, the router's
#: fold logic and Ticket.set_progress all import this tuple.
RESUME_MODES = ("greedy", "sample")


def new_request_id() -> str:
    """Process-unique serving request id, assigned at API admission
    and threaded through the whole Ticket lifecycle (span tags,
    flight-recorder events, the response body). The pid prefix keeps
    ids distinct across a fleet of engine replicas whose /metrics a
    ``veles-tpu metrics aggregate`` merges."""
    return "req-%d-%d" % (os.getpid(), next(_request_ids))


def new_trace_id() -> str:
    """Process-unique fleet trace id — the request_id family's
    naming (pid prefix, shared counter) applied to the CROSS-process
    correlation key: the fleet router mints one per accepted request
    and forwards it with every attempt, so however many replicas (and
    retries) serve the request, every span, flight-recorder event and
    journal record of its story carries the same ``trace_id`` — what
    ``veles-tpu trace fleet --request ID`` assembles a timeline
    from. A request that never crosses a router gets its own
    request_id as its trace_id (Ticket default), so single-replica
    traces need no router to exist."""
    return "trace-%d-%d" % (os.getpid(), next(_request_ids))


def request_tracing_enabled() -> bool:
    """THE per-request tracing switch (``root.common.trace.requests``,
    default on). Gates only the HOST-SIDE span/flight emission at
    ticket terminal — never device work, so dispatch counts are
    bit-identical on and off (locked by tests/test_request_tracing.py).
    The SLO histograms record regardless: p99 TTFT must be answerable
    on a fleet that runs with tracing off."""
    try:
        from ..config import root
        return bool(root.common.trace.get("requests", True))
    except Exception:        # noqa: BLE001 — config not importable
        return True


class Ticket:
    """One request's rendezvous between an HTTP handler thread and a
    serving worker (the generation twin of ``restful_api._Ticket``).
    The worker fills ``result`` (or ``error`` + ``code``) and sets
    ``event``; ``retry_after`` asks the handler to attach a
    ``Retry-After`` header (503 shed/expiry answers); ``deadline`` is
    the absolute wall time after which the request must no longer be
    served from the queue.

    The ticket is also the request-plane SLO record: it carries a
    process-unique ``request_id`` and host-side lifecycle timestamps
    (``enqueued`` → ``admitted`` → ``prefill_done`` → ``first_token``
    → terminal), stamped by the planes at step boundaries only.
    Each wait is observed WHERE IT ENDS, once a request: the queue
    wait at the first :meth:`mark_admitted`, TTFT and the prefill wait
    at the first :meth:`mark_first_token`, the first token's way out
    at :meth:`mark_first_write` (``telemetry/counters.py`` HISTOGRAMS),
    so a scrape, an alert and the overload governor see a slow first
    token when it comes and not a generation later. Their durations
    come from ``perf_counter`` stamps taken beside the epoch ones
    (which stay: the lifecycle spans' ``ts`` and the deadline's clock).
    :meth:`succeed`/:meth:`fail` are EXACTLY-ONCE: the first terminal
    call records what ends there (TPOT, end-to-end, the queue wait of
    a ticket that died in the queue), emits the request's lifecycle
    spans tagged with its id, and notes a terminal flight-recorder
    event; any later call is a no-op returning False — a ticket swept
    by both the tick path and the failure path can never
    double-count."""

    __slots__ = ("event", "result", "error", "code", "retry_after",
                 "deadline", "enqueued", "request_id", "trace_id",
                 "attempt", "mode",
                 "admitted", "prefill_done", "first_token",
                 "_p_enqueued", "_p_admitted", "_p_first_token",
                 "n_tokens", "outcome", "progress", "_terminal_lock",
                 "stream", "_stream_q")

    def __init__(self, deadline: Optional[float] = None,
                 request_id: Optional[str] = None,
                 mode: str = "greedy",
                 trace_id: Optional[str] = None,
                 attempt: int = 1,
                 stream: bool = False,
                 arrived: Optional[float] = None) -> None:
        self._terminal_lock = threading.Lock()
        self.event = threading.Event()
        self.result = None
        self.error: Optional[str] = None
        self.code: int = 500
        self.retry_after: Optional[float] = None
        self.deadline = deadline
        # ``arrived``: the handler's ``perf_counter()`` when it had the
        # request's headers. Reading and parsing a long prompt's body
        # under the interpreter lock the tick thread holds is part of
        # what the client waits: the request's clock starts there
        now = time.perf_counter()
        self._p_enqueued = now if arrived is None else arrived
        self.enqueued = time.time() - (now - self._p_enqueued)
        self.request_id = request_id or new_request_id()
        #: fleet-wide correlation key: adopted from the router's body
        #: when one arrives, else the request's own id — every
        #: lifecycle span/flight event carries it, so a single
        #: replica's trace joins a fleet trace seamlessly
        self.trace_id = trace_id or self.request_id
        #: which routing attempt this ticket serves (1-based; the
        #: router numbers retries, a direct request is attempt 1)
        self.attempt = max(1, int(attempt or 1))
        self.mode = str(mode)
        self.admitted: Optional[float] = None
        self.prefill_done: Optional[float] = None
        self.first_token: Optional[float] = None
        self._p_admitted: Optional[float] = None
        #: cleared by :meth:`mark_first_write`: the flag that makes
        #: the first token's way out one observation a request
        self._p_first_token: Optional[float] = None
        self.n_tokens = 0
        self.outcome: Optional[str] = None
        #: tokens emitted before a mid-decode failure/handoff — the
        #: token-level resume record a failover retry continues from
        self.progress: Optional[List[int]] = None
        #: token streaming (``stream=true`` requests): the serving
        #: plane pushes emitted tokens at step boundaries and the HTTP
        #: handler drains them onto the wire as SSE events; terminal
        #: settles enqueue a ``None`` sentinel so the drain loop ends
        #: the moment the answer exists
        self.stream = bool(stream)
        self._stream_q: Optional["_queue_mod.SimpleQueue"] = (
            _queue_mod.SimpleQueue() if self.stream else None)

    # -- lifecycle stamps (host-side, step boundaries only) ------------------
    def mark_admitted(self) -> None:
        """Stamp queue exit (slot admission / window-batch pop) and
        observe the queue wait that ends here; first stamp wins — a
        beam group's sibling slots share one ticket, and a preempted
        and requeued one is neither stamped nor observed again."""
        if self.admitted is not None:
            return
        self.admitted = time.time()
        self._p_admitted = time.perf_counter()
        observe("veles_serving_queue_wait_seconds",
                self._p_admitted - self._p_enqueued)
        if request_tracing_enabled():
            try:
                from ..telemetry.recorder import flight
                flight.note("request", request_id=self.request_id,
                            trace_id=self.trace_id,
                            attempt=self.attempt,
                            phase="admitted", mode=self.mode)
            except Exception:       # noqa: BLE001 — observers only
                pass

    def mark_prefill_done(self) -> None:
        if self.prefill_done is None:
            self.prefill_done = time.time()

    def mark_first_token(self) -> None:
        """Stamp the host's read of the first token and observe the
        two waits that end here: TTFT (from arrival) and the prefill
        wait (from admission: the step in flight that the prefill
        queues behind, the prefills ahead of it in the same tick, its
        own program). First stamp wins."""
        if self.first_token is not None:
            return
        self.first_token = time.time()
        self._p_first_token = now = time.perf_counter()
        observe("veles_serving_ttft_seconds", now - self._p_enqueued)
        if self._p_admitted is not None:
            observe("veles_serving_prefill_wait_seconds",
                    now - self._p_admitted)

    def mark_first_write(self) -> None:
        """The handler thread has written the first SSE event that
        carries a token: observe the first token's way out, from the
        host's read of it (:meth:`mark_first_token`) through the push,
        the handler's wake under the interpreter lock and the write.
        Once a request (the stamp is the flag); a ticket whose tokens
        burst at completion (the window plane) has none to clear."""
        p0, self._p_first_token = self._p_first_token, None
        if p0 is not None:
            observe("veles_serving_first_write_seconds",
                    time.perf_counter() - p0)

    def set_progress(self, tokens) -> None:
        """Attach the emitted-token prefix BEFORE a terminal
        :meth:`fail` — the failure answer then carries
        ``{resume: {tokens, tokens_done}}`` so a router retry can
        continue the decode from ``tokens_done`` instead of token 0.
        Only the plain decode modes resume (greedy/sample own a
        per-slot PRNG stream a resumed prefill can re-enter exactly;
        speculative/beam and the window plane retry from scratch), so
        other modes never attach progress. No-op after terminal."""
        if self.mode not in RESUME_MODES:
            return
        if not self.event.is_set():
            self.progress = [int(t) for t in tokens]

    # -- token streaming ------------------------------------------------------
    def push_tokens(self, tokens) -> bool:
        """Hand freshly emitted tokens to the streaming drain loop (a
        no-op for buffered tickets); True when an event was queued.
        Stamps first-token time: the
        moment a token enters this queue it is one queue hop from the
        client's socket, so the TTFT histogram now measures a real
        client-visible first token — not an internal prefill sync a
        buffered response would sit on for the whole generation."""
        if self._stream_q is None:
            return False
        toks = [int(t) for t in tokens]
        if not toks:
            return False
        self.mark_first_token()
        self._stream_q.put(toks)
        return True

    def next_stream_item(self, timeout: float):
        """Blocking drain step for the HTTP streaming handler: a token
        list, the ``None`` terminal sentinel, or raises
        ``queue.Empty`` on timeout."""
        assert self._stream_q is not None
        return self._stream_q.get(timeout=timeout)

    # -- terminal (exactly once) ---------------------------------------------
    def fail(self, error: str, code: int = 500,
             retry_after: Optional[float] = None,
             outcome: Optional[str] = None) -> bool:
        """Answer with an error; True only on the FIRST terminal call
        (callers count shed/expiry on that True, so a ticket seen by
        two sweeps is still counted once). The terminal transition is
        LOCKED, not a bare is_set() check: a wedged tick thread's late
        sweep racing a stop()-side abort must not double-record the
        histograms or let both callers count the shed."""
        with self._terminal_lock:
            if self.event.is_set():
                return False
            self.error = error
            self.code = code
            self.retry_after = retry_after
            self._account(outcome
                          or ("shed" if code == 503 else "error"))
            self.event.set()
            if self._stream_q is not None:
                self._stream_q.put(None)
        return True

    def succeed(self, result) -> bool:
        """Answer with a result; True only on the first terminal call.
        Dict results are stamped with the ``request_id`` so both
        decode planes answer with the id the trace/flight events
        carry."""
        with self._terminal_lock:
            if self.event.is_set():
                return False
            if isinstance(result, dict):
                result.setdefault("request_id", self.request_id)
                self.n_tokens = len(result.get("tokens") or ())
            self.result = result
            self._account("retired")
            self.event.set()
            if self._stream_q is not None:
                self._stream_q.put(None)
        return True

    def error_payload(self) -> Dict:
        """THE failure response body both HTTP planes send for a
        terminal-failed ticket: the error plus this request's id (and
        the shed's ``retry_after`` hint when one was set), so a fleet
        router retrying the request can correlate a shed/expiry with
        the attempt it belongs to — success bodies already carry the
        id via :meth:`succeed`."""
        body: Dict = {"error": self.error,
                      "request_id": self.request_id}
        if self.retry_after is not None:
            # dynamic backoff (docs/services.md "Overload & QoS"):
            # with a QoS pressure provider registered, the hint
            # scales with live queue depth so storming clients back
            # off proportionally; with QoS off, exactly the static
            # hint the terminal call set
            body["retry_after"] = self.retry_after_hint()
        if self.progress is not None:
            # the token-level resume record: this ATTEMPT's emitted
            # tokens (a resumed attempt reports only its own new
            # tokens — the router accumulates prefixes across
            # attempts), continuing the same per-slot PRNG stream
            body["resume"] = {"tokens": list(self.progress),
                              "tokens_done": len(self.progress)}
        return body

    def retry_after_hint(self) -> Optional[float]:
        """The ``Retry-After`` value this ticket's failure answer
        should carry — the static hint :meth:`fail` set, scaled by
        live queue pressure when a QoS pressure provider is
        registered (serving/overload.py)."""
        return dynamic_retry_after(self.retry_after)

    def _account(self, outcome: str) -> None:
        """Terminal SLO accounting — the histograms of what ends here
        always (TPOT, end-to-end, a queue wait that no admission
        ended), span/flight emission under the tracing switch. Never
        raises: a broken
        observer must not lose the request's answer. Deliberately
        runs INSIDE the terminal lock, before ``event.set()``:
        answered must imply accounted (the tests read the
        histograms the moment ``serve()`` returns),
        and the cost is bounded — once per REQUEST at a step
        boundary (≤ 4 small JSONL lines when a trace sink is open),
        never on the per-token path."""
        now = time.time()
        self.outcome = outcome
        try:
            if self.admitted is None and outcome in ("expired", "shed"):
                # died in the queue: its whole life WAS queue wait (an
                # admitted ticket's was observed at its admission)
                observe("veles_serving_queue_wait_seconds",
                        time.perf_counter() - self._p_enqueued)
            if self.first_token is not None \
                    and outcome == "retired" and self.n_tokens > 1:
                observe("veles_serving_tpot_seconds",
                        max(0.0, now - self.first_token)
                        / (self.n_tokens - 1))
            if outcome == "retired":
                observe("veles_serving_e2e_seconds",
                        max(0.0, now - self.enqueued))
            if not request_tracing_enabled():
                return
            from ..telemetry.recorder import flight
            from ..telemetry.spans import emit
            rid = self.request_id
            # every lifecycle span carries the fleet correlation pair
            # — trace_id + attempt — so a cross-process assembly
            # (veles-tpu trace fleet) stitches this replica's leg of
            # the request into the router's route.attempt bracket
            tags = {"request_id": rid, "trace_id": self.trace_id,
                    "attempt": self.attempt}
            if self.admitted is not None:
                emit("request.queue", self.enqueued,
                     self.admitted - self.enqueued, **tags)
                if self.prefill_done is not None:
                    emit("request.prefill", self.admitted,
                         self.prefill_done - self.admitted, **tags)
            if self.first_token is not None:
                emit("request.decode", self.first_token,
                     now - self.first_token, tokens=self.n_tokens,
                     **tags)
            emit("request", self.enqueued, now - self.enqueued,
                 outcome=outcome, mode=self.mode,
                 tokens=self.n_tokens, **tags)
            flight.note("request", request_id=rid,
                        trace_id=self.trace_id, attempt=self.attempt,
                        phase="done",
                        outcome=outcome, mode=self.mode,
                        tokens=self.n_tokens,
                        dur=round(now - self.enqueued, 6))
        except Exception:       # noqa: BLE001 — observability only
            pass


def split_expired(pairs: List[Tuple[Dict, Ticket]],
                  now: Optional[float] = None
                  ) -> Tuple[List[Tuple[Dict, Ticket]], List[Ticket]]:
    """Partition ``(req, ticket)`` pairs into (still live, expired
    tickets) by deadline — the check every dequeue point applies."""
    now = time.time() if now is None else now
    live, expired = [], []
    for req, ticket in pairs:
        if ticket.deadline is not None and now > ticket.deadline:
            expired.append(ticket)
        else:
            live.append((req, ticket))
    return live, expired


def shed_expired(tickets: List[Ticket]) -> None:
    """THE one deadline answer both decode planes give: 503 +
    Retry-After, counted — a ticket never rots in a queue past its
    useful life. Counting keys off :meth:`Ticket.fail`'s first-
    terminal True, so a ticket swept by BOTH the tick path and the
    failure path (a tick dying between ``take_admissions`` and its
    shed, then the loop's ``expire_queued`` sweep) still counts its
    expiry — and its queue-wait histogram sample — exactly once."""
    for ticket in tickets:
        if ticket.fail("request expired in serving queue", code=503,
                       retry_after=1.0, outcome="expired"):
            inc("veles_serving_expired_total")
            inc("veles_shed_requests_total")


class BeamGroup:
    """Host state shared by the ``beam_width`` hypothesis slots of one
    beam request. The engine fills the search state (current tokens,
    scores, finished flags) after the prefill expansion and advances
    it one top-k step per tick; the group retires as a unit."""

    __slots__ = ("req", "ticket", "slots", "live", "cur", "scores",
                 "finished", "toks", "step", "t_p")

    def __init__(self, req: Dict, ticket: Ticket) -> None:
        self.req = req
        self.ticket = ticket
        self.slots: List["Slot"] = []
        self.live = 0               # hypothesis slots not yet retired
        self.cur = None             # (W,) int32 current tokens
        self.scores = None          # (W,) f64 cumulative log-probs
        self.finished = None        # (W,) bool — eos frozen
        self.toks = None            # (W, n_new) emitted token matrix
        self.step = 0               # decoded positions past the first
        self.t_p = len(req["prompt"])


class Slot:
    """Host state of one occupied KV-cache row. ``pages`` are the page
    ids this row holds (freed at retirement); ``mode`` selects which
    fixed-shape program advances it (``greedy``/``sample`` ride the
    decode step, ``speculative`` the draft/verify round, ``beam`` the
    group top-k step); ``group`` links beam hypothesis rows."""

    __slots__ = ("idx", "req", "ticket", "t_p", "bucket", "tokens",
                 "n_new", "eos_id", "temperature", "mode", "pages",
                 "group", "rounds", "acc", "prefilled", "shared")

    def __init__(self, idx: int, req: Dict, ticket: Ticket,
                 bucket: int, pages: Optional[List[int]] = None,
                 group: Optional[BeamGroup] = None) -> None:
        self.idx = idx
        self.req = req
        self.ticket = ticket
        self.t_p = len(req["prompt"])
        self.bucket = bucket
        self.tokens: List[int] = []
        self.n_new = int(req["n_new"])
        self.eos_id = req.get("eos_id")
        self.temperature = float(req.get("temperature", 0.0))
        self.mode = str(req.get("mode", "greedy"))
        self.pages = list(pages or [])
        self.group = group
        self.rounds = 0     # speculative: draft/verify rounds run
        self.acc = 0        # speculative: total accepted draft tokens
        #: chunked prefill cursor: positions already written, or None
        #: once the prompt is fully prefilled (monolithic prefills
        #: never set it) — rows with a cursor are excluded from the
        #: decode step until their final chunk lands
        self.prefilled: Optional[int] = None
        #: leading page-table entries adopted READ-ONLY from the
        #: prefix cache — the decode step's write-back masks them to
        #: the sink, so a writer can never mutate a shared page
        self.shared = 0

    def record(self, token: int) -> bool:
        """Append one emitted token; True when the row is finished
        (its own ``n_new`` reached, or ``eos_id`` emitted — the moment
        continuous batching frees the slot for the next request,
        instead of riding out the longest co-tenant)."""
        self.tokens.append(int(token))
        if self.eos_id is not None and int(token) == self.eos_id:
            return True
        return len(self.tokens) >= self.n_new


class SlotScheduler:
    """Bounded queue + slot table + page ledger. All methods are
    thread-safe; the engine's worker waits on :attr:`cv` and the HTTP
    threads notify it on :meth:`push`. ``page_pool=None`` keeps the
    legacy slots-only admission (unit tests of the queue geometry)."""

    def __init__(self, max_slots: int, buckets: Tuple[int, ...],
                 max_context: int,
                 page_pool: Optional[PagePool] = None,
                 beam_width: int = 4, spec_gamma: int = 4,
                 slot_kind: str = "paged") -> None:
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.max_slots = int(max_slots)
        #: what a slot's per-request memory IS: "paged" rows hold a
        #: page table over the KV pool, "state" rows (the O(1) lane,
        #: serving/recurrent.py) hold a fixed recurrent-state tensor
        #: and never touch the page ledger. Stats/metrics key off this
        #: so a pageless replica's rows never enter the fleet's
        #: veles_serving_pages_* math
        self.slot_kind = str(slot_kind)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.max_context = int(max_context)
        if self.buckets[-1] > self.max_context:
            raise ValueError(
                "largest prefill bucket %d exceeds max_context %d"
                % (self.buckets[-1], self.max_context))
        self.page_pool = page_pool
        self.beam_width = max(1, int(beam_width))
        #: the engine's fixed speculation round width — the default
        #: for requests that omit ``gamma``, so page reservation uses
        #: the round size the spec program will actually run
        self.spec_gamma = max(1, int(spec_gamma))
        #: concurrent beam groups the fixed-shape beam program holds
        self.beam_groups = self.max_slots // self.beam_width
        self._beams_active = 0
        self.cv = threading.Condition()
        self._queue: deque = deque()
        self._free: List[int] = list(range(self.max_slots))
        self.slots: List[Optional[Slot]] = [None] * self.max_slots
        #: QoS switch (set by the owning engine from
        #: ``root.common.serving.qos``): True makes admission
        #: priority-aware — interactive requests jump queued batch
        #: work (see :meth:`_promote_interactive_locked`). False (the
        #: default) keeps strict FIFO, bit-identical to the pre-QoS
        #: scheduler.
        self.qos = False

    # -- admission geometry --------------------------------------------------
    def bucket_for(self, t_p: int) -> Optional[int]:
        """Smallest prefill bucket holding a ``t_p``-token prompt (the
        jit cache stays bounded by len(buckets) prefill programs plus
        the fixed decode/round/beam steps, not by distinct prompt
        lengths)."""
        for b in self.buckets:
            if t_p <= b:
                return b
        return None

    def _worst_positions(self, t_p: int, n_new: int, mode: str,
                         gamma: int) -> int:
        """Cache positions a request can ever touch — what the page
        ledger must be able to hold for it to complete."""
        if mode == "speculative":
            return t_p + n_new + int(gamma) + 1
        if mode == "beam":
            return t_p + max(n_new - 1, 1)
        return t_p + n_new

    def reject_reason(self, t_p: int, n_new: int, mode: str = "greedy",
                      gamma: Optional[int] = None) -> Optional[str]:
        """None when the request fits the slot pool; otherwise why not
        (the caller falls back to the window-coalescing path, which
        compiles per exact shape and has no context ceiling)."""
        bucket = self.bucket_for(t_p)
        if bucket is None:
            return ("prompt length %d exceeds the largest serving "
                    "bucket %d" % (t_p, self.buckets[-1]))
        worst = self._worst_positions(
            t_p, n_new, mode,
            self.spec_gamma if gamma is None else gamma)
        if worst > self.max_context:
            return ("prompt %d + generation window %d exceeds "
                    "max_context %d (mode=%s)"
                    % (t_p, worst - t_p, self.max_context, mode))
        width = self.beam_width if mode == "beam" else 1
        if width > self.max_slots:
            return ("beam width %d exceeds the pool's %d slots"
                    % (width, self.max_slots))
        if self.page_pool is not None:
            need = width * pages_for(max(bucket, worst),
                                     self.page_pool.page_size)
            if need > self.page_pool.pages:
                return ("request needs %d pages at worst, the pool "
                        "holds %d" % (need, self.page_pool.pages))
        return None

    # -- queue ----------------------------------------------------------------
    def push(self, req: Dict, ticket: Ticket,
             max_queue: Optional[int] = None) -> bool:
        """Enqueue; False when the bound is hit (caller sheds 503)."""
        with self.cv:
            if max_queue is not None and len(self._queue) >= max_queue:
                return False
            self._queue.append((req, ticket))
            self.cv.notify_all()
        return True

    def queue_depth(self) -> int:
        with self.cv:
            return len(self._queue)

    def busy_count(self) -> int:
        with self.cv:
            return self.max_slots - len(self._free)

    def expire_queued(self, now: Optional[float] = None) -> List[Ticket]:
        """Remove every expired ticket from the queue (any position) —
        the failure-path sweep: when ticks cannot run, deadlines must
        still be honored instead of callers hanging to their full
        timeout."""
        with self.cv:
            live, expired = split_expired(list(self._queue), now)
            self._queue = deque(live)
        return expired

    # -- page ledger -----------------------------------------------------------
    #
    # SHARD-AGNOSTIC by construction: every count in this ledger —
    # pages_for(...) at validate/admission, grow()'s shortfall, the
    # pool's free list — is in LOGICAL pages (page_size positions of
    # one slot's cache). Under tensor-parallel serving the device
    # pool's kv-head axis shards over the ("model",) mesh
    # (pages.per_shard_kv_heads), which divides every page's BYTES
    # per chip but never its position count, so identical knobs admit
    # identical request mixes at tp=1 and tp=N — asserted by
    # tests/test_tp_serving.py's logical-gauge comparisons.
    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Allocation with the ``serve.page_alloc`` fault point armed —
        the injection surface for page-exhaustion chaos. Raises
        :class:`FaultInjected` on an injected fault (callers shed);
        returns None on real exhaustion (admission waits for
        retirements, growth sheds)."""
        if self.page_pool is None:
            return []
        fire_fault("serve.page_alloc")
        return self.page_pool.alloc(n)

    def grow(self, slot: Slot, positions: int) -> bool:
        """Extend ``slot``'s page list to cover ``positions`` cache
        rows. True when covered (possibly without allocating); False
        means exhaustion or an injected ``serve.page_alloc`` fault —
        the engine sheds the row with 503 + Retry-After and frees its
        pages while the rest of the pool keeps decoding."""
        if self.page_pool is None:
            return True
        need = pages_for(positions, self.page_pool.page_size) \
            - len(slot.pages)
        if need <= 0:
            return True
        try:
            got = self._alloc_pages(need)
        except FaultInjected:
            return False
        if got is None:
            return False
        slot.pages.extend(got)
        return True

    # -- step-boundary transitions -------------------------------------------
    def take_admissions(self, now: Optional[float] = None
                        ) -> Tuple[List[Slot], List[Ticket]]:
        """Move queued requests into free slots (FIFO), dropping
        expired tickets. Admission is on page availability: the head
        request waits (keeping FIFO order) while the allocator cannot
        hold its prompt; an injected ``serve.page_alloc`` fault sheds
        it 503 + Retry-After instead. ``mode=beam`` requests take
        ``beam_width`` slots (one per hypothesis) plus one page set
        per slot. Returns (newly filled slots — the engine prefills
        each, expired tickets — the engine answers 503)."""
        now = time.time() if now is None else now
        admissions: List[Slot] = []
        expired: List[Ticket] = []
        with self.cv:
            if self.qos and len(self._queue) > 1:
                self._promote_interactive_locked()
            while self._queue:
                req, ticket = self._queue[0]
                if ticket.deadline is not None and now > ticket.deadline:
                    self._queue.popleft()
                    expired.append(ticket)
                    continue
                mode = str(req.get("mode", "greedy"))
                width = self.beam_width if mode == "beam" else 1
                if len(self._free) < width:
                    break
                if mode == "beam" and (
                        self._beams_active >= max(1, self.beam_groups)):
                    break
                bucket = self.bucket_for(len(req["prompt"]))
                if bucket is None:
                    # a poisoned head (checked=True submit bypassing
                    # accepts(), or a raw push) must be answered and
                    # dropped, not crash-loop every tick pre-pop
                    self._queue.popleft()
                    ticket.fail("prompt length %d exceeds the largest "
                                "serving bucket %d"
                                % (len(req["prompt"]),
                                   self.buckets[-1]), code=400)
                    continue
                # reserve the request's OWN worst case (prompt +
                # its n_new, never max_context): admission cost is
                # the request's actual footprint, so short requests
                # pack many-to-a-pool, and a row can never hit page
                # exhaustion mid-decode — growth past this is the
                # accounting safety net, not the steady state
                worst = max(bucket, self._worst_positions(
                    len(req["prompt"]), int(req["n_new"]), mode,
                    int(req.get("gamma", self.spec_gamma))))
                per_row = (0 if self.page_pool is None else
                           pages_for(worst, self.page_pool.page_size))
                rows_pages: List[List[int]] = []
                shed = starved = False
                for _ in range(width):
                    try:
                        got = self._alloc_pages(per_row)
                    except FaultInjected as e:
                        self._queue.popleft()
                        for back in rows_pages:
                            self.page_pool.free(back)
                        if ticket.fail(
                                "serving page pool exhausted: %s" % e,
                                code=503, retry_after=1.0):
                            inc("veles_shed_requests_total")
                        shed = True
                        break
                    if got is None:
                        # real exhaustion: keep FIFO order and wait
                        # for retirements to free pages
                        for back in rows_pages:
                            self.page_pool.free(back)
                        starved = True
                        break
                    rows_pages.append(got)
                if shed:
                    continue
                if starved:
                    break
                self._queue.popleft()
                ticket.mark_admitted()
                group = (BeamGroup(req, ticket) if mode == "beam"
                         else None)
                for w in range(width):
                    idx = self._free.pop(0)
                    slot = Slot(idx, req, ticket, bucket,
                                pages=rows_pages[w] if rows_pages
                                else [], group=group)
                    self.slots[idx] = slot
                    if group is not None:
                        group.slots.append(slot)
                        group.live += 1
                    admissions.append(slot)
                if group is not None:
                    self._beams_active += 1
            # even with no admission, purge expired tickets from ANY
            # queue position — a dead ticket behind a live head must
            # not rot to its handler's silent 504 while the pool is
            # full
            live, exp = split_expired(list(self._queue), now)
            self._queue = deque(live)
            expired.extend(exp)
        return admissions, expired

    def _promote_interactive_locked(self) -> None:
        """QoS admission order (``self.qos`` on, under ``cv``): a
        stable two-lane reorder — interactive tickets move ahead of
        queued batch work, each class keeping its own FIFO order —
        after which the admission loop runs UNCHANGED, so the
        page-wait / beam-cap semantics are identical in both modes.
        Batch is deferred, never dropped: it admits the moment no
        interactive request is waiting. Counts how many batch
        requests an interactive arrival actually jumped."""
        q = list(self._queue)
        hot = [p for p in q if request_priority(p[0]) == "interactive"]
        cold = [p for p in q if request_priority(p[0]) != "interactive"]
        if not hot or not cold or q == hot + cold:
            return
        last_hot = max(i for i, p in enumerate(q)
                       if request_priority(p[0]) == "interactive")
        jumped = sum(1 for p in q[:last_hot]
                     if request_priority(p[0]) != "interactive")
        if jumped:
            inc("veles_qos_batch_deferrals_total", jumped)
        self._queue = deque(hot + cold)

    def retire(self, slot: Slot) -> None:
        """Free the row — the very next :meth:`take_admissions` can
        hand it (and its pages) to a queued request. Idempotent: a
        slot already retired (e.g. by a shutdown abort racing a wedged
        worker's late ``_finish``) is left alone, so an index can
        never enter the free list twice."""
        with self.cv:
            if self.slots[slot.idx] is not slot:
                return
            self.slots[slot.idx] = None
            self._free.append(slot.idx)
            self._free.sort()
            if self.page_pool is not None and slot.pages:
                self.page_pool.free(slot.pages)
                slot.pages = []
            if slot.group is not None:
                slot.group.live -= 1
                if slot.group.live == 0:
                    self._beams_active -= 1
            self.cv.notify_all()

    def active(self) -> List[Slot]:
        with self.cv:
            return [s for s in self.slots if s is not None]

    def active_beams(self) -> List[BeamGroup]:
        """Distinct live beam groups, ordered by their first slot."""
        with self.cv:
            seen: List[BeamGroup] = []
            for s in self.slots:
                if s is not None and s.group is not None \
                        and s.group not in seen:
                    seen.append(s.group)
            return seen

    def drain(self, reason: str, code: int = 503,
              retry_after: Optional[float] = 5.0) -> int:
        """Fail every queued ticket (shutdown / drain-by-handoff);
        returns the number of FIRST-terminal settles — a ticket some
        other sweep already answered is popped but never re-counted."""
        with self.cv:
            pending = list(self._queue)
            self._queue.clear()
        settled = 0
        for _req, ticket in pending:
            if ticket.fail(reason, code=code, retry_after=retry_after):
                settled += 1
        return settled
