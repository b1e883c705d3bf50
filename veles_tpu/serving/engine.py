"""Continuous-batching decode engine: a paged slot-pool KV cache
driven by a bounded set of fixed-shape jitted programs.

Replaces the window-coalescing serving model (one batched decode per
exact shape key, everyone rides to the longest member's ``n_new``)
with iteration-level scheduling over a PAGED KV cache (the
block-table formulation of PAPERS.md's "Compiler-First State Space
Duality and Portable O(1) Autoregressive Caching for Inference"):

- K/V live in a global pool of fixed-size PAGES (``page_size``
  positions each, a multiple of ``decode_block``); every slot owns a
  page-table row — an int32 index array — and the jitted programs
  gather a slot's logical ``max_context`` cache view through it.
  Pool HBM is ``pages x page_size``, NOT ``max_slots x max_context``:
  concurrency is bounded by pages actually reserved, so the same HBM
  sustains roughly ``max_context / mean(prompt + n_new)`` times more
  concurrent slots than the dense pool it replaces;
- admission RESERVES each request's own worst case —
  ``ceil(max(bucket, prompt + n_new [+ gamma + 1]) / page_size)``
  pages per row, never ``max_context`` — and frees them the moment
  the row retires. Reserving up front makes page exhaustion
  impossible mid-decode in normal operation (the head request waits,
  FIFO kept, when the allocator cannot hold it); per-tick growth
  (:meth:`SlotScheduler.grow` via ``_grow_or_shed``) is the
  accounting safety net, and a row it cannot cover — or an injected
  ``serve.page_alloc`` fault — is shed 503 + Retry-After while
  everyone else keeps decoding;
- the decode step's shapes do not follow the traffic — page tables
  are data, not shape — and its view is one of a short ladder of
  lengths (``view_ladder``: ``pages_per_slot`` and its half;
  ``_decode`` hands it, each tick, the table's leading columns for
  the shortest that holds every active row, since what lies beyond
  a row's position is masked to nought anyway); prefill
  pads prompts to a small set of length ``buckets``, so the
  greedy/sample plane holds ``len(buckets) + len(view_ladder)``
  programs, never one per prompt length;
- ALL decode modes ride the pool: ``speculative`` rows advance by
  on-device draft/verify rounds (a second fixed-shape program sharing
  the page tables; the draft model's K/V pages ride the same
  allocator) and ``beam`` requests occupy ``beam_width`` hypothesis
  rows advanced by a fixed-shape group top-k step whose cache reorder
  is a page-granular copy. Each mode adds a bounded constant to the
  program count (:meth:`ContinuousEngine.programs_bound`);
- each slot carries its own PRNG stream derived purely from the
  request's ``seed``, so a request's tokens are id-exact vs its solo
  decode whatever strangers share the batch — greedy, sampled,
  speculative and beam rows co-tenant in one pool without changing
  each other's answers;
- the plain decode step's tokens stay on the device as the next
  step's input, so the tick DISPATCHES STEP n+1 BEFORE IT READS STEP
  n's TOKENS (:meth:`ContinuousEngine._decode`): the host reckons
  positions, pages, the view's rung and the mask for a step from what
  it can know beforehand, and reads, records and emits the step
  before while the chip runs this one; at most one step is in flight
  beyond the one being read. A row that ends on its ``eos_id`` costs
  one dropped row-step; a hand-off, an abort, a preemption, a shed, a
  change of weights, a speculative round or a beam step on the same
  pool, ``stop()`` and the idle wait read the step in flight first
  (:meth:`ContinuousEngine._drain`), decided from the engine's own
  state each tick, so a pool that must drain every tick runs in the
  serial order it always had. Same tokens either way.

The per-block cache math is ``nn/sampling.py``'s ``_block_prefill`` /
``_block_step`` (and ``nn/speculative.py``'s ``_block_span`` for the
verify window) applied to the gathered page view — positions beyond a
row's pages are causal-masked to exact zeros, so the paged programs
cannot drift numerically from the dense formulation or the scan
decoder.

Two optional planes ride the same programs (veles_tpu/quant/,
docs/services.md "Quantized serving"):

- **int8 weights** (``quant_weights``): decode matmul weights stored
  per-channel int8, dequantized at the head of each program;
- **int8 KV cache** (``quant_kv``): the page pool stores int8 payloads
  with per-page f32 scale sidecars — half the pool HBM at the same
  page count. Speculative/beam requests ride the window plane when the
  pool is int8 (their round/step programs are float-pool only);
- **AOT artifact** (``artifact``): pre-exported prefill/decode
  programs loaded at :meth:`start` — zero jit compiles on the
  greedy/sample path. Spec/beam programs always build live (counted).
  A corrupt or mismatched artifact falls back to live jit with a
  counted warning.

The heavy-traffic request plane (docs/services.md "Prefix sharing &
streaming") adds three latency features on top, all greedy/sample +
float-pool only:

- **prefix sharing** (``prefix_cache``): a radix-tree index over
  ``page_size``-token blocks (:class:`~veles_tpu.serving.pages.
  PrefixCache`) maps shared prompt prefixes to refcounted pages;
  admission adopts matched pages READ-ONLY into the new slot's page
  table (pages are data, so THE decode step still compiles once) and
  prefills only the unmatched suffix — a shared system prompt costs
  its pages and its prefill FLOPs once across the whole pool. The
  first write that must land inside a shared page (a full-prompt
  match re-computing its last position) copies that page first
  (copy-on-write, counted); the decode step writes back only the rows
  it made, and sends a row whose page lies among its slot's shared
  pages to the sink, so a writer can never mutate one. LRU leaves
  evict under allocator pressure;
- **chunked prefill** (``prefill_chunk``): long admissions prefill in
  fixed-size chunks co-scheduled with the decode tick — one chunk per
  tick per admitting row — instead of one monolithic bucketed pass,
  so a long admission stops stalling in-flight decodes (the
  ``prefill_stall`` gauge measures the residual per-tick stall). The
  chunk program reproduces ``attention_reference``'s exact arithmetic
  over the gathered page view, so chunked (and prefix-matched) rows
  stay bit-identical to the monolithic path;
- **token streaming**: rows whose ticket carries ``stream=True`` push
  emitted tokens at every step boundary (``Ticket.push_tokens``);
  the GenerationAPI drains them onto the wire as SSE events, so TTFT
  becomes a client-visible measurement. Still one event a step
  boundary; WHEN within the tick it is handed over: a prefill's first
  token and the last tokens of a row that finishes at once (then the
  terminal), a step's tokens for a row that goes on decoding right
  after the tick's NEXT dispatch has been issued
  (:meth:`ContinuousEngine._push_held`), so that the handler threads
  serialise and write while the tick thread sleeps on the device
  without the interpreter lock, not while it prepares the dispatch.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy

from ..error import VelesError
from ..logger import Logger
from ..nn.sampling import (_block_step, _count_decode_dispatches,
                           _embed_prompt, _head_logits,
                           _prefill_blocks, _split_rows, params_of,
                           split_stack)
from ..resilience import health
from ..resilience.faults import FaultInjected, fire as fire_fault
from ..telemetry import steptaps
from ..telemetry.counters import inc, observe
from ..telemetry.spans import emit, span
from .pages import pages_for, view_ladder, view_rung

#: floor for the temperature divisor inside the one shared decode
#: program (greedy rows carry temperature 0; their categorical lane is
#: computed-and-discarded, so the clamp only has to keep it finite)
_TEMP_EPS = 1e-3

#: the two syncs that leave the chip with nothing queued
#: (:meth:`ContinuousEngine._emptied`), and the histogram that takes
#: the seconds until the next program has been called, each
_UNFED = {"first_token": "veles_serving_unfed_first_token_seconds",
          "drain": "veles_serving_unfed_drain_seconds"}

#: a blocking read that returns sooner found its result ready
_LATE_READ_S = 50e-6

#: slot modes the plain decode step advances — also the only modes
#: that RESUME (scheduler.RESUME_MODES is the single source: their
#: per-slot PRNG stream advances exactly one split per emitted token,
#: so a retry can re-enter the stream mid-decode)
from .scheduler import RESUME_MODES as _STEP_MODES  # noqa: E402

#: jitted split-chain advance (built on first use): a 900-token
#: resume must cost ONE dispatch on the tick thread, not 900
#: host-loop split round-trips stalling every co-tenant decode
_advance_key_jit = None


def advanced_prng_key(seed: int, steps: int):
    """The per-slot PRNG carry after ``steps`` emitted tokens: every
    emitted token consumed exactly one ``jax.random.split`` of the
    slot's stream (``_split_rows`` batched, ``split(seed_key)`` at
    prefill — same carry-in-[0] convention), so the carry is a pure
    function of ``(seed, tokens emitted)``. A resumed prefill seeded
    with this key samples its first token from the SAME subkey the
    uninterrupted run would have used at that position — the
    token-level failover resume's id-exactness hinges on this one
    function. Computed as one jitted ``fori_loop`` dispatch (steps is
    a traced argument, so every resume depth shares one program)."""
    import jax
    key = jax.random.PRNGKey(int(seed))
    steps = int(steps)
    if steps <= 0:
        return key
    global _advance_key_jit
    if _advance_key_jit is None:
        import jax.numpy as jnp

        def advance(k, n):
            return jax.lax.fori_loop(
                0, n, lambda _i, kk: jax.random.split(kk)[0], k)

        _advance_key_jit = (jax.jit(advance), jnp)
    fn, jnp = _advance_key_jit
    return fn(key, jnp.int32(steps))


def _same_leaves(a: Dict, b: Dict) -> bool:
    """True when two ``params_of`` trees carry IDENTICAL array objects.
    ``device_view()`` returns its cached jax array until a host-side
    update re-places it, so object identity is the cheap 'weights
    unchanged' test the quantization cache keys on. An in-place device
    mutation that reuses the same ``jax.Array`` is invisible to this
    test — such mutators must call
    :meth:`ContinuousEngine.invalidate_quant_cache`."""
    if a.keys() != b.keys():
        return False
    for u in a:
        if a[u].keys() != b[u].keys():
            return False
        for k in a[u]:
            if a[u][k] is not b[u][k]:
                return False
    return True


def make_request(prompt, n_new, temperature=0.0, seed=0, eos_id=None,
                 mode="greedy", gamma=4, beam=4) -> Dict:
    """Normalized request dict (the subset of GenerationAPI's parsed
    request the engine consumes) — for tests and harnesses."""
    return {"prompt": [int(t) for t in prompt], "n_new": int(n_new),
            "temperature": float(temperature), "seed": int(seed),
            "eos_id": eos_id, "mode": str(mode), "gamma": int(gamma),
            "beam": int(beam)}


def fold_resume(req: Dict, resume_tokens) -> Dict:
    """Fold a failover retry's already-emitted tokens into an engine
    request: they become prompt suffix (the resumed prefill
    re-prefills them — one bucketed pass, never a re-decode),
    ``n_new`` drops to the REMAINING budget, and ``resume_k`` records
    how many stream positions the per-slot PRNG must advance before
    the first new token. ``req`` is the ORIGINAL request (full
    ``n_new``); the wire form a router sends — ``resume_tokens`` +
    remaining ``n_new`` — is what GenerationAPI's parse folds the
    same way."""
    resume = [int(t) for t in resume_tokens]
    if not resume:
        return dict(req, resume_k=0)
    remaining = int(req["n_new"]) - len(resume)
    if remaining < 1:
        raise ValueError(
            "resume_tokens (%d) leave no remaining n_new (%d)"
            % (len(resume), int(req["n_new"])))
    return dict(req,
                prompt=[int(t) for t in req["prompt"]] + resume,
                n_new=remaining, resume_k=len(resume))


class ContinuousEngine(Logger):
    """In-flight batching over a persistent paged KV-cache pool.

    ``wf`` is a generation-capable workflow (``Embedding`` →
    ``TransformerBlock``×N → ``LMHead``, validated at construction);
    ``draft`` is an optional smaller workflow of the same shape that
    enables ``mode=speculative`` on the pool. ``decode_block`` fuses
    that many decode steps into one dispatch (``lax.scan``);
    ``page_size`` must be a positive multiple of it so a chunk never
    outruns its growth check by more than one page.
    """

    def __init__(self, wf, max_slots: int = 8,
                 buckets: Tuple[int, ...] = (16, 32, 64, 128),
                 max_context: int = 640, decode_block: int = 1,
                 page_size: Optional[int] = None,
                 pages: Optional[int] = None,
                 spec_gamma: Optional[int] = None,
                 beam_width: Optional[int] = None,
                 draft=None,
                 quant_weights: Optional[bool] = None,
                 quant_kv: Optional[bool] = None,
                 artifact: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 tp: Optional[int] = None,
                 mesh=None,
                 name: str = "serving") -> None:
        super().__init__()
        from ..config import root
        from .pages import PagePool, PrefixCache
        from .scheduler import SlotScheduler
        self.wf = wf
        self.name = name
        # quantization policy (root.common.quant.*, CLI --quant-weights
        # /--quant-kv); both off = bit-identical to the float engine
        self.quant_weights = bool(
            root.common.quant.get("weights", False)
            if quant_weights is None else quant_weights)
        self.quant_kv = bool(
            root.common.quant.get("kv", False)
            if quant_kv is None else quant_kv)
        # AOT serving artifact (export/serve_artifact.py): loaded at
        # start(); empty/None = live jit
        self.artifact = str(
            root.common.serving.get("artifact", "")
            if artifact is None else (artifact or ""))
        self.artifact_mode = False
        #: live jit traces this engine paid for (0 in artifact mode)
        self.compiled_live = 0
        # raises VelesError on anything but a generation stack (a bare
        # workflow has no forwards at all — same rejection)
        self.stack = split_stack(list(getattr(wf, "forwards", ()) or ()),
                                 hybrid=True)
        #: blocks that state their own served programs and cache
        #: geometry (nn/hybrid.py HybridBlock); a stack that has one is
        #: served by the greedy/sample decode step alone, and what it
        #: refuses is never handed to the window plane, which runs
        #: TransformerBlock's equations (``window_fallback``)
        self._own_blocks = [b for b in self.stack["blocks"]
                            if hasattr(b, "serve_step")]
        self.window_fallback = not self._own_blocks
        #: the keys of what the step's sparse-expert layers count inside
        #: the program (telemetry/steptaps.py), in the order of the
        #: columns that carry them to the host after the slots' tokens;
        #: empty for a stack without such a layer
        self._tap_names: List[str] = []
        if any(getattr(b, "ffn", None) == "experts"
               for b in self._own_blocks):
            from ..nn.experts import TOUCHED, tap_keys
            self._tap_names = tap_keys() + [steptaps.counter_key(TOUCHED)]
        if any(getattr(b, "mixer", None) == "delta_rule"
               for b in self._own_blocks):
            # the delta-rule layers' live rows (nn/hybrid.py STATE_ROWS)
            from ..nn.hybrid import STATE_ROWS
            self._tap_names.append(steptaps.counter_key(STATE_ROWS))
        self.max_slots = int(max_slots)
        self.max_context = int(max_context)
        self.decode_block = max(1, int(decode_block))
        serving_cfg = root.common.serving
        self.page_size = int(
            serving_cfg.get("page_size", 16)
            if page_size is None else page_size)
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.page_size % self.decode_block:
            raise ValueError(
                "page_size %d must be a multiple of decode_block %d "
                "(a decode chunk may never outrun its page-growth "
                "check by more than one page)"
                % (self.page_size, self.decode_block))
        #: page-table entries per slot; the gathered view length is
        #: pages_per_slot * page_size >= max_context
        self.pages_per_slot = pages_for(self.max_context, self.page_size)
        cfg_pages = serving_cfg.get("pages", None) \
            if pages is None else pages
        #: usable pages; default = dense-equivalent capacity (every
        #: slot can hold max_context), which operators SHRINK to trade
        #: worst-case context reservation for more concurrent slots
        self.pages = int(self.max_slots * self.pages_per_slot
                         if cfg_pages in (None, 0) else cfg_pages)
        if self.pages < 1:
            raise ValueError("pages must be >= 1")
        self.spec_gamma = int(
            serving_cfg.get("spec_gamma", 4)
            if spec_gamma is None else spec_gamma)
        if self.spec_gamma < 1:
            raise ValueError("spec_gamma must be >= 1")
        self.beam_width = int(
            serving_cfg.get("beam_width", 4)
            if beam_width is None else beam_width)
        if self.beam_width < 1:
            raise ValueError("beam_width must be >= 1")
        from . import parse_buckets
        self.buckets = parse_buckets(buckets)
        #: the decode step's view lengths in pages, longest first
        #: (pages.view_ladder): ``_decode`` takes, each tick, the
        #: shortest that holds every active row
        self.view_ladder = view_ladder(self.pages_per_slot,
                                       self.page_size, self.buckets[0])
        self.page_pool = PagePool(self.pages, self.page_size)
        # heavy-traffic request plane knobs (root.common.serving.*,
        # CLI --serve-prefix-cache/--serve-prefill-chunk); both off =
        # bit-identical to the monolithic-prefill engine (test-locked)
        want_prefix = bool(
            serving_cfg.get("prefix_cache", False)
            if prefix_cache is None else prefix_cache)
        self.prefill_chunk = int(
            serving_cfg.get("prefill_chunk", 0)
            if prefill_chunk is None else prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 = "
                             "monolithic bucketed prefill)")
        if (want_prefix or self.prefill_chunk) and self.quant_kv:
            # the chunk/suffix program writes float rows and the COW
            # copy moves float pages — the int8 pool keeps the
            # monolithic plane (same answers, no sharing)
            self.warning("%s: prefix sharing / chunked prefill serve "
                         "the float pool only; int8 KV keeps the "
                         "monolithic prefill plane", name)
            want_prefix = False
            self.prefill_chunk = 0
        #: effective chunk width (tokens per prefill-chunk dispatch):
        #: the knob, or one page when only prefix sharing needs the
        #: suffix program
        self._chunk = self.prefill_chunk or self.page_size
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.page_pool, self.page_size)
            if want_prefix else None)
        if self.prefix_cache is not None:
            # allocator pressure reclaims cached prefixes LRU-first
            # before any admission is refused or shed
            self.page_pool.evictor = self.prefix_cache.evict
        if self._own_blocks:
            self._refuse_for_own_blocks(want_prefix, draft, tp, mesh)
        self.scheduler = SlotScheduler(self.max_slots, self.buckets,
                                       self.max_context,
                                       page_pool=self.page_pool,
                                       beam_width=self.beam_width,
                                       spec_gamma=self.spec_gamma)
        # QoS plane (root.common.serving.qos, CLI --serve-qos;
        # docs/services.md "Overload & QoS"): priority-aware admission
        # + lossless batch preemption. Off (the default) = scheduler
        # order, dispatch counts and outputs bit-identical to the
        # FIFO engine (test-locked feature-off lock).
        self.qos = bool(serving_cfg.get("qos", False))
        self.scheduler.qos = self.qos
        #: stable pressure source for dynamic Retry-After hints —
        #: registered only while a QoS engine runs (a bound method is
        #: a fresh object per access, so the identity-checked
        #: clear_pressure_provider needs this one stored)
        self._pressure_fn = lambda: (self.scheduler.queue_depth(),
                                     max(8, self.max_slots * 8))
        #: batch rows preempted for interactive admission / decoded
        #: tokens those preemptions preserved losslessly (stats keys)
        self.preemptions = 0
        self.preempted_tokens = 0
        # the draft workflow enables mode=speculative on the pool; an
        # unusable draft degrades spec to the window plane, never the
        # whole engine
        self.draft = None
        self.draft_stack = None
        if draft is not None:
            try:
                self.draft_stack = split_stack(
                    list(getattr(draft, "forwards", ()) or ()))
                self.draft = draft
            except VelesError as e:
                self.warning("%s: draft model unusable for pooled "
                             "speculation (%s); mode=speculative rides "
                             "the window plane", name, e)
        # tensor-parallel serving (root.common.serving.tp, CLI
        # --serve-tp; docs/services.md "Tensor-parallel serving"): the
        # fixed-shape programs shard_map over a 1D ("model",) mesh
        # slice — attention heads and K/V pages shard over the head
        # axis, FC/embedding weights shard column/row-parallel with
        # one psum per block, while page tables, the shared mask, slot
        # metadata and the PrefixCache stay REPLICATED host data
        # indexing logical pages. tp=1 (the default) is bit-identical
        # to the single-device engine (no shard_map in the trace).
        if mesh is not None and tp is None:
            tp = int(numpy.prod(list(mesh.shape.values())))
        self.tp = int(serving_cfg.get("tp", 1) if tp is None else tp)
        if self.tp < 1:
            raise ValueError("tp must be >= 1")
        self._mesh_arg = mesh
        self._tp_mesh_obj = None
        self._tp_params_cache = None   # (float tree, its placed twin)
        self._tp_draft_cache = None
        if self.tp > 1:
            if self.quant_weights or self.quant_kv:
                # the int8 programs dequantize per-page sidecars whose
                # scales are row-global; sharding them is future work
                raise VelesError(
                    "tensor-parallel serving (tp=%d) serves the float "
                    "plane only; disable --quant-weights/--quant-kv"
                    % self.tp)
            reason = self._tp_unshardable(self.stack)
            if reason:
                raise VelesError(
                    "stack cannot head-shard over tp=%d: %s"
                    % (self.tp, reason))
            if self.draft is not None:
                dreason = self._tp_unshardable(self.draft_stack)
                if dreason:
                    self.warning(
                        "%s: draft model cannot head-shard over tp=%d "
                        "(%s); mode=speculative rides the window "
                        "plane", name, self.tp, dreason)
                    self.draft = None
                    self.draft_stack = None
        pos_emb = self.stack["pos_emb"]
        self._table_len = (None if pos_emb is None else
                           pos_emb.param_arrays()["table"].shape[0])
        self._beam_G = max(1, self.max_slots // self.beam_width)
        self._progs: Dict = {}
        self._params = None
        self._draft_params = None
        self._quant_cache = None   # (float tree, its calibrated twin)
        self._caches = None
        self._draft_caches = None
        self._keys = None
        self._page_table = numpy.zeros(
            (self.max_slots, self.pages_per_slot), numpy.int32)
        #: the last token the HOST wrote a slot (a prefill's first, a
        #: speculative round's), and which of them it has written since
        #: the last step dispatch (:meth:`_set_tok`): the plain step
        #: takes those from the host and every other slot's from
        #: ``_last``, the tokens of the step before as they lie on the
        #: device, so a step never waits for the host to have read the
        #: one before it
        self._tok = numpy.zeros(self.max_slots, numpy.int32)
        self._fresh = numpy.ones(self.max_slots, bool)
        self._last = None
        #: the plain step dispatched and not yet read, ``(its tokens
        #: on the device, the rows it advances, its number among the
        #: dispatches)``, or None: at most one (:meth:`_decode` leaves
        #: it, :meth:`_drain` reads it)
        self._flying: Optional[Tuple] = None
        #: calls of compiled programs so far (:meth:`_fed`): a blocking
        #: read that returns on the NEWEST one leaves the device with
        #: nothing queued
        self._dispatched = 0
        #: ``(perf_counter when the chip was found with nothing queued,
        #: the cause)`` until the next call of a program has returned:
        #: the chip known to be unfed (:meth:`_emptied`); tick thread
        #: only
        self._unfed: Optional[Tuple[float, str]] = None
        #: seconds observed so far, a cause (``/stats`` ``unfed_s``)
        self.unfed_s = {cause: 0.0 for cause in _UNFED}
        #: a slot's next cache position; a masked-in row's advances at
        #: the step's dispatch, as the device's does
        self._pos = numpy.zeros(self.max_slots, numpy.int32)
        self._temp = numpy.zeros(self.max_slots, numpy.float32)
        #: per-slot count of leading READ-ONLY page-table entries
        #: (prefix-cache adoptions) — a decode-step input: the chunk
        #: write-back masks those pages to the sink, making "a writer
        #: never mutates a shared page" structural, not behavioral
        self._shared = numpy.zeros(self.max_slots, numpy.int32)
        self._thread: Optional[threading.Thread] = None
        self._closing = False
        #: pending drain-by-handoff: (reason, done event, count box) —
        #: consumed by the tick thread at the next step boundary
        self._handoff: Optional[Tuple] = None
        #: replica-death hook (set by GenerationAPI): called when an
        #: injected ``serve.replica_death`` fires mid-decode, AFTER
        #: the in-flight tickets are settled with their resume
        #: progress — the dying gasp a failover retry continues from
        self.on_death = None
        #: (ticket, tokens) of the last step's rows that go on
        #: decoding, handed to the streams under the next dispatch
        #: (:meth:`_push_held`); the tick thread's alone
        self._held: List[Tuple] = []
        #: stream events of decode-step tokens queued, and those of
        #: them queued while a dispatch was in flight (the stats
        #: surface's ``push_overlap_share``)
        self.token_pushes = 0
        self.token_pushes_overlapped = 0
        #: decode dispatches (step, speculative round, beam step) and
        #: the positions a slot that they gathered, summed (the stats
        #: surface's ``view_share``); those of them issued while the
        #: step before was still unread (``steps_ahead_share``)
        self.decode_dispatches = 0
        self.view_positions = 0
        self.steps_ahead = 0
        #: of the decode dispatches, those whose attention read the
        #: float pool through the page table (``paged_share``); the
        #: positions their live rows could see, and the positions the
        #: gather would have read for them (``live_share``)
        self.paged_reads = 0
        self.paged_positions = 0
        self.paged_view_positions = 0
        #: :meth:`_paged_read` of each block, once the pool is there
        self._paged: Optional[Tuple[bool, ...]] = None
        self.admitted = 0
        self.retired = 0
        self.peak_slots = 0
        #: chunked-prefill stall gauges: seconds of prefill work in
        #: the most recent tick that had co-tenant decodes in flight,
        #: and the worst such tick — THE "bounded TPOT jitter" number
        #: (veles_serving_prefill_stall_seconds on /metrics)
        self.prefill_stall_last = 0.0
        self.prefill_stall_max = 0.0
        #: requests that adopted at least one shared prefix block /
        #: chunk dispatches run (the stats surface)
        self.prefix_requests = 0
        self.chunk_dispatches = 0

    def _refuse_for_own_blocks(self, want_prefix, draft, tp, mesh) -> None:
        """A stack with a ``HybridBlock`` is served by the float pool's
        monolithic prefill and decode step on one device; every other
        plane is refused at construction with a line that names the
        block (a ValueError: the operator's knobs do not fit the model,
        and GenerationAPI lets that propagate instead of degrading to
        the window worker). Also checks that every such block keeps K/V
        rows or a state row (``cache_geometry`` raises for one that keeps
        neither)."""
        from ..config import root
        blk = self._own_blocks[0]
        d = self.stack["stem"].dim
        for b in self._own_blocks:
            b.cache_geometry(d, self.page_size)
        asked = [what for what, on in (
            ("int8 weights (--quant-weights)", self.quant_weights),
            ("the int8 KV pool (--quant-kv)", self.quant_kv),
            ("an AOT serve-artifact", bool(self.artifact)),
            ("the prefix cache", want_prefix),
            ("chunked prefill", bool(self.prefill_chunk)),
            ("a draft model for speculation", draft is not None),
            ("tensor-parallel serving (--serve-tp)",
             (int(root.common.serving.get("tp", 1) if tp is None
                  else tp) > 1) or mesh is not None),
        ) if on]
        if asked:
            raise ValueError(
                "%s (%s) is served by the float page pool's monolithic "
                "prefill and its greedy/sample decode step on one device; "
                "it does not serve %s"
                % (blk.name, type(blk).__name__, ", ".join(asked)))

    def _geometry(self, blk):
        """(kv heads, key width, value width, ring positions) of what a
        slot holds of ``blk``: the block's own statement, or the
        standing block's ``d // n_heads`` rows in pages (ring 0). None
        for a layer that keeps a state row instead (:meth:`_state`)."""
        d = self.stack["stem"].dim
        if hasattr(blk, "cache_geometry"):
            g = blk.cache_geometry(d, self.page_size)
            if "state" in g:
                return None
            return g["kv_heads"], g["k_dim"], g["v_dim"], g["ring"]
        hd = d // blk.n_heads
        return getattr(blk, "n_kv_heads", blk.n_heads), hd, hd, 0

    def _state(self, blk):
        """(state shape, tail shape) of the row a slot holds of a
        delta-rule layer (``HybridBlock.cache_geometry``), or None for a
        layer that keeps K/V rows."""
        if not hasattr(blk, "cache_geometry"):
            return None
        g = blk.cache_geometry(self.stack["stem"].dim, self.page_size)
        return (g["state"], g["tail"]) if "state" in g else None

    def _slot_row(self, blk):
        """What ``blk``'s pool holds a slot as one row: "ring" (a window
        layer's), "state" (a delta-rule layer's) or None (pages, read
        through the tables)."""
        if self._state(blk) is not None:
            return "state"
        return "ring" if self._geometry(blk)[3] else None

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "ContinuousEngine":
        if self._thread is not None:
            return self
        if self.artifact and not self.artifact_mode:
            self._load_artifact()
        self._closing = False
        if self.qos:
            from .overload import set_pressure_provider
            set_pressure_provider(self._pressure_fn)
        if self.tp > 1:
            # build the mesh eagerly so a too-small device pool fails
            # the START, not the first admitted request's prefill
            self._tp_mesh()
            inc("veles_tp_engines_total")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=self.name + ".engine")
        self._thread.start()
        from . import register_engine
        register_engine(self)
        self.info("%s: continuous batching up (slots=%d buckets=%s "
                  "max_context=%d decode_block=%d pages=%dx%d%s%s%s)",
                  self.name, self.max_slots, list(self.buckets),
                  self.max_context, self.decode_block, self.pages,
                  self.page_size,
                  " +spec" if self.draft is not None else "",
                  " +beam" if self.beam_width <= self.max_slots
                  else "",
                  " tp=%d" % self.tp if self.tp > 1 else "")
        return self

    def stop(self) -> None:
        with self.scheduler.cv:
            self._closing = True
            self.scheduler.cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        # a handoff the loop never consumed (stop racing a drain):
        # release its waiter — the abort below settles the tickets
        # (with progress) through the same first-terminal path
        pending_handoff, self._handoff = self._handoff, None
        if pending_handoff is not None:
            pending_handoff[1].set()
        self.scheduler.drain("server shutting down")
        self._abort_active("server shutting down", code=503,
                           retry_after=5.0, count_shed=False)
        if self.prefix_cache is not None:
            # release the index's page references — with every slot
            # retired above, the refcount ledger must balance to zero
            # (the poisoning regression test closes the loop)
            self.prefix_cache.clear()
        from .overload import clear_pressure_provider
        clear_pressure_provider(self._pressure_fn)
        from . import unregister_engine
        unregister_engine(self)

    # -- intake --------------------------------------------------------------
    def accepts(self, req: Dict) -> Optional[str]:
        """None when the slot pool can serve ``req``; otherwise the
        reason (caller falls back to the window-coalescing path)."""
        t_p, n_new = len(req["prompt"]), int(req["n_new"])
        mode = str(req.get("mode", "greedy"))
        if mode not in _STEP_MODES + ("speculative", "beam"):
            # fail CLOSED: an unrecognized mode would admit fine but
            # no tick path would ever advance it — the slot and its
            # reserved pages would leak for the life of the process
            return "unknown decode mode %r" % mode
        if t_p < 1:
            return "empty prompt"
        if self._own_blocks and mode not in _STEP_MODES:
            blk = self._own_blocks[0]
            return ("%s (%s) is served by the greedy/sample decode step "
                    "only; mode=%s is refused for this stack"
                    % (blk.name, type(blk).__name__, mode))
        if int(req.get("resume_k", 0) or 0) and mode not in _STEP_MODES:
            # resume re-enters the per-slot PRNG stream mid-decode —
            # a contract only the plain decode step owns (docs/
            # services.md "Lossless request plane": window-plane,
            # speculative and beam requests retry from scratch)
            return ("token-level resume serves greedy/sample only "
                    "(mode=%s retries from scratch)" % mode)
        if mode == "speculative":
            if self.draft is None:
                return "no pooled draft model (speculation rides the "\
                       "window plane)"
            if int(req.get("gamma", self.spec_gamma)) != self.spec_gamma:
                return ("gamma %d differs from the pool's fixed-shape "
                        "round (spec_gamma=%d)"
                        % (int(req.get("gamma", 0)), self.spec_gamma))
            if self.quant_kv:
                return "int8 KV pool serves greedy/sample only; "\
                       "speculation rides the window plane"
        if mode == "beam":
            if int(req.get("beam", self.beam_width)) != self.beam_width:
                return ("beam %d differs from the pool's fixed-shape "
                        "group (beam_width=%d)"
                        % (int(req.get("beam", 0)), self.beam_width))
            if self.quant_kv:
                return "int8 KV pool serves greedy/sample only; beam "\
                       "rides the window plane"
            vocab = int(self.stack["head"].vocab_size)
            if self.beam_width > vocab:
                return ("beam %d exceeds the head's vocab size %d"
                        % (self.beam_width, vocab))
        reason = self.scheduler.reject_reason(
            t_p, n_new, mode=mode,
            gamma=int(req.get("gamma", self.spec_gamma)))
        if reason:
            return reason
        worst = self.scheduler._worst_positions(
            t_p, n_new, mode, int(req.get("gamma", self.spec_gamma)))
        if self._table_len is not None and worst > self._table_len:
            return ("generation to %d positions exceeds the trained "
                    "PositionalEmbedding table (%d rows)"
                    % (worst, self._table_len))
        if self.draft is not None and mode == "speculative":
            dpe = self.draft_stack["pos_emb"]
            if dpe is not None and \
                    worst > dpe.param_arrays()["table"].shape[0]:
                return ("speculation to %d positions exceeds the "
                        "draft's PositionalEmbedding table" % worst)
        if mode != "beam" and \
                0 < float(req.get("temperature", 0.0)) < _TEMP_EPS:
            # the shared decode program clamps the divisor at
            # _TEMP_EPS; a colder-than-that request would sample from
            # different logits here than solo sampling.generate does —
            # route it to the window plane, which divides exactly
            return ("temperature %g below the engine's %g resolution"
                    % (req["temperature"], _TEMP_EPS))
        bucket = self.scheduler.bucket_for(t_p)
        if self._kernel_straddle(t_p, bucket, self.stack):
            # padding to the bucket would flip attention_core's
            # flash/reference choice vs the exact-length solo prefill
            # (choose_flash is length-gated) — different kernels drift
            # in the last bits and break the id-exactness contract, so
            # such a prompt rides the window plane instead
            return ("prompt %d pads to bucket %d across the "
                    "flash-attention crossover" % (t_p, bucket))
        if mode == "speculative" and self._kernel_straddle(
                t_p, bucket, self.draft_stack):
            return ("prompt %d pads to bucket %d across the draft's "
                    "flash-attention crossover" % (t_p, bucket))
        return None

    def _kernel_straddle(self, t_p: int, bucket: int, stack) -> bool:
        """True when any block's attention would pick a different
        kernel for the padded bucket length than for the exact prompt
        length (see ``ops.flash_attention.choose_flash``)."""
        if t_p == bucket:
            return False
        from ..ops.flash_attention import choose_flash
        d = stack["stem"].dim
        for blk in stack["blocks"]:
            if hasattr(blk, "serve_prefill"):
                continue        # its prefill has one kernel at any length
            hd = d // blk.n_heads
            if choose_flash(bucket, hd) != choose_flash(t_p, hd):
                return True
        return False

    def submit(self, req: Dict, ticket,
               max_queue: Optional[int] = None,
               checked: bool = False) -> bool:
        """Enqueue one request; False = queue bound hit (caller
        sheds). ``ticket`` follows the :class:`scheduler.Ticket`
        protocol (``fail`` / ``succeed`` / ``deadline``).
        ``checked=True`` skips :meth:`accepts` — for callers that just
        routed on its verdict."""
        if not checked:
            reason = self.accepts(req)
            if reason is not None:
                # direct submits (no API-side accepts() pre-check) get
                # a clean client-fault answer instead of a 500 at
                # admission
                ticket.fail(reason, code=400)
                return True
        # the closing check and the enqueue share the scheduler's
        # condition (an RLock): stop() flips _closing under the same
        # lock before draining, so a ticket can never slip into the
        # queue after the drain and strand its handler until 504
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
    def stats(self) -> Dict[str, float]:
        from ..quant import pool_nbytes
        in_use = self.page_pool.in_use()
        # occupancy per DISTINCT page: a page shared by N slots (or by
        # a slot and the prefix index) holds its positions once, so
        # the fragmentation gauge cannot go negative — or read as
        # phantom HBM — under prefix sharing (satellite fix; in_use
        # already counts shared pages once)
        occ: Dict[int, int] = {}
        prefilling = 0
        for slot in self.scheduler.active():
            pos = int(self._pos[slot.idx])
            if slot.prefilled is not None:
                prefilling += 1
            for j, page in enumerate(slot.pages):
                filled = max(0, min(pos - j * self.page_size,
                                    self.page_size))
                if filled:
                    occ[page] = max(occ.get(page, 0), filled)
        if self.prefix_cache is not None:
            for page in self.prefix_cache.cached_pages():
                occ[page] = self.page_size   # cached blocks are full
        occupied = sum(occ.values())
        frag = (0.0 if in_use == 0 else
                max(0.0, 1.0 - occupied / (in_use * self.page_size)))
        prefix_blocks = (0 if self.prefix_cache is None
                         else self.prefix_cache.stats()["blocks"])
        return {
            "slots": self.max_slots,
            "slots_busy": self.scheduler.busy_count(),
            "peak_slots": self.peak_slots,
            "queue_depth": self.scheduler.queue_depth(),
            "admitted": self.admitted,
            "retired": self.retired,
            # QoS plane (docs/services.md "Overload & QoS"): priority
            # admission + lossless batch preemption, all zero with the
            # knob off
            "qos": int(self.qos),
            "preemptions": self.preemptions,
            "preempted_tokens": self.preempted_tokens,
            "programs": len(self._progs),
            # slot-kind discriminator: "paged" rows page a KV pool;
            # the O(1) lane (serving/recurrent.py) reports "state" and
            # the /metrics renderers emit veles_serving_pages_* rows
            # ONLY for paged engines, so fleet page math never mixes
            # kinds
            "slot_kind": "paged",
            # paged-pool occupancy (serving/pages.py): what an
            # operator sizes `pages`/`page_size` with
            "pages_total": self.pages,
            "pages_in_use": in_use,
            "page_size": self.page_size,
            "page_fragmentation": round(frag, 4),
            # heavy-traffic request plane (docs/services.md "Prefix
            # sharing & streaming"): index occupancy, chunked-prefill
            # progress and the per-tick decode stall the chunking
            # exists to bound
            "prefix_cache": int(self.prefix_cache is not None),
            "prefix_blocks": prefix_blocks,
            "prefix_requests": self.prefix_requests,
            "prefill_chunk": self._chunk if (
                self.prefill_chunk or self.prefix_cache is not None)
            else 0,
            "chunk_dispatches": self.chunk_dispatches,
            "prefilling": prefilling,
            "prefill_stall_seconds": round(self.prefill_stall_max, 6),
            # the share of decode-step stream events handed over while
            # a dispatch was in flight (the handlers write while the
            # chip works); 0 with no streaming request
            "push_overlap_share": round(
                self.token_pushes_overlapped
                / max(1, self.token_pushes), 4),
            # the mean view a decode dispatch gathered a slot, over the
            # whole view (pages_per_slot pages); 1.0 = the ladder
            # never engaged (or has one rung)
            "view_share": round(
                self.view_positions
                / (self.decode_dispatches * self.pages_per_slot
                   * self.page_size), 4)
            if self.decode_dispatches else 1.0,
            # the share of decode dispatches issued while the step
            # before was still unread (the chip did not wait for the
            # host between the two); 0 for a pool that drains every
            # tick (speculative or beam rows beside the plain ones)
            "steps_ahead_share": round(
                self.steps_ahead / max(1, self.decode_dispatches), 4),
            # the share of decode dispatches whose attention read the
            # float pool through the page table, and of the positions
            # the gather would have read for them, those their live
            # rows could see (docs/services.md "How the decode step
            # reads the cache"); 0 where no step read so
            "paged_share": round(
                self.paged_reads / max(1, self.decode_dispatches), 4),
            "live_share": round(
                self.paged_positions / max(1, self.paged_view_positions),
                4),
            # seconds the chip was KNOWN to have nothing queued before
            # the engine's next program, by the sync that emptied it
            # (the two veles_serving_unfed_* histograms' sums)
            "unfed_s": {cause: round(s, 6)
                        for cause, s in self.unfed_s.items()},
            # quantization/AOT plane (veles_tpu/quant/): what the
            # /metrics mode gauges render on both surfaces
            "artifact_mode": int(self.artifact_mode),
            "quant_weights": int(self.quant_weights),
            "quant_kv": int(self.quant_kv),
            "compiled_live": self.compiled_live,
            # mesh-slice width this ONE logical replica spans (1 =
            # solo). Every page gauge above counts LOGICAL pages —
            # host-side allocator state plus global array shapes, both
            # shard-agnostic — so a tp=4 slice reports its occupancy
            # ONCE, not four times (fleet.merge keys chip math off
            # veles_serving_tp, never off page gauges)
            "tp": self.tp,
            "kv_pool_bytes": pool_nbytes(self._caches)
            + pool_nbytes(self._draft_caches),
            # of those, what the window layers' rings hold: max_slots x
            # ring positions a layer, whatever the slots' contexts (0
            # for a stack without a window layer that keeps a ring)
            "kv_ring_bytes": pool_nbytes(
                [c for b, c in zip(self.stack["blocks"],
                                   self._caches or ())
                 if self._slot_row(b) == "ring"]),
            "kv_ring_positions": max(
                [self._geometry(b)[3] for b in self._own_blocks
                 if self._slot_row(b) != "state"] or [0]),
            # of those, what the delta-rule layers' rows hold: a state in
            # float32 and a convolution's tail a slot, whatever the
            # slots' contexts (0 for a stack without such a layer)
            "state_bytes": pool_nbytes(
                [c for b, c in zip(self.stack["blocks"],
                                   self._caches or ())
                 if self._slot_row(b) == "state"]),
            # what ONE chip of the slice actually holds: the kv-head
            # axis shards tp ways (pages.per_shard_kv_heads), so the
            # per-chip HBM is the logical pool over tp — the number
            # an operator sizes a single chip's memory against
            "kv_pool_bytes_per_shard": (
                pool_nbytes(self._caches)
                + pool_nbytes(self._draft_caches)) // max(1, self.tp),
        }

    @property
    def closing(self) -> bool:
        """True once :meth:`stop` has begun — :meth:`submit` returns
        False for a closing engine too, and the caller's shed answer
        should say shutdown, not queue-full."""
        return self._closing

    @property
    def programs_built(self) -> int:
        """Jitted programs this engine ever built. The greedy/sample
        plane is bounded by ``len(buckets) + len(view_ladder)``;
        speculation adds its draft prefills + one round program, beam
        one step program — see :meth:`programs_bound`."""
        return len(self._progs)

    def programs_bound(self) -> int:
        """The hard ceiling on :attr:`programs_built`: bucketed
        prefills + the decode step at each rung of ``view_ladder``,
        plus (draft configured) the draft prefills + the spec round,
        plus (beam servable) the beam step and the sibling page-copy —
        a CONSTANT per engine, never a function of traffic."""
        bound = len(self.buckets) + len(self.view_ladder)
        if self.draft is not None:
            bound += len(self.buckets) + 1
        has_pagecopy = False
        if self.beam_width <= self.max_slots:
            bound += 1
            has_pagecopy = self.beam_width > 1
        if self.prefix_cache is not None or self.prefill_chunk:
            bound += 1               # the ONE prefill-chunk program
            if self.prefix_cache is not None:
                has_pagecopy = True  # COW copies ride pagecopy
        return bound + (1 if has_pagecopy else 0)

    def invalidate_quant_cache(self) -> None:
        """Drop the calibrated int8 twin (and the cached device view)
        so the next idle boundary recalibrates from the live weights.
        The identity-keyed cache in :meth:`_prepare_params` cannot see
        an IN-PLACE device mutation that reuses the same ``jax.Array``
        object — any code path that mutates parameters without
        re-placing them must call this, or quantized serving would
        keep the stale scales forever."""
        self._quant_cache = None
        self._params = None
        self._draft_params = None

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
                        self._flush()       # no dispatch follows a wait
                        # no traffic is not the host's lateness (and
                        # the wait has a histogram of its own)
                        self._unfed = None
                        with span("serving.loop.wait"):
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
                    # donated buffers may be gone — rebuild lazily; a
                    # step in flight goes with them unread, and its rows
                    # answer with what they had recorded
                    self._reset_pool()
                    self._abort_active("internal serving error",
                                       code=500, count_shed=False)
                    # a tick that dies before take_admissions never
                    # reaches the deadline check there: sweep the queue
                    # so waiting callers still get their 503 instead of
                    # hanging to full timeout, and back off instead of
                    # busy-spinning while the failure persists
                    from .scheduler import shed_expired
                    shed_expired(self.scheduler.expire_queued())
                    if not self._closing:
                        time.sleep(min(1.0, 0.05 * (2 ** fail_streak)))
        finally:
            health.heartbeats.unregister(hb)

    def _reset_pool(self) -> None:
        self._caches = self._draft_caches = self._keys = None
        self._last = self._flying = self._unfed = None
        self._params = self._draft_params = None

    def _active(self, modes: Tuple[str, ...]) -> List:
        return [s for s in self.scheduler.active() if s.mode in modes]

    def _tick(self) -> None:
        """One step boundary: admit into free slots, then advance each
        decode mode's rows by one fixed-shape dispatch."""
        pending_handoff = self._handoff
        if pending_handoff is not None:
            # drain-by-handoff runs ON the tick thread so the
            # progress snapshot can never race a decode dispatch
            self._handoff = None
            reason, done, box = pending_handoff
            try:
                box["count"] = self._do_handoff(reason)
            finally:
                done.set()
            return
        if self.scheduler.busy_count():
            try:
                # the mid-decode replica-death chaos site: `after=N`
                # kills this replica N in-flight ticks into its load,
                # deterministically — the settled tickets carry their
                # emitted-token prefix, so the router's failover
                # RESUMES from tokens_done instead of re-decoding
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
        carried = self._held
        with span("serving.tick", active=self.scheduler.busy_count()):
            self._tick_phases()
            if self._held is carried:
                # no dispatch of this tick took them along and left a
                # new list behind (every row chunk-prefilling, shed or
                # preempted): none follows before the next tick's
                self._push_held(phase=True)

    def _tick_phases(self) -> None:
        """The tick proper, one span a phase under ``serving.tick``
        (``serving.tick.{admit,prefill,prepare,dispatch,device,emit}``,
        each also a histogram: telemetry/spans.py SPAN_HISTOGRAMS), so
        that a profiler capture and ``/metrics`` both say where the
        host's time goes. A plain tick's order is admit, prefill,
        prepare and dispatch step n+1, hand step n-1's tokens to the
        streams (``emit``, :meth:`_push_held`), wait for step n's
        tokens (``device``), record them and finish rows (``emit``
        again): ``prepare``, ``dispatch`` and both halves of ``emit``
        run while the chip runs a step, and ``device`` is the wait for
        the step BEFORE the one just dispatched, short by what the host
        did meanwhile. Where the pipeline is drained (:meth:`_drain`)
        the wait is for the step just dispatched, as it always was
        before."""
        with span("serving.tick.prepare"):
            params = self._tick_params()
        from .scheduler import shed_expired
        # co-tenants in flight BEFORE this tick's admissions: only
        # their decode latency can be stalled by prefill work, so the
        # chunked-prefill stall gauge measures exactly that window
        had_inflight = self.scheduler.busy_count() > 0
        t_prefill = time.time()
        with span("serving.tick.admit"):
            if self.qos:
                # QoS preemption happens HERE, at the step boundary
                # before admission, so freed slots/pages are handed to
                # the waiting interactive requests in this same tick
                self._preempt_for_interactive()
            admissions, expired = self.scheduler.take_admissions()
            shed_expired(expired)
        with span("serving.tick.prefill", admitted=len(admissions)):
            if not self._admit_all(params, admissions):
                return
            self.peak_slots = max(self.peak_slots,
                                  self.scheduler.busy_count())
            # _prefill_tick handles its own serve.prefill_chunk fault
            # internally (sheds ONLY the faulted row, co-tenants keep
            # decoding) — no blanket abort may wrap it, or one injected
            # chunk fault would shed the whole pool
            prefill_work = bool(admissions) | self._prefill_tick(params)
        if prefill_work and had_inflight:
            self.prefill_stall_last = time.time() - t_prefill
            self.prefill_stall_max = max(self.prefill_stall_max,
                                         self.prefill_stall_last)
        try:
            if self._decodable():
                self._decode(params)
            elif self._flying is not None:
                self._drain()       # no plain step follows it this tick
            if self._active(("speculative",)):
                self._spec_tick(params)
            if self.scheduler.active_beams():
                self._beam_tick(params)
        except FaultInjected as e:
            # an injected decode fault DEGRADES: in-flight rows are
            # shed with Retry-After, the pool stays consistent (the
            # fault fires before the dispatch)
            self._abort_active(str(e), code=503, retry_after=1.0)

    def _tick_params(self):
        """The parameter snapshot this tick decodes on, and the pool."""
        # the param device-view walk (per-array locks) is too heavy to
        # repeat per decode chunk, but a snapshot held forever would
        # serve stale weights after a host-side update. Middle ground:
        # re-read whenever the pool is IDLE (no in-flight rows) — a
        # param change lands at the next burst boundary, no request
        # ever decodes on torn half-old/half-new weights, and under
        # sustained load the walk is never on the per-token path
        # (weights are frozen while serving, as everywhere in serving).
        params = self._params
        if params is None or self.scheduler.busy_count() == 0:
            # a step still in flight here holds only rows that ended on
            # an eos_id: it is read before the weights may change
            self._flush()
            params = self._params = self._prepare_params()
            if self.draft is not None:
                self._draft_params = self._prepare_draft_params()
        self._ensure_pool(params)
        return params

    def _admit_all(self, params, admissions) -> bool:
        """Admit the taken slots in order; False when an admission
        failed and the pool was reset (the tick ends there)."""
        for slot in admissions:
            if self.scheduler.slots[slot.idx] is not slot:
                # already retired within this very loop — an n_new=1
                # beam group is finished (and every hypothesis row
                # freed) by its FIRST slot's admission; dispatching
                # prefills for the dead siblings would waste device
                # work and smear host state over freed rows
                continue
            try:
                self._admit(params, slot)
            except Exception as e:    # noqa: BLE001 — answer, don't die
                # retire the whole group before answering: sibling
                # hypothesis rows share this ticket, and leaving them
                # active would let _abort_active below overwrite the
                # already-set answer (a torn 500/503 read in the
                # handler thread)
                for victim in (slot.group.slots
                               if slot.group is not None else [slot]):
                    self._retire_slot(victim)
                slot.ticket.fail("%s: %s" % (type(e).__name__, e),
                                 code=500)
                # the prefill program DONATES the pool: a dispatch
                # that died may have consumed the co-tenants' caches
                # with it, and there is no cheap way to tell. Shed the
                # in-flight rows (503 + Retry-After) and rebuild the
                # pool rather than decode on possibly-dead buffers.
                self.exception("%s: admission failed; resetting the "
                               "slot pool", self.name)
                self._reset_pool()      # a step in flight too, unread
                self._abort_active("serving pool reset after a failed "
                                   "admission", code=503,
                                   retry_after=1.0)
                return False
        return True

    # -- QoS preemption --------------------------------------------------------
    @staticmethod
    def _emitted(slot) -> List[int]:
        """Every token this request has emitted since the CLIENT's
        submission: tokens an in-engine preemption folded back into
        the prompt (``_qos_prefix``) plus this slot's own decode
        output. Progress snapshots and final results are built from
        this, so preemption stays invisible on the wire — a router's
        own ``resume_tokens`` are NOT included (the router accounts
        for those itself, exactly as before)."""
        return list(slot.req.get("_qos_prefix", ())) + list(slot.tokens)

    def _preempt_victims(self, need: int) -> List:
        """Pick up to ``need`` preemptable batch rows: plain decode
        modes only (their PRNG stream resumes exactly), fully
        prefilled, with at least one emitted token and at least one
        still to go (a row about to finish is cheaper to let finish).
        Cheapest first — fewest decoded tokens means the smallest
        re-prefill on resume."""
        from .overload import request_priority
        victims = [s for s in self.scheduler.active()
                   if s.group is None and s.mode in _STEP_MODES
                   and request_priority(s.req) == "batch"
                   and s.prefilled is None and s.tokens
                   and len(s.tokens) < s.n_new]
        victims.sort(key=lambda s: (len(s.tokens), s.idx))
        return victims[:max(0, need)]

    def _preempt_for_interactive(self) -> None:
        """QoS preemption at the step boundary (docs/services.md
        "Overload & QoS"): when more interactive requests wait than
        free slots exist, batch rows are preempted through the
        token-level resume path — emitted tokens fold back into the
        prompt (:func:`fold_resume`), ``resume_k`` accumulates so the
        resumed prefill re-enters the per-slot PRNG stream exactly,
        and the SAME un-terminated ticket requeues. No terminal
        fires, no histogram double-samples: the client of a preempted
        batch request just sees a pause, and its final answer is
        bit-identical to an uninterrupted decode (test-locked)."""
        from .overload import qos_preempt_enabled, request_priority
        if not qos_preempt_enabled():
            return
        with self.scheduler.cv:
            waiting = sum(
                1 for req, _t in self.scheduler._queue
                if request_priority(req) == "interactive")
            free = len(self.scheduler._free)
        if waiting <= free:
            return
        victims = self._preempt_victims(waiting - free)
        if victims:
            # a victim's progress is what it has recorded, so a step in
            # flight is read first (it may end a row: choose again);
            # and a requeued ticket may meet its deadline in the queue,
            # so its last step's tokens go out before it goes back there
            self._flush()
            victims = self._preempt_victims(waiting - free)
        for slot in victims:
            emitted = self._emitted(slot)
            resumed = fold_resume(slot.req, slot.tokens)
            # chained folds accumulate: the PRNG must advance one
            # split per token EVER emitted for this request, not just
            # this preemption's batch (fold_resume alone records only
            # the latest fold — correct for the router's single-shot
            # wire form, not for repeated in-engine preemption)
            resumed["resume_k"] = (int(slot.req.get("resume_k", 0)
                                       or 0) + len(slot.tokens))
            resumed["_qos_prefix"] = emitted
            resumed["_requeued"] = True
            # progress rides the ticket too: a failure between
            # preemption and completion still answers with the full
            # resume record
            slot.ticket.set_progress(emitted)
            self._retire_slot(slot)
            self.scheduler.push(resumed, slot.ticket)
            self.preemptions += 1
            self.preempted_tokens += len(slot.tokens)
            inc("veles_qos_preemptions_total")
            inc("veles_qos_preempted_tokens_total", len(slot.tokens))
            self.debug("%s: preempted batch request %s at %d tokens "
                       "(lossless resume queued)", self.name,
                       slot.ticket.request_id, len(emitted))

    def _prepare_params(self) -> Dict:
        """Fresh device-side params for the serving programs: the
        float tree, or its per-channel int8 twin under
        ``quant_weights``. Calibration is NOT repeated per idle
        boundary: ``device_view()`` returns the cached jax array until
        a host-side update re-places it, so leaf identity against the
        last-calibrated tree tells exactly when the weights actually
        changed — unchanged weights reuse the quantized twin, updated
        weights get fresh scales at the next burst boundary. In-place
        device mutations (same ``jax.Array`` object, new bytes) are
        invisible here — their authors must call
        :meth:`invalidate_quant_cache`."""
        params = params_of(self.wf)
        if self.tp > 1:
            # sharded placement is cached by the same leaf-identity
            # test the quant twin uses: unchanged weights reuse the
            # resident shards, updated weights re-place at the next
            # burst boundary (quant is gated off under tp)
            cached = self._tp_params_cache
            if cached is not None and _same_leaves(cached[0], params):
                return cached[1]
            placed = self._tp_place(
                params, self._params_pspec(self.stack, params))
            self._tp_params_cache = (params, placed)
            return placed
        if not self.quant_weights:
            return params
        cached = self._quant_cache
        if cached is not None and _same_leaves(cached[0], params):
            return cached[1]
        from ..quant import quantize_params
        qparams, _report = quantize_params(params)
        self._quant_cache = (params, qparams)
        return qparams

    def _prepare_draft_params(self) -> Dict:
        """The draft tree — under ``tp`` placed on the mesh with the
        same identity caching as :meth:`_prepare_params`."""
        params = params_of(self.draft)
        if self.tp <= 1:
            return params
        cached = self._tp_draft_cache
        if cached is not None and _same_leaves(cached[0], params):
            return cached[1]
        placed = self._tp_place(
            params, self._params_pspec(self.draft_stack, params))
        self._tp_draft_cache = (params, placed)
        return placed

    def _ensure_pool(self, params) -> None:
        if self._caches is not None:
            return
        import jax.numpy as jnp
        from ..quant import block_page_pool
        rows = self.page_pool.device_rows
        dtype = self._pool_dtype(params)

        def pools(stack, quantized):
            d = stack["stem"].dim
            out = []
            for blk in stack["blocks"]:
                state = self._state(blk)
                if state is not None:
                    # a delta-rule layer: a row a slot whatever its
                    # context, the state in float32 and the tail of the
                    # convolution's inputs in the activations' type
                    out.append((jnp.zeros((self.max_slots,) + state[0],
                                          jnp.float32),
                                jnp.zeros((self.max_slots,) + state[1],
                                          dtype)))
                    continue
                if hasattr(blk, "cache_geometry"):
                    # the block's own heads and widths; a window layer
                    # keeps a ring a slot and no page
                    kv, kd, vd, ring = self._geometry(blk)
                    lead = ((self.max_slots, ring) if ring
                            else (rows, self.page_size))
                    out.append((jnp.zeros(lead + (kv, kd), dtype),
                                jnp.zeros(lead + (kv, vd), dtype)))
                    continue
                bkv = getattr(blk, "n_kv_heads", blk.n_heads)
                hd = d // blk.n_heads
                out.append(block_page_pool(rows, self.page_size, bkv,
                                           hd, dtype, quantized))
            return tuple(out)

        self._caches = pools(self.stack, self.quant_kv)
        if self.draft is not None and not self.quant_kv:
            # the draft pool shares the allocator and page tables; it
            # stays float. Under quant_kv accepts() routes EVERY
            # speculative request to the window plane, so allocating
            # it there would be pure dead HBM against the very claim
            # quant_kv makes
            self._draft_caches = pools(self.draft_stack, False)
        self._keys = jnp.zeros((self.max_slots, 2), jnp.uint32)
        # what the step before gave (its tokens, the experts' counts
        # beside them); until a step has run, every slot's token is
        # the host's. Uploaded, not made on the device: a shape of its
        # own there would be one more program to build in set-up
        self._last = jnp.asarray(numpy.zeros(
            (self.decode_block, self.max_slots + len(self._tap_names)),
            numpy.int32))
        self._fresh[:] = True
        if self.tp > 1:
            # pools shard over the kv-head axis (each chip holds every
            # logical page's heads/tp slice — pages.py per_shard_kv);
            # keys stay replicated. Placing them NOW keeps the
            # donation path alias-clean from the first dispatch
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh = self._tp_mesh()
            self._caches = self._tp_place(
                self._caches, self._caches_pspec(self.stack))
            if self._draft_caches is not None:
                self._draft_caches = self._tp_place(
                    self._draft_caches,
                    self._caches_pspec(self.draft_stack))
            self._keys, self._last = jax.device_put(
                (self._keys, self._last), NamedSharding(mesh, P()))

    def _pool_dtype(self, params):
        """Float dtype of the activation path (the stem table's —
        also under quant_weights, which never touches ``table``)."""
        stem = self.stack["stem"]
        return params[stem.name]["table"].dtype

    # -- tensor-parallel mesh (docs/services.md "Tensor-parallel
    # serving") -----------------------------------------------------------
    @property
    def _tp_axis(self):
        """Mesh axis name the programs shard over, or None solo."""
        return "model" if self.tp > 1 else None

    def _tp_unshardable(self, stack) -> Optional[str]:
        """Reason string when ``stack`` cannot head/vocab-shard over
        ``self.tp`` ways, else None. Every sharded dimension must
        divide evenly — a ragged shard would silently change the
        math, and id-exactness is the whole contract."""
        tp = self.tp
        stem, head = stack["stem"], stack["head"]
        vocab = stem.param_arrays()["table"].shape[0]
        if vocab % tp:
            return "vocab %d %% tp %d != 0" % (vocab, tp)
        hv = head.param_arrays()["weights"].shape[1]
        if hv % tp:
            return "head vocab %d %% tp %d != 0" % (hv, tp)
        from .pages import per_shard_kv_heads
        for blk in stack["blocks"]:
            kv = getattr(blk, "n_kv_heads", blk.n_heads)
            try:
                per_shard_kv_heads(kv, tp)
            except ValueError:
                return ("%s heads %d/kv %d not divisible by tp %d"
                        % (blk.name, blk.n_heads, kv, tp))
            if blk.n_heads % tp:
                return ("%s heads %d/kv %d not divisible by tp %d"
                        % (blk.name, blk.n_heads, kv, tp))
            hidden = blk.param_arrays()["w1"].shape[1]
            if hidden % tp:
                return ("%s ffn hidden %d %% tp %d != 0"
                        % (blk.name, hidden, tp))
        return None

    def _tp_mesh(self):
        """The 1D ``("model",)`` mesh slice this engine serves as —
        built lazily (no jax import at construction) from the first
        ``self.tp`` local devices, or the caller's ``mesh=`` knob."""
        if self._tp_mesh_obj is None:
            if self._mesh_arg is not None:
                self._tp_mesh_obj = self._mesh_arg
            else:
                import jax
                devs = jax.devices()
                if len(devs) < self.tp:
                    raise VelesError(
                        "tp=%d needs %d devices; %d visible (set "
                        "TPU_VISIBLE_CHIPS / XLA_FLAGS for a CPU "
                        "virtual mesh)" % (self.tp, self.tp,
                                           len(devs)))
                from jax.sharding import Mesh
                self._tp_mesh_obj = Mesh(
                    numpy.array(devs[:self.tp]), ("model",))
        return self._tp_mesh_obj

    def _params_pspec(self, stack, params):
        """PartitionSpec tree matching ``params``: wq/wk/wv/w1/w3 and
        the head weights shard COLUMN-parallel, wo/w2 and the stem
        table ROW-parallel, b1/head-bias along their sharded dim; b2,
        norms and the positional table stay replicated (b2 is added
        once AFTER the block psum — a sharded b2 would be
        tp-counted)."""
        from jax.sharding import PartitionSpec as P
        stem, head = stack["stem"], stack["head"]
        blocks = {blk.name for blk in stack["blocks"]}
        col = {"wq", "wk", "wv", "w1", "w3"}
        row = {"wo", "w2"}
        out = {}
        for uname, leaves in params.items():
            spec = {}
            for key in leaves:
                if uname == stem.name and key == "table":
                    s = P("model", None)
                elif uname == head.name and key == "weights":
                    s = P(None, "model")
                elif uname == head.name and key == "bias":
                    s = P("model")
                elif uname in blocks and key in col:
                    s = P(None, "model")
                elif uname in blocks and key in row:
                    s = P("model", None)
                elif uname in blocks and key == "b1":
                    s = P("model")
                else:
                    s = P()
                spec[key] = s
            out[uname] = spec
        return out

    def _caches_pspec(self, stack):
        """Per-block K/V page-pool specs: the pool's kv-head axis
        (axis 2 of (rows, page_size, kv, hd)) shards over the mesh —
        each chip holds every LOGICAL page's ``kv/tp`` head slice, so
        page ids, refcounts, COW and the eviction ledger never learn
        about sharding."""
        from jax.sharding import PartitionSpec as P
        s = P(None, None, "model", None)
        return tuple((s, s) for _ in stack["blocks"])

    def _tp_place(self, tree, specs):
        """``device_put`` a pytree onto the mesh per its spec tree."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self._tp_mesh()
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        return jax.device_put(tree, shardings)

    def _finalize(self, fn, donate=(), in_specs=None, out_specs=None):
        """jit a program builder's raw function — plain ``jax.jit``
        solo (bit-identical to the pre-TP engine), or jit(shard_map)
        over the ``("model",)`` mesh under ``tp>1``. One seam, so
        every builder stays a single definition for both planes."""
        import jax
        if self.tp <= 1:
            return jax.jit(fn, donate_argnums=donate)
        return jax.jit(
            jax.shard_map(fn, mesh=self._tp_mesh(), in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            donate_argnums=donate)

    # -- admission ------------------------------------------------------------
    def _refresh_table_row(self, slot) -> None:
        """Sync the host page-table row with the slot's page list —
        THE one place the row layout is written (admission, growth and
        the sibling page-copy all go through here)."""
        row = self._page_table[slot.idx]
        row[:] = 0
        row[:len(slot.pages)] = slot.pages

    def _table_row(self, slot):
        import jax.numpy as jnp
        self._refresh_table_row(slot)
        return jnp.asarray(self._page_table[slot.idx])

    def _set_tok(self, idx: int, token: int) -> None:
        """The host writes slot ``idx``'s last token (a prefill's
        first, a speculative round's, a retired row's nought): the next
        plain step takes it from the host, not from the device's row of
        the step before."""
        self._tok[idx] = token
        self._fresh[idx] = True

    def _admit(self, params, slot) -> None:
        import jax
        import jax.numpy as jnp
        t_p, bucket = slot.t_p, slot.bucket
        group = slot.group
        if group is not None and slot is not group.slots[0]:
            # sibling hypothesis rows start as exact copies of the
            # lead row's prompt cache: ONE page-granular device copy
            # instead of re-running the full prefill per hypothesis
            # (the lead admits first — take_admissions fills groups in
            # order)
            dst_row = self._table_row(slot)
            src_row = self._table_row(group.slots[0])
            self._caches = self._program("pagecopy")(
                src_row, dst_row, self._caches)
            self._pos[slot.idx] = t_p
            self._temp[slot.idx] = slot.temperature
            return
        if group is None and slot.mode in _STEP_MODES \
                and self._admit_chunked(slot):
            # prefix adoption / chunked prefill: the suffix prefills
            # chunk-by-chunk across ticks (_prefill_tick), co-scheduled
            # with the decode step instead of stalling it
            return
        ids = numpy.zeros((1, bucket), numpy.int32)
        ids[0, :t_p] = slot.req["prompt"]
        ids_dev = jnp.asarray(ids)
        table_row = self._table_row(slot)
        prog = self._program("prefill", bucket)
        resume_k = int(slot.req.get("resume_k", 0) or 0)
        # a resumed request's prompt already carries its emitted-token
        # prefix (fold_resume); the PRNG carry must re-enter the
        # stream at the resumed position — one host-side split per
        # token already emitted, so the resumed decode's noise is
        # bit-identical to the uninterrupted run's
        seed_key = (advanced_prng_key(slot.req.get("seed", 0), resume_k)
                    if resume_k
                    else jax.random.PRNGKey(int(slot.req.get("seed",
                                                             0))))
        if resume_k and group is None:
            inc("veles_resume_tokens_total", resume_k)
        with span("serving.prefill", bucket=bucket, slot=slot.idx,
                  t_p=t_p, mode=slot.mode,
                  request_id=slot.ticket.request_id,
                  trace_id=slot.ticket.trace_id,
                  attempt=slot.ticket.attempt):
            first, logits, self._keys, self._caches = prog(
                params, ids_dev, numpy.int32(t_p),
                numpy.int32(slot.idx), numpy.float32(slot.temperature),
                seed_key, table_row, self._keys, self._caches)
            prefill = self._dispatched
            self._push_held(overlapped=True)
        inc("veles_serving_prefill_dispatches_total")
        inc("veles_serving_prefill_positions_total", bucket)
        self._pos[slot.idx] = t_p
        self._temp[slot.idx] = slot.temperature
        if slot.mode == "speculative":
            self._draft_caches = self._program("dprefill", bucket)(
                self._draft_params, ids_dev, table_row,
                self._draft_caches)
            inc("veles_serving_prefill_dispatches_total")
        if group is None:
            if not slot.req.get("_requeued"):
                # a preempted-and-requeued request was admitted once
                # already — exactly-once accounting holds across
                # preempt → requeue → finish (its queue wait is the
                # ticket's, veles_serving_queue_wait_seconds)
                inc("veles_serving_admitted_total")
                self.admitted += 1
            asked = time.perf_counter()
            first = int(first)
            # the int() above synced the prefill dispatch: this step
            # boundary IS prefill-done and first-token time (host-side
            # stamps only — no device work rides on tracing)
            self._emptied("first_token", prefill, asked)
            slot.ticket.mark_prefill_done()
            slot.ticket.mark_first_token()
            self._set_tok(slot.idx, first)
            if slot.mode in _STEP_MODES:
                self._prefix_insert(slot)
            done = slot.record(first)
            slot.ticket.push_tokens([first])
            if done:
                self._finish(slot)
            return
        # beam: count the REQUEST once, expand the first top-W
        # hypotheses from the prefill logits (the same log_softmax +
        # top_k arithmetic nn/beam.py's first expansion runs)
        if slot is group.slots[0]:
            inc("veles_serving_admitted_total")
            self.admitted += 1
            logp0 = jax.nn.log_softmax(
                jnp.asarray(logits).astype(jnp.float32))
            top0, tok0 = jax.lax.top_k(logp0, self.beam_width)
            asked = time.perf_counter()
            group.cur = numpy.asarray(tok0, numpy.int32)
            group.scores = numpy.asarray(top0, numpy.float32)
            self._emptied("first_token", prefill, asked)
            eos = slot.eos_id
            group.finished = (group.cur == (-1 if eos is None
                                            else int(eos)))
            group.toks = numpy.zeros(
                (self.beam_width, slot.n_new), numpy.int32)
            group.toks[:, 0] = group.cur
            group.step = 0
            # the numpy.asarray(top-k) above synced the expansion:
            # the group's first hypothesis tokens exist NOW
            slot.ticket.mark_prefill_done()
            slot.ticket.mark_first_token()
            if slot.n_new == 1:
                self._finish_beam(group)

    # -- prefix sharing + chunked prefill -------------------------------------
    def _chunk_kernel_safe(self, bucket: int) -> bool:
        """True when the monolithic bucketed prefill would use the
        REFERENCE attention kernel for every block at this bucket —
        the chunk/suffix program always computes reference arithmetic
        over the gathered page view, so chunking (and adopting pages
        a chunked/reference prefill wrote) is only id-exact when the
        monolithic path would have picked the same kernel. Above the
        flash crossover the request simply rides the monolithic
        plane (same answers, no sharing)."""
        from ..ops.flash_attention import choose_flash
        d = self.stack["stem"].dim
        for blk in self.stack["blocks"]:
            if choose_flash(bucket, d // blk.n_heads):
                return False
        return True

    def _admit_chunked(self, slot) -> bool:
        """Prefix-cache adoption + chunked-prefill start for one plain
        decode-mode admission (already holding its worst-case page
        reservation). True when the slot now prefills chunk-by-chunk
        across ticks; False = the caller runs the monolithic bucketed
        prefill exactly as before."""
        if (self.prefix_cache is None and not self.prefill_chunk) \
                or self.quant_kv:
            return False
        if not self._chunk_kernel_safe(slot.bucket):
            return False
        t_p = slot.t_p
        P = self.page_size
        matched: List[int] = []
        if self.prefix_cache is not None:
            try:
                # raise = injected index loss, corrupt = injected
                # index rot: both DEGRADE to a shorter/empty match and
                # a full prefill — the token comparison inside match()
                # is the authority, so a corrupted index can never
                # adopt wrong pages
                corrupting = fire_fault("serve.prefix_match")
                matched = self.prefix_cache.match(slot.req["prompt"],
                                                  corrupt=corrupting)
            except FaultInjected as e:
                self.warning("%s: injected prefix-match fault (%s) — "
                             "degrading to a full prefill",
                             self.name, e)
                matched = []
            if matched:
                inc("veles_prefix_hits_total")
                self.prefix_requests += 1
            elif t_p // P:
                inc("veles_prefix_misses_total")
        if not matched and not self.prefill_chunk:
            return False
        # at least one token must prefill (the suffix pass emits the
        # first token's logits), so a FULL-prompt match re-computes
        # its last position — into a COPY of the last shared page
        # (copy-on-write), never into the shared page itself
        start = min(len(matched) * P, t_p - 1)
        k_full = start // P
        cow_src = matched[k_full] if len(matched) * P > start else None
        give_back: List[int] = []
        for i in range(k_full):
            give_back.append(slot.pages[i])
            slot.pages[i] = matched[i]
        slot.shared = k_full
        self._shared[slot.idx] = k_full
        if k_full:
            inc("veles_prefix_shared_pages_total", k_full)
        if cow_src is not None:
            fresh = self.page_pool.alloc(1)
            if fresh:
                import jax.numpy as jnp
                src = numpy.zeros(self.pages_per_slot, numpy.int32)
                dst = numpy.zeros(self.pages_per_slot, numpy.int32)
                src[0], dst[0] = cow_src, fresh[0]
                self._caches = self._program("pagecopy")(
                    jnp.asarray(src), jnp.asarray(dst), self._caches)
                give_back.append(slot.pages[k_full])
                slot.pages[k_full] = fresh[0]
                inc("veles_prefix_cow_copies_total")
            else:
                # no page to copy into: shorten the match to the block
                # boundary — the whole last block re-prefills
                start = k_full * P
            self.page_pool.free([cow_src])   # drop the match's ref
        self.page_pool.free(give_back)
        resume_k = int(slot.req.get("resume_k", 0) or 0)
        if resume_k:
            inc("veles_resume_tokens_total", resume_k)
        if not slot.req.get("_requeued"):
            # preempted-and-requeued rows were counted at their first
            # admission (see _admit) — never twice
            inc("veles_serving_admitted_total")
            self.admitted += 1
        slot.prefilled = start
        self._pos[slot.idx] = start
        self._temp[slot.idx] = slot.temperature
        return True

    def _prefix_insert(self, slot) -> None:
        """Cache a freshly prefilled prompt's FULL blocks so the next
        admission shares them. The slot's pages stay immutable for
        those positions (decode writes land at >= t_p, the write-back
        masks shared entries), so the index's reference outlives the
        slot safely. Skipped above the flash crossover: pages a flash
        prefill wrote must not seed reference-kernel suffixes."""
        if self.prefix_cache is None or slot.group is not None \
                or slot.mode not in _STEP_MODES:
            return
        if not self._chunk_kernel_safe(slot.bucket):
            return
        n_blocks = slot.t_p // self.page_size
        if n_blocks:
            self.prefix_cache.insert(
                slot.req["prompt"][:n_blocks * self.page_size],
                slot.pages[:n_blocks])

    def _prefill_tick(self, params) -> bool:
        """Advance every chunk-prefilling row by ONE chunk — the
        co-scheduling half of chunked prefill: admissions interleave
        with the decode step at ``prefill_chunk`` granularity instead
        of stalling it for a monolithic bucketed pass. Returns True
        when any chunk dispatched. The ``serve.prefill_chunk`` fault
        fires per chunk: an injected raise sheds THAT row 503 +
        Retry-After with a resume payload while co-tenants keep
        decoding."""
        import jax
        import jax.numpy as jnp
        pending = [s for s in self._active(_STEP_MODES)
                   if s.prefilled is not None]
        work = False
        for slot in pending:
            if self.scheduler.slots[slot.idx] is not slot:
                continue
            try:
                fire_fault("serve.prefill_chunk")
            except FaultInjected as e:
                # shed with a resume payload: nothing was emitted yet,
                # so the payload is the (possibly empty) progress — a
                # router retry redoes the prefill elsewhere
                slot.ticket.set_progress(self._emitted(slot))
                self._retire_slot(slot)
                if slot.ticket.fail(
                        "injected prefill-chunk fault: %s" % e,
                        code=503, retry_after=1.0):
                    inc("veles_shed_requests_total")
                continue
            t_p = slot.t_p
            p0 = int(slot.prefilled)
            C = self._chunk
            final = p0 + C >= t_p
            ids = numpy.zeros(C, numpy.int32)
            seg = slot.req["prompt"][p0:p0 + C]
            ids[:len(seg)] = seg
            resume_k = int(slot.req.get("resume_k", 0) or 0)
            # the PRNG carry matters only at the final chunk (it
            # samples the first token); resumed requests re-enter
            # their stream exactly like the monolithic prefill
            seed_key = (advanced_prng_key(slot.req.get("seed", 0),
                                          resume_k)
                        if final and resume_k
                        else jax.random.PRNGKey(
                            int(slot.req.get("seed", 0))))
            table_row = self._table_row(slot)
            with span("serving.prefill_chunk", slot=slot.idx, p0=p0,
                      chunk=C, t_p=t_p, final=int(final),
                      request_id=slot.ticket.request_id,
                      trace_id=slot.ticket.trace_id):
                first, self._keys, self._caches = \
                    self._program("pchunk")(
                        params, jnp.asarray(ids), numpy.int32(p0),
                        numpy.int32(t_p), numpy.int32(slot.idx),
                        numpy.float32(slot.temperature), seed_key,
                        table_row, numpy.int32(1 if final else 0),
                        self._keys, self._caches)
                chunk = self._dispatched
                self._push_held(overlapped=True)
            inc("veles_serving_prefill_dispatches_total")
            inc("veles_serving_prefill_positions_total", C)
            self.chunk_dispatches += 1
            work = True
            if not final:
                slot.prefilled = p0 + C
                self._pos[slot.idx] = min(p0 + C, t_p)
                continue
            slot.prefilled = None
            self._pos[slot.idx] = t_p
            asked = time.perf_counter()
            first = int(first)          # syncs the chunk dispatch
            self._emptied("first_token", chunk, asked)
            slot.ticket.mark_prefill_done()
            slot.ticket.mark_first_token()
            self._set_tok(slot.idx, first)
            self._prefix_insert(slot)
            done = slot.record(first)
            slot.ticket.push_tokens([first])
            if done:
                self._finish(slot)
        return work

    def _decodable(self) -> List:
        """Plain decode-mode rows whose prefill is complete — the rows
        THE decode step advances (chunk-prefilling rows join at their
        final chunk's step boundary)."""
        return [s for s in self._active(_STEP_MODES)
                if s.prefilled is None]

    # -- page growth -----------------------------------------------------------
    def _grow_or_shed(self, slots: List, need_fn) -> List:
        """Extend each slot's pages to cover ``need_fn(slot)``
        positions before the next dispatch. Admission reserved every
        row's own worst case, so this normally allocates NOTHING —
        it is the accounting safety net: a slot the allocator cannot
        cover (ledger drift, or an injected ``serve.page_alloc``
        fault) is SHED — 503 + Retry-After, pages freed, pool stays
        consistent — while the survivors keep decoding. Returns the
        surviving slots; their page-table rows are refreshed."""
        alive: List = []
        dead = set()
        for slot in slots:
            if id(slot) in dead:
                continue
            grown = self.scheduler.grow(slot, need_fn(slot))
            if grown:
                self._refresh_table_row(slot)
                alive.append(slot)
                continue
            victims = (slot.group.slots if slot.group is not None
                       else [slot])
            for v in victims:
                dead.add(id(v))
                if v in alive:
                    alive.remove(v)
                self._retire_slot(v)
            # ONE shed request however many hypothesis rows it held —
            # the admitted/retired counters are per request too, and
            # fail()'s first-terminal True keeps a ticket another
            # sweep already answered from counting twice
            if slot.mode in _STEP_MODES:
                victims[0].ticket.set_progress(
                    self._emitted(victims[0]))
            self._push_held()
            if victims[0].ticket.fail(
                    "serving page pool exhausted mid-decode",
                    code=503, retry_after=1.0):
                inc("veles_shed_requests_total")
        return alive

    # -- the chip known to be unfed ---------------------------------------------
    def _emptied(self, cause: str, number: int, asked: float) -> None:
        """A blocking read of what dispatch ``number`` gave, begun at
        ``asked`` (``perf_counter``), has just returned. Where that was
        the newest dispatch the device now has nothing queued: stamp
        the moment and the ``cause`` (a key of ``_UNFED``: the sync
        that emptied it); :meth:`_fed` observes the interval when the
        engine has next called a program. A read that returned at once
        found the result ready: the chip may have stood idle before
        the host looked, the interval is then a lower bound, and
        ``veles_serving_unfed_late_reads_total`` says how often."""
        if number != self._dispatched:
            return              # a later program is still queued
        now = time.perf_counter()
        if now - asked < _LATE_READ_S:
            inc("veles_serving_unfed_late_reads_total")
        if self._unfed is None:     # else known empty since earlier
            self._unfed = (now, cause)

    def _fed(self) -> None:
        """The engine's call of a compiled program (whatever
        :meth:`_program` returned) has returned, so the device has work
        again: number the dispatch and, where the chip was known to be
        unfed, observe for how long (the call's own length is the
        host's too: a program's arguments are a thousand leaves). On a
        plain tick that is one comparison: the step before is unread,
        so no stamp is set."""
        self._dispatched += 1
        if self._unfed is not None:
            (since, cause), self._unfed = self._unfed, None
            unfed = time.perf_counter() - since
            observe(_UNFED[cause], unfed)
            self.unfed_s[cause] += unfed
            # a finished interval for the ring and ``trace export``
            # (where spans are recorded); no live span, which would
            # cross ``serving.tick.prefill`` and ``.dispatch``
            emit("serving.unfed", time.time() - unfed, unfed, cause=cause)

    # -- a step's tokens, to the streams ---------------------------------------
    def _push_tokens(self, handed, overlapped: bool = False) -> None:
        """Queue each ``(ticket, tokens)`` of a decode step on its
        stream and count the events queued (buffered tickets have no
        queue and count nothing)."""
        pushed = sum(ticket.push_tokens(tokens)
                     for ticket, tokens in handed)
        if not pushed:
            return
        self.token_pushes += pushed
        inc("veles_serving_token_pushes_total", pushed)
        if overlapped:
            self.token_pushes_overlapped += pushed
            inc("veles_serving_token_pushes_overlapped_total", pushed)

    def _push_held(self, overlapped: bool = False,
                   phase: bool = False) -> None:
        """Hand the last step's tokens, kept by its ``emit`` for the
        rows that went on decoding, to their streams. Called with
        ``overlapped`` right after a dispatch to the device has been
        issued and before the host waits for it: every push wakes an
        HTTP handler thread that serialises and writes its SSE event
        under the interpreter lock, and there the tick thread is about
        to sleep without the lock for the whole program, where after
        ``emit`` it would queue for the lock behind all of them at
        every upload of the next ``prepare``. Called plainly wherever
        no dispatch follows or a terminal is about to be set (a tick
        that dispatches nothing, a shed, an abort, a hand-off, a
        preemption, the idle wait), so that a stream's order stays
        first token, each step's tokens, terminal; a row that is
        admitted or still prefilling has nothing kept (a preempted
        ticket's went out before it was queued again). What is kept
        belongs to the ticket, not to the slot. ``phase`` where no
        other phase's span is open: the push is then the second half
        of ``serving.tick.emit``, and no second is in two sums. Tick
        thread only; always leaves a NEW list behind (:meth:`_tick`
        tells by that whether a dispatch took the old one along)."""
        held, self._held = self._held, []
        if not held:
            return
        if phase:
            with span("serving.tick.emit"):
                self._push_tokens(held, overlapped)
        else:
            self._push_tokens(held, overlapped)

    # -- the decode chunk ------------------------------------------------------
    def _count_decode_dispatch(self, pages: int, ahead: bool = False,
                               seen: Optional[int] = None) -> None:
        """One decode dispatch (the step, a speculative round or a
        beam step) whose views were ``pages`` pages long; ``ahead``
        where the step before it was still unread; ``seen``, for a step
        that read through the page table, the positions its live rows
        could see, summed over rows and the chunk's iterations."""
        positions = pages * self.page_size
        self.decode_dispatches += 1
        self.view_positions += positions
        inc("veles_serving_decode_dispatches_total")
        inc("veles_serving_view_positions_total", positions)
        if ahead:
            self.steps_ahead += 1
            inc("veles_serving_steps_ahead_total")
        if seen is not None:
            self.paged_reads += 1
            self.paged_positions += seen
            self.paged_view_positions += (positions * self.max_slots
                                          * self.decode_block)
            inc("veles_serving_paged_reads_total")
            inc("veles_serving_paged_positions_total", seen)

    def _seen(self, mask) -> int:
        """The positions the masked-in rows of a step can see, reckoned
        from where each row stands (``_pos``): a row at ``p`` sees ``p +
        j + 1`` at the chunk's iteration ``j``, or the last ``window``
        of them where every paged block keeps one (the least)."""
        pos = self._pos[mask > 0].astype(numpy.int64)
        seen = pos[:, None] + numpy.arange(1, self.decode_block + 1)
        windows = [getattr(b, "window", None) or 0 for b, on in
                   zip(self.stack["blocks"], self._paged_blocks()) if on]
        if min(windows, default=0):
            seen = numpy.minimum(seen, min(windows))
        return int(seen.sum())

    def _decode(self, params) -> None:
        """Dispatch the plain step n+1, THEN read, record and emit step
        n: the chip runs a step while the host does all of that and
        prepares the next, and never waits for the host between two
        plain steps. Step n+1 needs nothing of step n that the host
        cannot know beforehand:

        - its tokens stay on the device (``_last``: what step n gave
          goes back in as it is, and its last row is merged inside the
          program with the rows the host has written since:
          :meth:`_set_tok`);
        - a masked-in row's position advances by ``decode_block`` at the
          dispatch, here as on the device, so the pages it needs, the
          view's rung and the table follow as they always did;
        - a row whose step in flight reaches its ``n_new`` is masked out
          of this one; it finishes when that step is read.

        A row that ends on its ``eos_id`` cannot be foreseen: it is in
        step n+1 too, and that row-step is DROPPED when read (the slot
        is no longer the row's: never recorded, never pushed). Its key
        advanced once more, and the slot's next prefill sets the key
        anew from its request's seed; its K/V row landed in a page the
        slot held when the step was dispatched, past the row's last
        position, which the next owner of the page (a prefill, a chunk,
        a copy: each dispatched later, so run later) writes before any
        read of it, as it does every position it comes to own. A page
        the prefix cache adopted holds whole blocks of a PROMPT: a
        decode row lies past its prompt, and the write-back sends a row
        inside a slot's shared pages to the sink besides, so no such
        page is ever written.

        The step dispatched here stays in flight until the next
        :meth:`_decode` or a :meth:`_drain`; at most one does."""
        import jax.numpy as jnp
        block = self.decode_block

        def need(s):
            return min(s.t_p + s.n_new, int(self._pos[s.idx]) + block)

        def going():
            # a row whose step in flight reaches its n_new is done but
            # for the reading
            ahead = {id(s) for s in self._flying[1]} if self._flying else ()
            return [s for s in self._decodable()
                    if len(s.tokens) + (block if id(s) in ahead else 0)
                    < s.n_new]

        with span("serving.tick.prepare"):
            rows = going()
            grows = self._flying is not None and any(
                pages_for(need(s), self.page_size) > len(s.pages)
                for s in rows)
        if grows:
            # growth past a reservation may shed the row, with what it
            # has recorded: in the serial order (admission reserved
            # each row's worst case, so this is the safety net's path)
            self._drain()
        flying = self._flying
        with span("serving.tick.prepare"):
            active = self._grow_or_shed(going() if grows else rows, need)
            if active:
                # the step runs at the shortest view that holds every
                # active row: the table's width IS the view's length,
                # and what lies beyond a row's ``pos`` weighs nought
                # anyway. A masked-out row may lie beyond it (result
                # discarded, write to the sink page)
                pages = view_rung(self.view_ladder,
                                  max(need(s) for s in active),
                                  self.page_size)
                mask = numpy.zeros(self.max_slots, numpy.int32)
                for slot in active:
                    mask[slot.idx] = 1
                fire_fault("serve.decode_step")
                # -1: the slot's token is the device's own (``_last``).
                # Copies, each: the host writes these arrays again
                # while the step is in flight, and an upload may alias
                # the memory it was given (the CPU backend's does)
                host = tuple(jnp.asarray(a) for a in (
                    numpy.where(self._fresh, self._tok, -1)
                    .astype(numpy.int32),
                    self._pos.copy(), self._temp.copy(), mask,
                    self._page_table[:, :pages].copy(),
                    self._shared.copy()))
                step = self._program("step", pages)
        if not active:
            self._drain()           # every row ends in the step in flight
            return
        with span("serving.decode_step", active=len(active),
                  chunk=block):
            with span("serving.tick.dispatch"):
                toks, self._keys, self._caches = step(
                    params, *host, self._last, self._keys, self._caches)
                self._last = toks
                # dropped here, not at the function's end: freeing a
                # device array yields the interpreter lock, and after
                # the emit phase every handler thread is waiting for it
                del host
            self._flying = (toks, active, self._dispatched)
            self._fresh[:] = False
            seen = (self._seen(mask) if any(self._paged_blocks())
                    else None)
            for slot in active:
                self._pos[slot.idx] += block
            self._count_decode_dispatch(pages, ahead=flying is not None,
                                        seen=seen)
            self._push_held(overlapped=True, phase=True)
            if flying is not None:
                self._land(flying)

    def _land(self, flying, drained: bool = False) -> None:
        """Wait for a dispatched step's tokens, record them and finish
        the rows they end; ``flying`` is what :meth:`_decode` left.
        ``drained`` where no step was dispatched after it: its read
        may then leave the chip with nothing queued."""
        toks, rows, number = flying
        if drained:
            asked = time.perf_counter()
        with span("serving.tick.device"):
            toks = numpy.asarray(toks)          # (decode_block, S)
        if drained:
            self._emptied("drain", number, asked)
        if self._tap_names:
            # the expert layers' counts came with the tokens: no
            # further dispatch, no further sync
            counts = toks[:, self.max_slots:].sum(axis=0)
            toks = toks[:, :self.max_slots]
            steptaps.publish({k: float(v)
                              for k, v in zip(self._tap_names, counts)})
        with span("serving.tick.emit"):
            # a row that ended on its eos_id in the step before was
            # retired when that was read: this row-step of it is dropped
            rows = [s for s in rows
                    if self.scheduler.slots[s.idx] is s]
            base_len = {id(s): len(s.tokens) for s in rows}
            finished: List = []
            for h in range(toks.shape[0]):
                still = [s for s in rows if s not in finished]
                if not still:
                    break
                for slot in still:
                    if slot.record(int(toks[h, slot.idx])):
                        finished.append(slot)
            for slot in rows:
                # a streaming row that goes on decoding hands this
                # chunk's tokens to its drain loop under the next
                # dispatch (_push_held) ...
                if slot.ticket.stream and slot not in finished:
                    self._held.append(
                        (slot.ticket, slot.tokens[base_len[id(slot)]:]))
            for slot in finished:
                # ... one that finishes, at once — before _finish's
                # terminal sentinel, so the wire order is
                # tokens-then-done and its slot is free for the next
                # admission
                self._push_tokens(
                    [(slot.ticket, slot.tokens[base_len[id(slot)]:])])
                self._finish(slot)

    def _drain(self) -> None:
        """Read, record and emit the step in flight, if there is one:
        the serial order, restored wherever something other than a
        plain step (or an admission and its prefill, which queue behind
        it on the device) comes next: a speculative round or a beam
        step on the same pool, a tick with no plain row left, and
        every ending (:meth:`_flush`). Decided from the engine's own
        state where it is called: a pool that drains every tick behaves
        as it did before a step was ever left in flight."""
        flying, self._flying = self._flying, None
        if flying is not None:
            with span("serving.decode_step", active=len(flying[1]),
                      chunk=self.decode_block):
                self._push_held(phase=True)     # an earlier step's first
                self._land(flying, drained=True)

    def _flush(self) -> None:
        """Nothing in flight and nothing kept: before a hand-off, an
        abort, a preemption, a shed, a change of weights, :meth:`stop`
        and the idle wait. A row's progress is then all it was ever
        dispatched for, and a stream has every token before its
        terminal is set."""
        self._drain()
        self._push_held()

    # -- the speculative round -------------------------------------------------
    def _spec_tick(self, params) -> None:
        """One on-device draft/verify round for every speculative row:
        the draft proposes ``spec_gamma`` tokens (a ``lax.scan`` of
        single-row steps over its paged view), the target verifies the
        whole window in ONE multi-position pass, and the accept rule
        emits up to gamma tokens per row — all rows advance by their
        own accepted lengths inside one fixed-shape dispatch."""
        import jax.numpy as jnp
        gamma = self.spec_gamma
        self._drain()       # a plain step of this tick is read first
        with span("serving.tick.prepare"):
            active = self._grow_or_shed(
                self._active(("speculative",)),
                lambda s: min(s.t_p + s.n_new + gamma + 1,
                              int(self._pos[s.idx]) + gamma))
            if not active:
                return
            smask = numpy.zeros(self.max_slots, numpy.int32)
            for slot in active:
                smask[slot.idx] = 1
            fire_fault("serve.decode_step")
            spec = self._program("spec")
            host = (jnp.asarray(self._tok), jnp.asarray(self._pos),
                    jnp.asarray(self._temp), jnp.asarray(smask),
                    jnp.asarray(self._page_table))
        with span("serving.spec_round", active=len(active),
                  gamma=gamma):
            with span("serving.tick.dispatch"):
                (out_vec, n_emit, acc, new_tok, self._keys,
                 self._caches, self._draft_caches) = spec(
                    params, self._draft_params, *host, self._keys,
                    self._caches, self._draft_caches)
                del host            # as in _decode
            self._push_held(overlapped=True, phase=True)
            asked = time.perf_counter()
            with span("serving.tick.device"):
                out_vec = numpy.asarray(out_vec)     # (S, gamma)
                n_emit = numpy.asarray(n_emit)
                acc = numpy.asarray(acc)
                new_tok = numpy.asarray(new_tok)
            # the serial order: the round just dispatched is read
            self._emptied("drain", self._dispatched, asked)
        self._count_decode_dispatch(self.pages_per_slot)
        inc("veles_serving_spec_rounds_total", len(active))
        with span("serving.tick.emit"):
            for slot in active:
                i = slot.idx
                emitted = int(n_emit[i])
                slot.rounds += 1
                slot.acc += int(acc[i])
                self._pos[i] += emitted
                self._set_tok(i, int(new_tok[i]))
                done = False
                base = len(slot.tokens)
                for t in out_vec[i, :emitted]:
                    if slot.record(int(t)):
                        done = True
                        break
                if done:                # as in _decode
                    self._push_tokens([(slot.ticket, slot.tokens[base:])])
                    self._finish(slot)
                elif slot.ticket.stream:
                    self._held.append((slot.ticket, slot.tokens[base:]))

    # -- the beam step ---------------------------------------------------------
    def _beam_tick(self, params) -> None:
        """One top-k step for every live beam group: each hypothesis
        row runs the single-row step over its paged view, the group
        expands W x V continuations, keeps the top W, and REORDERS the
        caches by surviving parent — a page-granular copy through the
        page tables, batched across groups in one fixed-shape
        dispatch. The arithmetic is nn/beam.py's (f32 log_softmax,
        frozen-eos lanes, flat top_k), so a pooled beam request's
        tokens equal its solo ``beam_generate`` exactly."""
        import jax.numpy as jnp
        self._drain()       # a plain step of this tick is read first
        with span("serving.tick.prepare"):
            groups = self.scheduler.active_beams()
            hyps = [s for g in groups for s in g.slots]
            alive_slots = self._grow_or_shed(
                hyps, lambda s: min(s.t_p + max(s.n_new - 1, 1),
                                    int(self._pos[s.idx]) + 1))
            groups = [g for g in groups
                      if all(s in alive_slots for s in g.slots)]
            if not groups:
                return
            G, W, P = self._beam_G, self.beam_width, self.pages_per_slot
            cur = numpy.zeros((G, W), numpy.int32)
            pos = numpy.zeros(G, numpy.int32)
            scores = numpy.full((G, W), -numpy.inf, numpy.float32)
            finished = numpy.zeros((G, W), bool)
            eosv = numpy.full(G, -1, numpy.int32)
            gmask = numpy.zeros(G, numpy.int32)
            tables_g = numpy.zeros((G, W, P), numpy.int32)
            for gi, group in enumerate(groups):
                cur[gi] = group.cur
                pos[gi] = group.t_p + group.step
                scores[gi] = group.scores
                finished[gi] = group.finished
                eosv[gi] = (-1 if group.slots[0].eos_id is None
                            else int(group.slots[0].eos_id))
                gmask[gi] = 1
                for wi, slot in enumerate(group.slots):
                    tables_g[gi, wi] = self._page_table[slot.idx]
            fire_fault("serve.decode_step")
            beam = self._program("beam")
            host = (jnp.asarray(cur), jnp.asarray(pos),
                    jnp.asarray(scores), jnp.asarray(finished),
                    jnp.asarray(eosv), jnp.asarray(gmask),
                    jnp.asarray(tables_g))
        with span("serving.beam_step", groups=len(groups),
                  width=W):
            with span("serving.tick.dispatch"):
                tok, parent, new_scores, new_fin, self._caches = \
                    beam(params, *host, self._caches)
                del host            # as in _decode
            asked = time.perf_counter()
            with span("serving.tick.device"):
                tok = numpy.asarray(tok)
                parent = numpy.asarray(parent)
                new_scores = numpy.asarray(new_scores)
                new_fin = numpy.asarray(new_fin)
            self._emptied("drain", self._dispatched, asked)
        self._count_decode_dispatch(self.pages_per_slot)
        inc("veles_serving_beam_steps_total", len(groups))
        with span("serving.tick.emit"):
            for gi, group in enumerate(groups):
                i = group.step + 1
                group.toks = group.toks[parent[gi]].copy()
                group.toks[:, i] = tok[gi]
                group.cur = tok[gi].copy()
                group.scores = new_scores[gi].copy()
                group.finished = new_fin[gi].copy()
                group.step = i
                for slot in group.slots:
                    self._pos[slot.idx] += 1
                if i >= group.slots[0].n_new - 1:
                    self._finish_beam(group)

    # -- retirement -------------------------------------------------------------
    def _retire_slot(self, slot) -> None:
        """Clear a row's host state and free its slot + pages. The
        page-table row is zeroed so a retired row's stale view can
        never alias pages the allocator hands to the next admission."""
        self._set_tok(slot.idx, 0)
        self._pos[slot.idx] = 0
        self._temp[slot.idx] = 0.0
        self._shared[slot.idx] = 0
        self._page_table[slot.idx, :] = 0
        self.scheduler.retire(slot)

    def _finish(self, slot) -> None:
        """Retire a row the moment it is done: free the slot and its
        pages (the next admission reuses them immediately) and answer
        the ticket."""
        # co-resident rows at retirement — the window plane's
        # batched_with response key, kept so the schema does not
        # depend on which plane served the request
        batched_with = max(0, self.scheduler.busy_count() - 1)
        self._retire_slot(slot)
        # _emitted prepends any tokens an in-engine QoS preemption
        # folded back into the prompt — the client's answer covers
        # the WHOLE generation, bit-identical to an uninterrupted run
        tokens = self._emitted(slot)
        result = {"tokens": tokens,
                  "batched_with": batched_with,
                  "engine": "continuous"}
        if slot.mode == "speculative":
            rounds = max(slot.rounds, 1)
            result["rounds"] = rounds
            result["acceptance"] = slot.acc / (rounds * self.spec_gamma)
        # count only a first-terminal answer, symmetric with every
        # shed path: a late _finish racing a stop()-side abort must
        # not push retired past admitted
        if slot.ticket.succeed(result):
            inc("veles_serving_retired_total")
            inc("veles_serving_tokens_total", len(tokens))
            self.retired += 1

    def _finish_beam(self, group) -> None:
        """Answer a beam request: rank hypotheses exactly like
        ``beam_generate`` (descending score; eos freezing already
        shaped the scores) and retire every hypothesis row."""
        order = numpy.argsort(-group.scores.astype(numpy.float64))
        best = int(order[0])
        for slot in group.slots:
            self._retire_slot(slot)
        batched_with = max(0, self.scheduler.busy_count() - 1)
        # gated on first-terminal like _finish: one retirement per
        # REQUEST, never re-counted by a late tick racing an abort
        if group.ticket.succeed({
                "tokens": [int(t) for t in group.toks[best]],
                "scores": [float(group.scores[i]) for i in order],
                "batched_with": batched_with,
                "engine": "continuous"}):
            inc("veles_serving_retired_total")
            inc("veles_serving_tokens_total", group.toks.shape[1])
            self.retired += 1

    def _abort_active(self, reason: str, code: int = 500,
                      retry_after: Optional[float] = None,
                      count_shed: bool = True) -> None:
        self._flush()
        answered = set()
        for slot in self.scheduler.active():
            # aborted rows hand their emitted-token prefix back on the
            # ticket BEFORE the terminal: the failure answer then
            # carries {resume: ...} and a failover retry re-enters the
            # decode at tokens_done instead of token 0 (plain decode
            # modes only — spec/beam retries restart from scratch)
            if slot.mode in _STEP_MODES \
                    and (slot.tokens
                         or slot.req.get("_qos_prefix")):
                slot.ticket.set_progress(self._emitted(slot))
            self._retire_slot(slot)
            if id(slot.ticket) not in answered:
                answered.add(id(slot.ticket))
                # one shed per REQUEST, not per hypothesis row — kept
                # like-for-like with admitted/retired accounting;
                # count only a first-terminal answer (an already-
                # answered ticket must not re-count)
                first = slot.ticket.fail(reason, code=code,
                                         retry_after=retry_after)
                if count_shed and first:
                    inc("veles_shed_requests_total")

    # -- drain-by-handoff ------------------------------------------------------
    def handoff(self, reason: str = "server draining; request handed "
                                    "off with resume progress",
                timeout: float = 30.0) -> int:
        """Hand every in-flight request back to its caller: at the
        NEXT step boundary each active ticket is settled 503 +
        Retry-After with its emitted-token prefix attached
        (``error_payload()`` then carries ``resume``), so a fleet
        router re-dispatches it elsewhere with ``resume_tokens`` and
        the drain's latency is bounded by one step boundary — never
        by the longest co-tenant generation. Queued (not yet
        admitted) tickets are shed the same 503 without progress.
        Runs on the tick thread (a progress snapshot can never race a
        decode dispatch); returns the number of requests handed back
        with progress. Safe on an idle or closing engine (0)."""
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
        """The tick-thread half of :meth:`handoff`. The ``serve.handoff``
        fault point fires once per in-flight ticket: an injected raise
        degrades THAT ticket to a plain 503 shed (no resume progress —
        its retry re-decodes from scratch), never blocks the drain."""
        self._flush()
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
            # every hypothesis/co-tenant row of the ticket retires
            self._retire_slot(slot)
        # queued-but-unadmitted tickets leave with the same answer
        # (no progress — nothing was decoded for them yet)
        shed = self.scheduler.drain(reason, code=503, retry_after=1.0)
        if shed:
            inc("veles_shed_requests_total", shed)
        return handed

    # -- jitted programs -------------------------------------------------------
    def _program(self, kind: str, bucket: Optional[int] = None):
        """The program of ``kind``, built at the first call that
        needs it. ``bucket`` is a prefill's padded prompt length or
        the step's view length in pages (``view_ladder``): the step's
        builder takes no length, its program's shapes follow the
        table it is first called with, one executable a key."""
        key = (kind, bucket)
        prog = self._progs.get(key)
        if prog is None:
            # in artifact mode the base-plane programs were installed
            # at start(); spec/beam/draft programs always build live
            builders = {"prefill": self._build_prefill,
                        "dprefill": self._build_draft_prefill,
                        "step": self._build_decode,
                        "spec": self._build_spec_round,
                        "beam": self._build_beam_step,
                        "pchunk": self._build_prefill_chunk,
                        "pagecopy": self._build_page_copy}
            jitted = (builders[kind](bucket)
                      if kind in ("prefill", "dprefill")
                      else builders[kind]())
            prog = self._progs[key] = self._instrument_live(jitted)
        return prog

    def _installed(self, call):
        """An artifact-installed program as the engine calls it: every
        call is a dispatch to :meth:`_fed` and, on a sharded engine,
        one ``veles_tp_dispatches_total`` — the TP observability seam
        (the live path does both inside ``_instrument_live``)."""
        import functools
        tp_on = self.tp > 1

        @functools.wraps(call)
        def fed(*args, **kwargs):
            if tp_on:
                inc("veles_tp_dispatches_total")
            out = call(*args, **kwargs)
            self._fed()
            return out
        return fed

    def _instrument_live(self, jitted):
        """Wrap a live jitted program: every call counts one
        ``veles_decode_dispatches_total`` (the round-5 regression
        lock's counter — same contract as
        ``sampling._count_decode_dispatches``). The first call
        explicitly lowers+compiles (``jit.lower(...).compile()``, the
        ``accelerated.cost_of`` pattern) and installs the compiled
        executable for every later dispatch, so
        ``veles_serving_compile_seconds_total`` brackets ONLY the
        trace+compile — the cold-start cost the AOT artifact path
        exists to delete — never the first dispatch's execution.
        Engine programs are fixed-shape, so one compile per program is
        exact, not a heuristic."""
        box: Dict[str, object] = {}
        tp_on = self.tp > 1

        def dispatch(*args):
            inc("veles_decode_dispatches_total")
            if tp_on:
                # the TP observability seam: every dispatch that ran
                # through a shard_mapped program (it never moves solo:
                # test_feature_off_counters_stay_zero)
                inc("veles_tp_dispatches_total")
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
                # building a program is set-up, not the host's lateness
                self._unfed = None
            out = exe(*args)
            self._fed()
            return out

        dispatch._jitted = jitted
        # the compiled executable, once built (tests read its HLO)
        dispatch.compiled = lambda: box.get("exe")
        return dispatch

    # -- AOT artifact (export/serve_artifact.py) ------------------------------
    def stack_signature(self) -> Dict:
        """Geometry the exported programs are shape-committed to: the
        abstract spec of (params tree, page pool) plus every serving
        knob the base-plane programs bake in. Export stamps it into
        the artifact; load refuses on any mismatch — a program traced
        for different shapes would fail deep inside XLA with an opaque
        error (or worse, run on reinterpreted buffers). Purely
        abstract: under ``quant_weights`` the int8 spec comes from
        ``quantize_params_spec``, so building a signature never runs
        (or counts) a calibration pass."""
        import jax

        def spec(tree):
            return jax.tree_util.tree_map(
                lambda a: [list(a.shape), str(a.dtype)], tree)

        params = params_of(self.wf)
        if self.quant_weights:
            from ..quant import quantize_params_spec
            sig_params = quantize_params_spec(params)
        else:
            sig_params = params
        stem, blocks = self.stack["stem"], self.stack["blocks"]
        d = stem.dim
        pools = []
        for blk in blocks:
            if hasattr(blk, "cache_geometry"):
                pools.append(list(self._geometry(blk)
                                  or map(list, self._state(blk))))
                continue
            bkv = getattr(blk, "n_kv_heads", blk.n_heads)
            pools.append([bkv, d // blk.n_heads])
        return {
            "params": spec(sig_params),
            "pools": pools,
            "pool_dtype": str(self._pool_dtype(params)),
            "max_slots": self.max_slots,
            "buckets": list(self.buckets),
            "max_context": self.max_context,
            "decode_block": self.decode_block,
            # paged-pool geometry: page tables are now program inputs,
            # so the page count and size are shape commitments too
            "page_size": self.page_size,
            "pages": self.pages,
            "pages_per_slot": self.pages_per_slot,
            "quant_weights": bool(self.quant_weights),
            "quant_kv": bool(self.quant_kv),
            # the request plane's shape commitments: the decode step
            # takes the per-slot shared-page mask since v3, and the
            # chunk width shapes the (live-built) suffix program — an
            # artifact exported under other knobs refuses cleanly
            "prefix_cache": self.prefix_cache is not None,
            "prefill_chunk": int(self.prefill_chunk),
            # v5: sharded programs are committed to a mesh shape — an
            # artifact exported for one slice width refuses on another
            # (and every v4 artifact, lacking the key, refuses too)
            "tp": int(self.tp),
            "mesh": ([["model", self.tp]] if self.tp > 1 else []),
            # v6: the decode step takes the tokens of the step before
            # back as it gave them (the host's row says -1 where they
            # hold); an artifact without the key has the older step
            "step_tokens": "device",
        }

    def _load_artifact(self) -> bool:
        """Install the artifact's pre-exported programs into
        ``_progs``. Any failure — unreadable package, version/geometry
        mismatch, corrupt program bytes, injected ``artifact.load``
        fault — logs a counted warning and leaves the engine on live
        jit: a bad artifact degrades startup latency, never
        availability."""
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
        # the artifact holds the step at the whole view and no other
        # length: the ladder is that one rung, nothing compiles
        self.view_ladder = (self.pages_per_slot,)
        for key, call in programs.items():
            # artifact-installed programs are the same shard_mapped
            # executables the live path builds, so they feed the TP
            # dispatch seam too — otherwise a sharded engine serving
            # from an artifact under-reports veles_tp_dispatches_total
            self._progs[key] = self._installed(
                _count_decode_dispatches(call))
        self.artifact_mode = True
        inc("veles_artifact_loads_total")
        self.info("%s: AOT artifact loaded from %s (%d programs; zero "
                  "jit compiles on the serving path)", self.name,
                  self.artifact, len(programs))
        return True

    # -- paged gather/scatter helpers (trace-time) ----------------------------
    def _view(self, payload, table_row):
        """Gather one slot's logical cache view through its page-table
        row: (pages, page_size, kv, hd) + (P,) -> (P*page_size, kv,
        hd). Unallocated entries point at the sink page; its garbage
        rows sit beyond the causal mask until a write claims them."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("page_gather"):
            pages = jnp.take(payload, table_row, axis=0, mode="clip")
            return pages.reshape((-1,) + payload.shape[2:])

    def _row_targets(self, tables, pos, mask):
        """Per-slot (page id, in-page offset) for writing position
        ``pos`` — masked rows are pointed at the sink page, so one
        batched scatter serves every lane of the fixed-shape step."""
        import jax.numpy as jnp
        P = tables.shape[1]
        pg_idx = jnp.clip(pos // self.page_size, 0, P - 1)
        pg = jnp.take_along_axis(tables, pg_idx[:, None], axis=1)[:, 0]
        pg = jnp.where(mask > 0, pg, 0)
        off = jnp.clip(pos % self.page_size, 0, self.page_size - 1)
        return pg, off

    def _paged_row_step(self, blk, p, kp, vp, tp=1, tp_axis=None):
        """The vmap'able single-row paged decode body shared by THE
        decode step and the spec round's draft proposal: gather the
        row's logical view through its page-table row, advance one
        position with ``_block_step``, return ``(y, k_new, v_new)`` —
        only the newly written position's rows, for the batched page
        scatter. One definition so the gather/write discipline cannot
        diverge between decode modes."""
        import jax.numpy as jnp

        def row(x_row, trow, pos_row):
            ck = self._view(kp, trow)
            cv = self._view(vp, trow)
            y, ck2, cv2 = _block_step(blk, p, x_row[None, None, :],
                                      ck[None], cv[None], pos_row,
                                      tp=tp, tp_axis=tp_axis)
            return (y[0, 0],
                    jnp.take(ck2[0], pos_row, axis=0, mode="clip"),
                    jnp.take(cv2[0], pos_row, axis=0, mode="clip"))

        return row

    def _paged_read(self, blk) -> bool:
        """THE predicate of the float step's attention read for ``blk``:
        through the page table, in one Pallas call that reads the live
        pages alone (``ops/paged_attention.py``), where the chip is a
        TPU, the pool is a float pool, the kernel fits the block
        (:meth:`_kernel_fits`) and compiles for its rows; elsewhere
        through a gathered view. Under ``--serve-tp`` each shard's
        kernel reads its own KV heads of the pool."""
        import jax
        from ..ops import paged_attention
        if self.quant_kv or not self._kernel_fits(blk):
            return False
        kv, hd, vd, _ = self._geometry(blk)
        dtype = self._caches[self.stack["blocks"].index(blk)][0].dtype
        return (jax.default_backend() == "tpu"
                and paged_attention.supported(hd, self.page_size,
                                              kv // self.tp, dtype, vd))

    def _kernel_fits(self, blk) -> bool:
        """Whether the paged read computes ``blk``'s attention: its K and
        V lie in pages (a standing block, or a hybrid block's full layer:
        not a window layer's ring, not a delta-rule layer's state, which
        are a slot's row already) and it keeps no learned sink, which the
        kernel's softmax does not merge."""
        return (self._slot_row(blk) is None
                and not getattr(blk, "has_sink", False))

    def _paged_blocks(self) -> Tuple[bool, ...]:
        """:meth:`_paged_read` of each block, decided once the pool is
        there (the step's builder and the dispatch's counters ask)."""
        if self._paged is None:
            self._paged = tuple(self._paged_read(b)
                                for b in self.stack["blocks"])
        return self._paged

    def _paged_attend(self, blk, kp, vp, tables, chunk_pos, j):
        """``_block_step``'s attention read for ``blk`` at the chunk's
        iteration ``j``, every row at once (a hybrid block's
        ``serve_step`` takes the same): the pool's positions before the
        chunk's first (``chunk_pos``), through ``tables``, and the rows
        that the chunk has made so far, the row's own among them, which
        its carry holds (S, decode_block, kv, width) until the chunk is
        written back. ``scale`` is the block's, where its q and keys come
        padded beyond their width; ``name`` the kernel's device operation's
        (``ops/paged_attention.py``), the block's scope where the read is
        to be timed under it. A masked-out row's result is discarded."""
        import jax.numpy as jnp
        from ..ops import matmul_precision
        from ..ops.paged_attention import KERNEL, paged_decode_attention
        from jax import lax
        window = getattr(blk, "window", None)
        made = jnp.arange(self.decode_block)
        end = jnp.minimum(chunk_pos, tables.shape[1] * self.page_size)
        # the products' operands as the configuration states them:
        # Precision.DEFAULT's bfloat16 on the chip (None), else float32
        operand = (None if matmul_precision() == lax.Precision.DEFAULT
                   else jnp.float32)

        def attend(q, k, v, rows_k, rows_v, pos, scale=None, name=KERNEL):
            rows_k = rows_k.at[:, j].set(k[:, 0].astype(rows_k.dtype))
            rows_v = rows_v.at[:, j].set(v[:, 0].astype(rows_v.dtype))
            ok = jnp.broadcast_to(made <= j, rows_k.shape[:2])
            start = jnp.zeros_like(pos)
            if window:
                ok = ok & (chunk_pos[:, None] + made > pos[:, None] - window)
                start = jnp.maximum(pos - window + 1, 0)
            o = paged_decode_attention(q[:, 0], kp, vp, tables, start, end,
                                       rows_k, rows_v, ok, scale=scale,
                                       operand_dtype=operand, name=name)
            return o[:, None], rows_k, rows_v

        return attend

    def _scatter_prompt(self, pool, rows, table_row, bucket, scales=None):
        """Write a bucket's prefill K or V rows page-wise into the
        pool: (bucket, kv, hd) padded up to whole pages and scattered
        at this slot's page ids (a static-length index slice — the
        program stays fixed-shape). ``scales`` rides along for the
        int8 pool's per-page sidecar."""
        import jax
        import jax.numpy as jnp
        n_pages = -(-bucket // self.page_size)
        pad = n_pages * self.page_size - bucket
        with jax.named_scope("page_writeback"):
            if pad:
                rows = jnp.pad(rows,
                               ((0, pad),) + ((0, 0),) * (rows.ndim - 1))
            rows = rows.reshape((n_pages, self.page_size)
                                + rows.shape[1:])
            pool = pool.at[table_row[:n_pages]].set(rows)
            if scales is None:
                return pool
            if pad:
                scales = jnp.pad(scales, ((0, pad),))
            return pool, scales.reshape(n_pages, self.page_size)

    def _ring_prompt(self, ring, rows, t_p, slot):
        """Write a prompt's last rows into ``slot``'s ring of a window
        layer: ``ring`` (slots, R, kv, hd), ``rows`` (bucket, kv, hd).
        Index j takes the newest position under ``t_p`` that is
        congruent to j modulo R (the step reads and writes a position at
        its value modulo R); where the prompt is shorter than the ring
        the indices past it take row 0 and stay unseen, since the step's
        mask knows which position an index can hold."""
        import jax
        import jax.numpy as jnp
        length = ring.shape[1]
        with jax.named_scope("ring_writeback"):
            last = t_p - 1
            held = last - (last - jnp.arange(length)) % length
            kept = jnp.take(rows, jnp.clip(held, 0, rows.shape[0] - 1),
                            axis=0)
            return jax.lax.dynamic_update_slice(
                ring, kept[None].astype(ring.dtype), (slot, 0, 0, 0))

    # -- program builders ------------------------------------------------------
    def _build_prefill(self, bucket: int):
        """One program per bucket: pad-to-``bucket`` full-window pass
        through ``_block_prefill`` writing K/V page-wise into this
        slot's pages, plus the request's FIRST sampled token, the
        last-real-position logits (the beam expansion's input) and the
        slot's private PRNG carry. Under ``quant_weights`` the program
        takes the int8 parameter tree and dequantizes at its head
        (XLA fuses the ``q·s`` into each consuming matmul); under
        ``quant_kv`` the computed float rows are quantized once —
        per-position scales in the per-page sidecars — before the
        pool write."""
        import jax
        import jax.numpy as jnp
        from ..ops import matmul_precision
        stack = self.stack
        stem, pos_emb = stack["stem"], stack["pos_emb"]
        blocks, head = stack["blocks"], stack["head"]
        prec = matmul_precision()
        d = stem.dim
        quant_w, quant_kv = self.quant_weights, self.quant_kv
        tp, tp_axis = self.tp, self._tp_axis
        own = bool(self._own_blocks)
        held = {b.name: self._slot_row(b) for b in blocks}
        norm = stack["norm"]

        def prefill(params, ids, t_p, slot, temp, seed_key, table_row,
                    keys, caches):
            if quant_w:
                # reconstruct in the model's own float dtype (the
                # never-quantized stem table's — read at trace time),
                # not a hard f32: a bf16 model's quantized engine must
                # run the same-dtype matmuls the float engine does
                from ..quant import dequantize_params
                params = dequantize_params(
                    params, dtype=params[stem.name]["table"].dtype)
            x = _embed_prompt(stem, pos_emb, params, ids, tp=tp,
                              tp_axis=tp_axis)
            x, blk_caches = _prefill_blocks(
                blocks, params, x, bucket, d, tp=tp, tp_axis=tp_axis,
                live=(jnp.arange(bucket) < t_p) if own else None)
            new_caches = []
            for blk, (ck, cv), pool in zip(blocks, blk_caches, caches):
                if held[blk.name] == "state":
                    # the slot's row whole: a reused slot keeps nothing
                    with jax.named_scope(blk.name), \
                            jax.named_scope("state_write"):
                        new_caches.append(tuple(
                            jax.lax.dynamic_update_slice(
                                part, rows.astype(part.dtype),
                                (slot,) + (0,) * (part.ndim - 1))
                            for part, rows in zip(pool, (ck, cv))))
                    continue
                if held[blk.name]:
                    new_caches.append(tuple(
                        self._ring_prompt(ring, rows[0], t_p, slot)
                        for ring, rows in zip(pool, (ck, cv))))
                    continue
                # pad rows land in the pages too; they are causal-
                # masked for every real position and the decode steps
                # rewrite position p before the read mask reaches it
                if quant_kv:
                    from ..quant import quantize_rows_int8
                    kq, vq, ks, vs = pool
                    qk, sk = quantize_rows_int8(ck)
                    qv, sv = quantize_rows_int8(cv)
                    kq, skp = self._scatter_prompt(kq, qk[0],
                                                   table_row, bucket,
                                                   sk[0])
                    vq, svp = self._scatter_prompt(vq, qv[0],
                                                   table_row, bucket,
                                                   sv[0])
                    n_pages = -(-bucket // self.page_size)
                    ks = ks.at[table_row[:n_pages]].set(skp)
                    vs = vs.at[table_row[:n_pages]].set(svp)
                    new_caches.append((kq, vq, ks, vs))
                else:
                    kp, vp = pool
                    kp = self._scatter_prompt(kp, ck[0], table_row,
                                              bucket)
                    vp = self._scatter_prompt(vp, cv[0], table_row,
                                              bucket)
                    new_caches.append((kp, vp))
            x_last = jnp.take(x[0], t_p - 1, axis=0, mode="clip")
            logits = _head_logits(head, params, x_last, prec,
                                  tp_axis=tp_axis, norm=norm)
            with jax.named_scope("sample"):
                k2 = jax.random.split(seed_key)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                samp = jax.random.categorical(
                    k2[1], logits / jnp.maximum(temp, _TEMP_EPS)
                ).astype(jnp.int32)
                first = jnp.where(temp > 0, samp, greedy)
                keys = jax.lax.dynamic_update_slice(
                    keys, k2[0][None], (slot, 0))
            return first, logits, keys, tuple(new_caches)

        if tp <= 1:
            return self._finalize(prefill, donate=(7, 8))
        from jax.sharding import PartitionSpec as P
        cs = self._caches_pspec(self.stack)
        pspec = self._params_pspec(self.stack, params_of(self.wf))
        return self._finalize(
            prefill, donate=(7, 8),
            in_specs=(pspec, P(), P(), P(), P(), P(), P(), P(), cs),
            out_specs=(P(), P(), P(), cs))

    def _build_draft_prefill(self, bucket: int):
        """The draft model's prompt pass for a speculative admission:
        writes the draft's K/V pages through the SAME page-table row
        the target uses (the slot's pages index both pools), emits
        nothing."""
        import jax
        stack = self.draft_stack
        stem, pos_emb = stack["stem"], stack["pos_emb"]
        blocks = stack["blocks"]
        d = stem.dim
        tp, tp_axis = self.tp, self._tp_axis

        def dprefill(params_d, ids, table_row, dcaches):
            x = _embed_prompt(stem, pos_emb, params_d, ids, tp=tp,
                              tp_axis=tp_axis)
            _x, blk_caches = _prefill_blocks(blocks, params_d, x,
                                             bucket, d, tp=tp,
                                             tp_axis=tp_axis)
            new_caches = []
            for (ck, cv), (kp, vp) in zip(blk_caches, dcaches):
                kp = self._scatter_prompt(kp, ck[0], table_row, bucket)
                vp = self._scatter_prompt(vp, cv[0], table_row, bucket)
                new_caches.append((kp, vp))
            return tuple(new_caches)

        if tp <= 1:
            return self._finalize(dprefill, donate=(3,))
        from jax.sharding import PartitionSpec as P
        cs = self._caches_pspec(stack)
        pspec = self._params_pspec(stack, params_of(self.draft))
        return self._finalize(
            dprefill, donate=(3,),
            in_specs=(pspec, P(), P(), cs), out_specs=cs)

    def _build_decode(self):
        """THE decode step: ``decode_block`` scan iterations of the
        vmapped single-row ``_block_step`` over every slot's gathered
        page view — one fixed shape, compiled exactly once a view
        length (``view_ladder``; the view is as long as the table
        handed in is wide); page
        tables arrive as DATA. The float pool gathers each row's view
        ONCE per chunk, carries it through the scan (the inner step
        runs at dense-pool cost) and writes into the pool only the
        rows the chunk made: ``decode_block`` rows a slot, in one
        batched row scatter per block and tensor at chunk end,
        through the same ``_row_targets`` the int8 pool and the
        speculative round write through (a masked row, a row inside a
        shared page and a row past the view's end target the sink
        page). Where :meth:`_paged_read` says so (a TPU, a block whose
        K and V lie in pages), a block gets no view: its attention reads
        the pool through the tables in one Pallas call, beside the rows
        the chunk has made so far, which the scan carries until that
        same write-back. Per-row sampling draws from each slot's private
        key stream, advanced ONLY for masked-in rows, so a row's
        noise is a pure function of its request's seed whatever other
        modes share the pool. Under ``quant_kv`` each scan iteration
        dequantizes the row's int8 view for the attention read, runs
        the SAME ``_block_step``, then quantizes only the one newly
        written position with its own fresh scale — previously
        written rows are never re-scaled, so there is no error
        accumulation across steps. The chunk's tokens, which the host
        reads, go back into the NEXT call of the program as they are
        (``last``; never donated), and it takes a slot's token from
        their last row wherever the host's row says -1
        (:meth:`_decode`): one program a tick, and no step waits for
        the host to have read the step before it."""
        import jax
        import jax.numpy as jnp
        from ..ops import matmul_precision
        stack = self.stack
        stem, pos_emb = stack["stem"], stack["pos_emb"]
        blocks, head = stack["blocks"], stack["head"]
        prec = matmul_precision()
        quant_w, quant_kv = self.quant_weights, self.quant_kv
        tp, tp_axis = self.tp, self._tp_axis
        # a window layer's ring and a delta-rule layer's state are each
        # every slot's row of the pool: no table, no gather
        held = {b.name: self._slot_row(b) for b in blocks}
        norm = stack["norm"]
        tap_names = self._tap_names
        paged = self._paged_blocks()

        def embed_rows(params, tok, pos):
            from ..nn.sampling import _embed_ids
            x = _embed_ids(stem, params, tok, tp=tp, tp_axis=tp_axis)
            if pos_emb is not None:
                x = x + jnp.take(params[pos_emb.name]["table"], pos,
                                 axis=0, mode="clip")
            return x                            # (S, D)

        def step(params, tok, pos, temp, mask, tables, shared, last,
                 keys, caches):
            if quant_w:
                from ..quant import dequantize_params
                params = dequantize_params(
                    params, dtype=params[stem.name]["table"].dtype)
            # a slot's token is the host's where the host has written
            # one since the last step (it sends -1 elsewhere), else the
            # last row this program gave in the step before: ``last`` is
            # that step's ``toks`` as they were returned, a masked-out
            # slot's token carried through them
            tok = jnp.where(tok >= 0, tok, last[-1, :tok.shape[0]])

            def sample_next(tok, pos, keys, x):
                logits = _head_logits(head, params, x, prec,
                                      tp_axis=tp_axis, norm=norm)  # (S, V)
                # _split_rows IS the id-exactness contract: the same
                # carry/subkey convention solo and batched generate
                # use — advanced only for rows this step owns, so
                # co-tenant spec rows keep their own stream positions
                with jax.named_scope("sample"):
                    keys2, subs = _split_rows(keys)
                    keys = jnp.where(mask[:, None] > 0, keys2, keys)
                    greedy = jnp.argmax(logits,
                                        axis=-1).astype(jnp.int32)
                    samp = jax.vmap(jax.random.categorical)(
                        subs,
                        logits / jnp.maximum(temp, _TEMP_EPS)[:, None]
                    ).astype(jnp.int32)
                    nxt = jnp.where(temp > 0, samp, greedy)
                    nxt = jnp.where(mask > 0, nxt, tok)
                    return nxt, pos + (mask > 0), keys

            if not quant_kv:
                # CHUNK-VIEW formulation: gather each row's logical
                # view ONCE per chunk and carry it through the scan
                # (the per-iteration math is then exactly the dense
                # pool's — no gathers on the inner step). The views
                # are scratch: each iteration also yields the ONE row
                # it wrote per slot, block and tensor, and only those
                # rows are scattered into the pool at chunk end — the
                # pages a step did not write are never rewritten. A
                # block read through the page table (``paged``) gets
                # no view: the scan carries the chunk's rows it made,
                # which the pool holds only once they are written back
                views = []
                for blk, (kp, vp), on in zip(blocks, caches, paged):
                    if held[blk.name]:
                        # a window layer's ring (a delta-rule layer's
                        # state) IS every slot's view: no table, no
                        # gather; the step writes into it
                        views.append((kp, vp))
                        continue
                    if on:
                        views.append(tuple(
                            jnp.zeros((tok.shape[0], self.decode_block)
                                      + pool.shape[2:], pool.dtype)
                            for pool in (kp, vp)))
                        continue
                    views.append((
                        jax.vmap(lambda t, kp=kp: self._view(kp, t))(
                            tables),
                        jax.vmap(lambda t, vp=vp: self._view(vp, t))(
                            tables)))         # each (S, T, kv, hd)

                def body(carry, j):
                    tok, pos, keys, vws = carry
                    x = embed_rows(params, tok, pos)
                    new_vws, rows = [], []
                    # what the blocks' expert layers count of this
                    # step (nothing, for a stack without one)
                    with steptaps.collecting() as counted:
                        for blk, (ck, cv), (kp, vp), on in zip(
                                blocks, vws, caches, paged):
                            p = params[blk.name]
                            if hasattr(blk, "serve_step"):
                                # the block's own one-position step, every
                                # row at once (its experts route the batch);
                                # a paged one reads through the tables
                                x, ck, cv, kn, vn = blk.serve_step(
                                    p, x, ck, cv, pos, mask > 0,
                                    attend=self._paged_attend(
                                        blk, kp, vp, tables, chunk_pos, j)
                                    if on else None)
                                new_vws.append((ck, cv))
                                rows.append((kn, vn))
                                continue
                            if on:
                                y, ck, cv = _block_step(
                                    blk, p, x[:, None, :], ck, cv, pos,
                                    tp=tp, tp_axis=tp_axis,
                                    attend=self._paged_attend(
                                        blk, kp, vp, tables, chunk_pos, j))
                                x = y[:, 0]
                                new_vws.append((ck, cv))
                                rows.append((ck[:, j], cv[:, j]))
                                continue

                            def row(x_row, ck_row, cv_row, pos_row,
                                    blk=blk, p=p):
                                y, ck2, cv2 = _block_step(
                                    blk, p, x_row[None, None, :],
                                    ck_row[None], cv_row[None], pos_row,
                                    tp=tp, tp_axis=tp_axis)
                                return (y[0, 0], ck2[0], cv2[0],
                                        jnp.take(ck2[0], pos_row, axis=0,
                                                 mode="clip"),
                                        jnp.take(cv2[0], pos_row, axis=0,
                                                 mode="clip"))

                            x, ck, cv, kn, vn = jax.vmap(row)(
                                x, ck, cv, pos)
                            new_vws.append((ck, cv))
                            rows.append((kn, vn))       # each (S, kv, hd)
                    nxt, pos2, keys = sample_next(tok, pos, keys, x)
                    out = nxt
                    if tap_names:
                        # the expert layers' counts ride the tokens' own
                        # array to the host: whole numbers, one column
                        # each after the slots'
                        out = jnp.concatenate([nxt, jnp.round(jnp.stack(
                            [counted[k] for k in tap_names])).astype(
                                jnp.int32)])
                    return ((nxt, pos2, keys, tuple(new_vws)),
                            (out, pos, tuple(rows)))

                chunk_pos = pos
                (_, _, keys, vws), (toks, wpos, rows) = jax.lax.scan(
                    body, (tok, pos, keys, tuple(views)),
                    jnp.arange(self.decode_block))
                # row write-back, (decode_block, S) targets at once.
                # Sent to the sink page instead of the pool: a masked
                # row; a row inside one of its slot's leading SHARED
                # (prefix-adopted) pages — a shared page is
                # structurally read-only here, so a retired (or live)
                # writer can never mutate one; and a row at or past
                # the view's end (a finished row's overshoot inside
                # its last chunk), which _row_targets would clip onto
                # the slot's last real row.
                with jax.named_scope("page_writeback"):
                    ok = ((mask[None, :] > 0)
                          & (wpos // self.page_size >= shared[None, :])
                          & (wpos < tables.shape[1] * self.page_size))
                    pg, off = jax.vmap(
                        self._row_targets, in_axes=(None, 0, 0))(
                            tables, wpos, ok)
                    new_caches = []
                    for blk, (kp, vp), (kn, vn), view in zip(
                            blocks, caches, rows, vws):
                        if held[blk.name]:
                            # the ring (the state) the scan carried has
                            # the rows
                            new_caches.append(view)
                            continue
                        new_caches.append((kp.at[pg, off].set(kn),
                                           vp.at[pg, off].set(vn)))
                return toks, keys, tuple(new_caches)

            # int8 pool: per-step gather/scatter — the read has to
            # dequantize row-wise anyway, and only the one new
            # position may be (re)quantized per step (no error
            # accumulation), so there is no whole-view carry to win
            def body(carry, _):
                tok, pos, keys, caches = carry
                x = embed_rows(params, tok, pos)
                new_caches = []
                for blk, pool in zip(blocks, caches):
                    p = params[blk.name]
                    from ..quant import (dequantize_rows_int8,
                                         quantize_rows_int8)
                    kq, vq, ks, vs = pool

                    def rowq(x_row, trow, pos_row, blk=blk, p=p,
                             kq=kq, vq=vq, ks=ks, vs=vs):
                        ck = dequantize_rows_int8(
                            self._view(kq, trow),
                            self._view(ks, trow),
                            dtype=x_row.dtype)
                        cv = dequantize_rows_int8(
                            self._view(vq, trow),
                            self._view(vs, trow),
                            dtype=x_row.dtype)
                        y, ck2, cv2 = _block_step(
                            blk, p, x_row[None, None, :],
                            ck[None], cv[None], pos_row)
                        # quantize ONLY the newly written position
                        k_new = jnp.take(ck2[0], pos_row, axis=0,
                                         mode="clip")
                        v_new = jnp.take(cv2[0], pos_row, axis=0,
                                         mode="clip")
                        qk, sk = quantize_rows_int8(k_new[None])
                        qv, sv = quantize_rows_int8(v_new[None])
                        return (y[0, 0], qk[0], sk[0], qv[0],
                                sv[0])

                    x, kn, ksn, vn, vsn = jax.vmap(rowq)(
                        x, tables, pos)
                    with jax.named_scope("page_writeback"):
                        pg, off = self._row_targets(tables, pos, mask)
                        kq = kq.at[pg, off].set(kn)
                        vq = vq.at[pg, off].set(vn)
                        ks = ks.at[pg, off].set(ksn)
                        vs = vs.at[pg, off].set(vsn)
                    new_caches.append((kq, vq, ks, vs))
                nxt, pos, keys = sample_next(tok, pos, keys, x)
                return (nxt, pos, keys, tuple(new_caches)), nxt

            (tok, pos, keys, caches), toks = jax.lax.scan(
                body, (tok, pos, keys, caches), None,
                length=self.decode_block)
            return toks, keys, caches            # toks (chunk, S)

        if tp <= 1:
            return self._finalize(step, donate=(8, 9))
        from jax.sharding import PartitionSpec as P
        cs = self._caches_pspec(self.stack)
        pspec = self._params_pspec(self.stack, params_of(self.wf))
        return self._finalize(
            step, donate=(8, 9),
            in_specs=(pspec, P(), P(), P(), P(), P(), P(), P(), P(), cs),
            out_specs=(P(), P(), cs))

    def _build_spec_round(self):
        """ONE fixed-shape speculative round over the pool: the draft
        proposes ``spec_gamma`` tokens per row (a ``lax.scan`` of
        single-row steps through the draft's paged view), the target
        verifies the whole window in one ``_block_span`` pass per row,
        and ``nn/speculative``'s accept arithmetic (greedy
        prefix-match or the Leviathan rejection rule — selected
        per-row by temperature) emits up to gamma tokens. Rejected
        positions leave stale page rows behind; every read masks
        strictly by position and the next round overwrites from the
        accepted head, so stale rows are never observed — the same
        cache discipline as the solo decoder, which greedy rows
        therefore match bit-for-bit."""
        import jax
        import jax.numpy as jnp
        from ..nn.speculative import _block_span, _stochastic_accept
        from ..ops import matmul_precision
        gamma = self.spec_gamma
        tgt, drf = self.stack, self.draft_stack
        prec = matmul_precision()
        quant_w = self.quant_weights
        tp, tp_axis = self.tp, self._tp_axis

        def embed_rows(stack, params, tok, pos):
            from ..nn.sampling import _embed_ids
            x = _embed_ids(stack["stem"], params, tok, tp=tp,
                           tp_axis=tp_axis)
            pe = stack["pos_emb"]
            if pe is not None:
                x = x + jnp.take(params[pe.name]["table"], pos,
                                 axis=0, mode="clip")
            return x

        def spec_round(params_t, params_d, tok, pos, temp, smask,
                       tables, keys, caches_t, caches_d):
            if quant_w:
                from ..quant import dequantize_params
                params_t = dequantize_params(
                    params_t,
                    dtype=params_t[tgt["stem"].name]["table"].dtype)
            tau = jnp.where(temp > 0, temp, 1.0)        # (S,)
            keys2 = jax.vmap(
                lambda k: jax.random.split(k, 3))(keys)  # (S, 3, 2)
            k_carry, k_d, k_a = keys2[:, 0], keys2[:, 1], keys2[:, 2]
            keys = jnp.where(smask[:, None] > 0, k_carry, keys)

            # -- draft proposes gamma tokens ---------------------------------
            def propose(carry, j):
                dtok, caches_d = carry
                x = embed_rows(drf, params_d, dtok, pos + j)
                new_caches = []
                for blk, (kp, vp) in zip(drf["blocks"], caches_d):
                    p = params_d[blk.name]
                    x, k_new, v_new = jax.vmap(
                        self._paged_row_step(blk, p, kp, vp, tp=tp,
                                             tp_axis=tp_axis))(
                            x, tables, pos + j)
                    pg, off = self._row_targets(tables, pos + j, smask)
                    kp = kp.at[pg, off].set(k_new)
                    vp = vp.at[pg, off].set(v_new)
                    new_caches.append((kp, vp))
                logits = _head_logits(drf["head"], params_d, x, prec,
                                      tp_axis=tp_axis) / tau[:, None]
                greedy_t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                samp = jax.vmap(
                    lambda k, row: jax.random.categorical(
                        jax.random.fold_in(k, j), row)
                )(k_d, logits).astype(jnp.int32)
                nxt = jnp.where(temp > 0, samp, greedy_t)
                nxt = jnp.where(smask > 0, nxt, dtok)
                probs = jax.nn.softmax(logits, axis=-1)
                return (nxt, tuple(new_caches)), (nxt, probs)

            (_, caches_d), (d_toks, pd) = jax.lax.scan(
                propose, (tok, caches_d), jnp.arange(gamma))
            d_toks = jnp.moveaxis(d_toks, 0, 1)     # (S, gamma)
            pd = jnp.moveaxis(pd, 0, 1)             # (S, gamma, V)

            # -- target verifies the window in one pass ----------------------
            window = jnp.concatenate([tok[:, None], d_toks[:, :-1]],
                                     axis=1)        # (S, gamma)
            x = jax.vmap(
                lambda w, p0: embed_rows(
                    tgt, params_t, w, p0 + jnp.arange(gamma))
            )(window, pos)                          # (S, gamma, D)
            new_caches_t = []
            for blk, (kp, vp) in zip(tgt["blocks"], caches_t):
                p = params_t[blk.name]

                def vrow(x_row, trow, pos_row, blk=blk, p=p,
                         kp=kp, vp=vp):
                    ck = self._view(kp, trow)
                    cv = self._view(vp, trow)
                    y, ck2, cv2 = _block_span(
                        blk, p, x_row[None], ck[None], cv[None],
                        pos_row, tp=tp, tp_axis=tp_axis)
                    news_k = [jnp.take(ck2[0], pos_row + j, axis=0,
                                       mode="clip")
                              for j in range(gamma)]
                    news_v = [jnp.take(cv2[0], pos_row + j, axis=0,
                                       mode="clip")
                              for j in range(gamma)]
                    return (y[0], jnp.stack(news_k), jnp.stack(news_v))

                x, knews, vnews = jax.vmap(vrow)(x, tables, pos)
                for j in range(gamma):
                    pg, off = self._row_targets(tables, pos + j, smask)
                    kp = kp.at[pg, off].set(knews[:, j])
                    vp = vp.at[pg, off].set(vnews[:, j])
                new_caches_t.append((kp, vp))
            caches_t = tuple(new_caches_t)
            t_logits = _head_logits(tgt["head"], params_t, x, prec,
                                    tp_axis=tp_axis) \
                / tau[:, None, None]                # (S, gamma, V)

            # -- accept + emit (nn/speculative arithmetic) -------------------
            ar = jnp.arange(gamma)

            def accept(k_a_row, t_row, pd_row, d_row, temp_row):
                t_arg = jnp.argmax(t_row, axis=-1).astype(jnp.int32)
                match = d_row == t_arg
                a_g = jnp.minimum(
                    jnp.argmin(match) + gamma * match.all(), gamma)
                fix_g = t_arg[jnp.minimum(a_g, gamma - 1)]
                a_s, fix_s = _stochastic_accept(
                    k_a_row, jax.nn.softmax(t_row, axis=-1), pd_row,
                    d_row)
                a = jnp.where(temp_row > 0, a_s, a_g)
                fix = jnp.where(temp_row > 0, fix_s, fix_g)
                out_vec = jnp.where(ar < a, d_row,
                                    jnp.where(ar == a, fix, 0))
                n_emit = jnp.minimum(a + 1, gamma)
                new_tok = jnp.where(a < gamma, fix, d_row[gamma - 1])
                return a, out_vec, n_emit, new_tok

            a, out_vec, n_emit, new_tok = jax.vmap(accept)(
                k_a, t_logits, pd, d_toks, temp)
            n_emit = jnp.where(smask > 0, n_emit, 0)
            a = jnp.where(smask > 0, a, 0)
            new_tok = jnp.where(smask > 0, new_tok, tok)
            return (out_vec, n_emit, a, new_tok, keys, caches_t,
                    caches_d)

        if tp <= 1:
            return self._finalize(spec_round, donate=(7, 8, 9))
        from jax.sharding import PartitionSpec as P
        cs_t = self._caches_pspec(tgt)
        cs_d = self._caches_pspec(drf)
        pspec_t = self._params_pspec(tgt, params_of(self.wf))
        pspec_d = self._params_pspec(drf, params_of(self.draft))
        return self._finalize(
            spec_round, donate=(7, 8, 9),
            in_specs=(pspec_t, pspec_d, P(), P(), P(), P(), P(), P(),
                      cs_t, cs_d),
            out_specs=(P(), P(), P(), P(), P(), cs_t, cs_d))

    def _build_prefill_chunk(self):
        """ONE fixed-shape suffix/chunk prefill shared by prefix-cache
        adoption and chunked prefill: ``_chunk`` prompt tokens at
        positions ``p0..p0+C-1`` for a single slot, attending over the
        slot's gathered page view (adopted prefix K/V included).

        Id-exactness is arithmetic, not luck: the attention reproduces
        ``attention_reference``'s EXACT op order — einsum in the model
        dtype, f32 cast then ``* scale``, -1e30 mask, ``exp(s-max)``
        softmax, value product with weights cast back to the model
        dtype — so a chunked (or prefix-matched) prompt's layer
        outputs are bit-identical to the monolithic bucketed pass
        (masked view positions contribute EXACT zeros whatever the
        padded length; ``_chunk_kernel_safe`` keeps flash-crossover
        buckets on the monolithic plane). Chunk K/V rows scatter
        per-position through the page table (positions beyond the
        table target the sink; pad positions past ``t_p`` are
        rewritten by the decode step before any read mask reaches
        them). The FINAL chunk samples the request's first token with
        the bucketed prefill's exact seed-key convention and installs
        the slot's PRNG carry; non-final chunks leave ``keys``
        untouched."""
        import jax
        import jax.numpy as jnp
        from ..nn.attention import expand_kv
        from ..nn.speculative import _rope_span
        from ..nn.transformer import block_ffn, block_norm
        from ..ops import matmul_precision
        stack = self.stack
        stem, pos_emb = stack["stem"], stack["pos_emb"]
        blocks, head = stack["blocks"], stack["head"]
        prec = matmul_precision()
        d = stem.dim
        C = self._chunk
        P = self.page_size
        quant_w = self.quant_weights
        tp, tp_axis = self.tp, self._tp_axis

        def pchunk(params, ids, p0, t_p, slot, temp, seed_key,
                   table_row, final, keys, caches):
            if quant_w:
                from ..quant import dequantize_params
                params = dequantize_params(
                    params, dtype=params[stem.name]["table"].dtype)
            x = _embed_prompt(stem, pos_emb, params, ids[None],
                              pos0=p0, tp=tp,
                              tp_axis=tp_axis)         # (1, C, D)
            pos_idx = p0 + jnp.arange(C)
            pg = jnp.take(table_row, pos_idx // P, mode="fill",
                          fill_value=0)
            off = pos_idx % P
            new_caches = []
            for blk, (kp, vp) in zip(blocks, caches):
                p = params[blk.name]
                h = blk.n_heads // tp
                kv = getattr(blk, "n_kv_heads", blk.n_heads) // tp
                hd = d // blk.n_heads
                a_in = block_norm(jnp, blk, p, x, "ln1")
                q = jnp.dot(a_in, p["wq"],
                            precision=prec).reshape(1, C, h, hd)
                k = jnp.dot(a_in, p["wk"],
                            precision=prec).reshape(1, C, kv, hd)
                v = jnp.dot(a_in, p["wv"],
                            precision=prec).reshape(1, C, kv, hd)
                if blk.rope:
                    base = getattr(blk, "rope_base", 10000.0)
                    q = _rope_span(jnp, q, p0, base)
                    k = _rope_span(jnp, k, p0, base)
                # gathered view + C zero rows: dynamic_update_slice
                # then never clamp-shifts over real rows, and the
                # extra keys sit behind the causal mask as exact zeros
                ck = self._view(kp, table_row)
                cv = self._view(vp, table_row)
                zpad = jnp.zeros((C,) + ck.shape[1:], ck.dtype)
                ck = jax.lax.dynamic_update_slice(
                    jnp.concatenate([ck, zpad]), k[0], (p0, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    jnp.concatenate([cv, zpad]), v[0], (p0, 0, 0))
                k_full = expand_kv(jnp, ck[None], h)
                v_full = expand_kv(jnp, cv[None], h)
                scale = 1.0 / (hd ** 0.5)
                s = jnp.einsum("bqhd,bkhd->bhqk", q,
                               k_full).astype(jnp.float32) * scale
                t_idx = jnp.arange(k_full.shape[1])[None, :]
                q_idx = pos_idx[:, None]
                valid = t_idx <= q_idx
                win = getattr(blk, "window", None)
                if win:
                    valid = valid & (t_idx > q_idx - win)
                s = jnp.where(valid[None, None], s, -1e30)
                w = jnp.exp(s - s.max(axis=-1, keepdims=True))
                w = w / w.sum(axis=-1, keepdims=True)
                o = jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype),
                               v_full).reshape(1, C, h * hd)
                proj = jnp.dot(o, p["wo"], precision=prec)
                if tp_axis is not None:
                    proj = jax.lax.psum(proj, tp_axis)
                x = x + proj
                f_in = block_norm(jnp, blk, p, x, "ln2")
                x = x + block_ffn(jnp, blk, p, f_in, prec,
                                  tp_axis=tp_axis)
                kp = kp.at[pg, off].set(k[0])
                vp = vp.at[pg, off].set(v[0])
                new_caches.append((kp, vp))
            x_last = jnp.take(x[0], t_p - 1 - p0, axis=0, mode="clip")
            logits = _head_logits(head, params, x_last, prec,
                                  tp_axis=tp_axis)
            k2 = jax.random.split(seed_key)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            samp = jax.random.categorical(
                k2[1], logits / jnp.maximum(temp, _TEMP_EPS)
            ).astype(jnp.int32)
            first = jnp.where(temp > 0, samp, greedy)
            upd = jax.lax.dynamic_update_slice(keys, k2[0][None],
                                               (slot, 0))
            keys = jnp.where(final > 0, upd, keys)
            return first, keys, tuple(new_caches)

        if tp <= 1:
            return self._finalize(pchunk, donate=(9, 10))
        from jax.sharding import PartitionSpec as PS
        cs = self._caches_pspec(self.stack)
        pspec = self._params_pspec(self.stack, params_of(self.wf))
        return self._finalize(
            pchunk, donate=(9, 10),
            in_specs=(pspec, PS(), PS(), PS(), PS(), PS(), PS(), PS(),
                      PS(), PS(), cs),
            out_specs=(PS(), PS(), cs))

    def _build_page_copy(self):
        """Clone one slot's pages into another slot's pages — the
        beam sibling admission: every hypothesis row starts as an
        identical copy of the lead row's prompt cache, so one
        page-granular device copy replaces ``beam_width - 1``
        redundant prefill dispatches. Unallocated table entries alias
        the sink page on both sides (garbage copied to garbage, never
        read). Beam never serves the int8 pool, so the pools here are
        always float ``(k, v)`` pairs."""
        import jax
        import jax.numpy as jnp

        def pagecopy(src_row, dst_row, caches):
            # page ids are LOGICAL: under tp each shard copies its own
            # kv-head slice of the same page rows — the body is
            # axis-0 take/set, transparently shard-agnostic
            new_caches = []
            for kp, vp in caches:
                kp = kp.at[dst_row].set(
                    jnp.take(kp, src_row, axis=0, mode="clip"))
                vp = vp.at[dst_row].set(
                    jnp.take(vp, src_row, axis=0, mode="clip"))
                new_caches.append((kp, vp))
            return tuple(new_caches)

        if self.tp <= 1:
            return self._finalize(pagecopy, donate=(2,))
        from jax.sharding import PartitionSpec as P
        cs = self._caches_pspec(self.stack)
        return self._finalize(pagecopy, donate=(2,),
                              in_specs=(P(), P(), cs), out_specs=cs)

    def _build_beam_step(self):
        """ONE fixed-shape beam step over every group: each hypothesis
        runs the single-row step over its paged view; the group-level
        top-k (f32 log_softmax, frozen-eos lanes, flat ``top_k`` over
        W·V — ``nn/beam.py``'s exact arithmetic) picks the surviving
        (parent, token) pairs, and the cache reorder lands as a
        page-granular copy: every child's pages are rewritten from its
        parent's updated view through the page tables in one batched
        scatter. Masked groups read real pages but write the sink."""
        import jax
        import jax.numpy as jnp
        from ..ops import matmul_precision
        stack = self.stack
        stem, pos_emb = stack["stem"], stack["pos_emb"]
        blocks, head = stack["blocks"], stack["head"]
        prec = matmul_precision()
        quant_w = self.quant_weights
        W, P = self.beam_width, self.pages_per_slot
        page = self.page_size
        tp, tp_axis = self.tp, self._tp_axis

        def beam_step(params, cur, pos, scores, finished, eosv, gmask,
                      tables_g, caches):
            if quant_w:
                from ..quant import dequantize_params
                params = dequantize_params(
                    params, dtype=params[stem.name]["table"].dtype)
            G = cur.shape[0]
            flat_tab = tables_g.reshape(G * W, P)
            flat_cur = cur.reshape(G * W)
            flat_pos = jnp.repeat(pos, W)
            from ..nn.sampling import _embed_ids
            x = _embed_ids(stem, params, flat_cur, tp=tp,
                           tp_axis=tp_axis)
            if pos_emb is not None:
                x = x + jnp.take(params[pos_emb.name]["table"],
                                 flat_pos, axis=0, mode="clip")
            views = []                      # per block: updated views
            for blk in blocks:
                p = params[blk.name]
                kp, vp = caches[len(views)]

                def row(x_row, trow, pos_row, blk=blk, p=p,
                        kp=kp, vp=vp):
                    ck = self._view(kp, trow)
                    cv = self._view(vp, trow)
                    y, ck2, cv2 = _block_step(
                        blk, p, x_row[None, None, :],
                        ck[None], cv[None], pos_row,
                        tp=tp, tp_axis=tp_axis)
                    return y[0, 0], ck2[0], cv2[0]

                x, ck_new, cv_new = jax.vmap(row)(x, flat_tab,
                                                  flat_pos)
                views.append((ck_new, cv_new))  # (GW, T, kv, hd)
            logits = _head_logits(head, params, x, prec,
                                  tp_axis=tp_axis)     # (GW, V)
            v = logits.shape[-1]
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32)).reshape(G, W, v)

            def group_topk(logp_g, scores_g, fin_g, eos_g):
                frozen = jnp.full((v,), -jnp.inf).at[eos_g].set(0.0)
                logp_g = jnp.where(fin_g[:, None], frozen[None, :],
                                   logp_g)
                joint = scores_g[:, None] + logp_g       # (W, V)
                flat, idx = jax.lax.top_k(joint.reshape(-1), W)
                parent = idx // v
                tok = (idx % v).astype(jnp.int32)
                fin = fin_g[parent] | (tok == eos_g)
                return tok, parent, flat, fin

            tok, parent, new_scores, new_fin = jax.vmap(group_topk)(
                logp, scores, finished, eosv)
            # cache reorder: child pages <- parent's updated view,
            # page-granular, one batched scatter per block
            flat_parent = (parent
                           + (jnp.arange(G) * W)[:, None]).reshape(
                               G * W)
            write_tab = jnp.where(
                gmask.astype(bool)[:, None, None], tables_g, 0
            ).reshape(G * W * P)
            new_caches = []
            for (kp, vp), (ck_new, cv_new) in zip(caches, views):
                sel_k = jnp.take(ck_new, flat_parent, axis=0,
                                 mode="clip")
                sel_v = jnp.take(cv_new, flat_parent, axis=0,
                                 mode="clip")
                shape = (G * W * P, page) + sel_k.shape[2:]
                kp = kp.at[write_tab].set(sel_k.reshape(shape))
                vp = vp.at[write_tab].set(sel_v.reshape(shape))
                new_caches.append((kp, vp))
            return tok, parent, new_scores, new_fin, tuple(new_caches)

        if tp <= 1:
            return self._finalize(beam_step, donate=(8,))
        from jax.sharding import PartitionSpec as PS
        cs = self._caches_pspec(self.stack)
        pspec = self._params_pspec(self.stack, params_of(self.wf))
        return self._finalize(
            beam_step, donate=(8,),
            in_specs=(pspec, PS(), PS(), PS(), PS(), PS(), PS(), PS(),
                      cs),
            out_specs=(PS(), PS(), PS(), PS(), cs))
