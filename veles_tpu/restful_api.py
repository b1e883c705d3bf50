"""RESTful serving: HTTP POST a sample, get the model's answer.

Equivalent of the reference's veles/restful_api.py:78 (RESTfulAPI unit:
twisted Site; POST /api JSON → RestfulLoader feed → workflow run in test
mode → JSON result). Stdlib ``http.server`` replaces twisted (not in this
environment); the serving workflow itself is the same shape: a Repeater
loop of RestfulLoader → forwards → RESTfulAPI, where this unit runs after
the forwards each pass and answers the HTTP request that fed the sample.

The HTTP thread and the workflow thread meet through per-request tickets:
the handler feeds (sample, ticket) to the loader and blocks on the
ticket's event; this unit's ``run()`` fills the ticket from the forward
output and sets the event.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from http.server import BaseHTTPRequestHandler
from typing import Any, Dict, Optional

import numpy

from ._http import (HTTPService, bytes_reply, handle_alerts,
                    handle_metrics_history, handle_trace_spans,
                    json_reply, read_json_object)
from .config import root
from .error import VelesError
from .resilience import health
from .resilience.faults import FaultInjected, fire as fire_fault
from .serving.scheduler import (Ticket as _Ticket, shed_expired,
                                split_expired)
from .telemetry.spans import span
from .units import Unit


#: numbers the profile captures of this process, so that two in one
#: second get directories of their own
_profile_ids = itertools.count(1)


class RESTfulAPI(Unit):
    """Serving endpoint unit (reference: veles/restful_api.py:78).

    Wire into a forward workflow:
        api = RESTfulAPI(wf, port=8080, loader=rest_loader)
        api.link_attrs(last_forward, ("input", "output"))
        api.link_from(last_forward); repeater.link_from(api)
    """

    MAPPING = "restful_api"
    hide_from_registry = False

    def __init__(self, workflow, loader=None, port: int = 0,
                 path: str = "/api", request_timeout: float = 60.0,
                 max_pending: int = None, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.view_group = "SERVICE"
        self.loader = loader
        self.port = port
        self.path = path
        self.request_timeout = request_timeout
        #: in-flight bound: requests beyond it are SHED (503 +
        #: Retry-After) instead of queueing without limit
        self.max_pending = int(max_pending if max_pending is not None
                               else root.common.resilience.get(
                                   "max_pending", 64) or 64)
        self._pending = 0
        self._pending_lock = threading.Lock()
        #: tickets fed but not yet terminal — what a stop()/drain
        #: sweep settles via the first-terminal fail() (503 +
        #: Retry-After + request_id) instead of letting the handlers
        #: rot to a silent 504
        self._outstanding: set = set()
        #: forward output to answer from (link_attrs from the last forward)
        self.input = None
        self._service: Optional[HTTPService] = None
        self.requests_served = 0
        self.demand("loader")

    # -- lifecycle ----------------------------------------------------------
    def initialize(self, **kwargs):
        res = super().initialize(**kwargs)
        if res:
            return res
        if self._service is not None:
            return None
        api = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route into our logger
                api.debug("http: " + fmt, *args)

            def do_GET(self):
                if health.handle_health(self, self.path):
                    return
                if handle_trace_spans(self, self.path,
                                      name="rest.%s" % api.name):
                    return
                if handle_metrics_history(self, self.path,
                                          name="rest.%s" % api.name):
                    return
                if handle_alerts(self, self.path):
                    return
                if self.path != "/metrics":
                    self.send_error(404)
                    return
                from .telemetry.alerts import render_firing
                from .telemetry.counters import (METRICS_CONTENT_TYPE,
                                                 metrics_text)
                text = metrics_text({
                    "veles_rest_requests_served": api.requests_served,
                    "veles_rest_pending": api._pending}) \
                    + render_firing()
                bytes_reply(self, 200, text.encode(),
                            METRICS_CONTENT_TYPE)

            def do_POST(self):
                if self.path != api.path:
                    self.send_error(404)
                    return
                try:
                    fire_fault("serve.request")
                except FaultInjected as e:
                    # an injected serving fault DEGRADES (shed +
                    # Retry-After, counted), never crashes the handler
                    from .serving.scheduler import new_request_id
                    health.shed(self, retry_after=1.0, reason=str(e),
                                request_id=new_request_id())
                    return
                with api._pending_lock:
                    if api._pending >= api.max_pending:
                        from .serving.scheduler import new_request_id
                        health.shed(
                            self, retry_after=1.0,
                            reason="%d requests in flight (bound %d)"
                            % (api._pending, api.max_pending),
                            request_id=new_request_id())
                        return
                    api._pending += 1
                try:
                    self._serve()
                finally:
                    with api._pending_lock:
                        api._pending -= 1

            def _serve(self):
                try:
                    body = read_json_object(self)
                    # the LOADER owns its wire format (image loaders
                    # decode base64 payloads; the base reads "input")
                    sample = api.loader.parse_request(body)
                except (ValueError, KeyError, VelesError) as e:
                    # client-fault only — a server-side bug (missing
                    # parse_request, broken override) must surface as
                    # a 5xx, not masquerade as a bad request
                    self._reply(400, {"error": "bad request: %s" % e})
                    return
                ticket = _Ticket()
                try:
                    api.loader.feed(sample, ticket=ticket)
                except VelesError as e:
                    from .loader.stream import LoaderClosed
                    # shape rejection is the CLIENT's fault; a closed
                    # loader is the server shutting down
                    code = 503 if isinstance(e, LoaderClosed) else 400
                    self._reply(code, {"error": str(e)})
                    return
                except Exception as e:
                    self._reply(503, {"error": str(e)})
                    return
                with api._pending_lock:
                    api._outstanding.add(ticket)
                try:
                    settled = ticket.event.wait(api.request_timeout)
                finally:
                    with api._pending_lock:
                        api._outstanding.discard(ticket)
                if not settled:
                    self._reply(504, {"error": "inference timed out",
                                      "request_id": ticket.request_id})
                    return
                if ticket.error is not None:
                    headers = None
                    if ticket.retry_after:
                        headers = {"Retry-After": str(max(1, int(
                            ticket.retry_after)))}
                    json_reply(self, ticket.code,
                               ticket.error_payload(), headers=headers)
                    return
                self._reply(200, {"result": ticket.result,
                                  "request_id": ticket.request_id})

            def _reply(self, code: int, payload: Dict[str, Any]):
                json_reply(self, code, payload)

        self._service = HTTPService(Handler, self.port,
                                    self.name + ".http")
        self.port = self._service.port
        self._service.start_serving()
        # watchtower sampler (telemetry/timeseries.py): a no-op config
        # read unless root.common.telemetry.watch.enabled
        from .telemetry import timeseries
        timeseries.add_gauge_provider(
            "rest.%s" % self.name,
            lambda: {"veles_rest_requests_served": self.requests_served,
                     "veles_rest_pending": self._pending})
        timeseries.maybe_start()
        health.mark_ready("rest.%s" % self.name)
        health.heartbeats.beat("rest.%s" % self.name)
        self.info("%s: REST API on http://127.0.0.1:%d%s", self.name,
                  self.port, self.path)
        return None

    # -- graph side ---------------------------------------------------------
    def run(self) -> None:
        # the serving loop's liveness beat: a stuck forward stops this
        # aging and /healthz flips unhealthy
        health.heartbeats.beat("rest.%s" % self.name)
        tickets = list(getattr(self.loader, "current_tickets", ()))
        real = [(i, t) for i, t in enumerate(tickets)
                if isinstance(t, _Ticket)]
        if not real:
            return      # samples came from somewhere else (e.g. warm-up)
        try:
            out = self.input
            if out is None:
                raise VelesError("%s: no forward output linked" % self.name)
            if hasattr(out, "map_read"):
                out = out.map_read()
            out = numpy.asarray(out)
            # the linked output's FIRST axis is minibatch rows (the
            # serving wiring links the batched forward output): row i
            # answers ticket i — also when each row is a scalar
            # (ndim==1), where returning the whole vector would leak
            # every client's result to every client. Terminals go
            # through succeed()/fail() — first-terminal exactly-once,
            # histograms + flight events recorded — never a bare
            # result/event poke a shutdown sweep could double-settle.
            served = 0
            for i, ticket in real:
                ticket.mark_admitted()
                if ticket.succeed(numpy.asarray(out[i]).tolist()):
                    served += 1
            self.requests_served += served
        except Exception as e:
            for _, ticket in real:
                ticket.mark_admitted()
                ticket.fail("%s: %s" % (type(e).__name__, e), code=500)
        finally:
            self.loader.current_tickets = []

    def stop(self) -> None:
        health.forget("rest.%s" % self.name)
        from .telemetry import timeseries
        timeseries.remove_gauge_provider("rest.%s" % self.name)
        if self._service is not None:
            self._service.stop_serving()
            self._service = None
        # straggler sweep: every fed-but-unanswered ticket settles
        # through the first-terminal fail() — 503 + Retry-After +
        # request_id (error_payload), histograms/flight recorded
        # exactly once however many stop()/drain sweeps run
        with self._pending_lock:
            stragglers = list(self._outstanding)
        for ticket in stragglers:
            ticket.fail("server shutting down", code=503,
                        retry_after=5.0)


class GenerationAPI(Unit):
    """REST serving for the autoregressive generation stack: POST
    ``{"prompt": [ids], "n_new": N}`` (+ optional ``mode``:
    ``greedy`` | ``sample`` | ``speculative`` | ``beam``,
    ``temperature``, ``gamma``, ``beam``, ``seed``) →
    ``{"tokens": [...]}`` plus decode stats.

    Two decode planes serve the queue (reference equivalent:
    `veles/restful_api.py:78` + `veles/loader/restful.py:52`, which
    served one forward per request):

    - ``engine="continuous"`` (default): greedy and sample requests
      ride the continuous-batching engine (``veles_tpu/serving/``) — a
      persistent ``max_slots``-row KV-cache pool with ONE fixed-shape
      jitted decode step, prefill padded to ``buckets`` (jit cache
      bounded by ``programs_bound()``: len(buckets) and the step's 1
      or 2 view lengths), iteration-level admission
      into free slots and per-row retirement at ``eos_id`` / own
      ``n_new``. Per-slot PRNG streams keep every row id-exact vs its
      solo decode, so batching never changes answers — stochastic
      decodes included. Requests the pool cannot hold (prompt longer
      than the largest bucket, context overflow) fall back to the
      window worker below.
    - ``engine="window"``: the legacy micro-batcher — a worker thread
      coalesces the queue for ``batch_window`` seconds and batches
      requests sharing an exact shape key into one
      ``sampling.generate`` / ``generate_speculative`` call.
      ``speculative`` and ``beam`` requests always take this path.

    A ticket older than its ``request_timeout`` deadline is answered
    503 + Retry-After by whichever plane dequeues it — it never sits
    in the queue past its useful life.

    Standalone service unit: not part of the Repeater loop — the
    device program IS the generation; ``initialize`` starts the HTTP
    service + worker(s), ``stop`` drains them.
    """

    MAPPING = "generation_api"
    hide_from_registry = False

    MODES = ("greedy", "sample", "speculative", "beam")

    def __init__(self, workflow, draft=None, port: int = 0,
                 path: str = "/generate", max_new: int = 512,
                 batch_window: float = 0.02,
                 request_timeout: float = 120.0,
                 max_queue: int = None, engine: str = None,
                 max_slots: int = None, buckets=None,
                 max_context: int = None,
                 decode_block: int = None,
                 page_size: int = None, pages: int = None,
                 spec_gamma: int = None, beam_width: int = None,
                 quant_weights: bool = None, quant_kv: bool = None,
                 artifact: str = None,
                 prefix_cache: bool = None,
                 prefill_chunk: int = None,
                 state_cache: bool = None,
                 profile_dir: str = None, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.view_group = "SERVICE"
        #: the TARGET model workflow is the unit's own workflow; an
        #: optional DRAFT workflow enables mode=speculative
        self.draft = draft
        self.port = port
        self.path = path
        self.max_new = int(max_new)
        #: queue bound: requests arriving beyond it are SHED (503 +
        #: Retry-After) instead of growing the queue unboundedly
        self.max_queue = int(max_queue if max_queue is not None
                             else root.common.resilience.get(
                                 "max_queue", 256) or 256)
        self.batch_window = float(batch_window)
        self.request_timeout = float(request_timeout)
        # continuous-batching knobs (root.common.serving.* defaults —
        # see veles_tpu/serving/ and docs/services.md)
        serving_cfg = root.common.serving
        self.engine_kind = str(engine or serving_cfg.get(
            "engine", "continuous"))
        self.max_slots = int(max_slots if max_slots is not None
                             else serving_cfg.get("max_slots", 8))
        self.buckets = (buckets if buckets is not None
                        else serving_cfg.get("buckets",
                                             [16, 32, 64, 128]))
        self.max_context = int(
            max_context if max_context is not None
            else serving_cfg.get("max_context", 640))
        self.decode_block = int(
            decode_block if decode_block is not None
            else serving_cfg.get("decode_block", 1))
        # paged-pool + pooled-decode-mode knobs (None defers to
        # root.common.serving.* inside the engine; see serving/pages.py
        # and docs/services.md "Paged KV cache")
        self.page_size = page_size
        self.pages = pages
        self.spec_gamma = spec_gamma
        self.beam_width = beam_width
        # quantization / AOT-artifact policy (veles_tpu/quant/,
        # docs/services.md "Quantized serving"): None defers to
        # root.common.quant.* / root.common.serving.artifact inside
        # the engine, keeping CLI flags, config and kwargs one policy
        self.quant_weights = quant_weights
        self.quant_kv = quant_kv
        self.artifact = artifact
        # heavy-traffic request plane (docs/services.md "Prefix
        # sharing & streaming"): None defers to
        # root.common.serving.{prefix_cache,prefill_chunk} inside the
        # engine; streaming is per-request (``stream=true``), gated by
        # root.common.serving.stream
        self.prefix_cache = prefix_cache
        self.prefill_chunk = prefill_chunk
        # O(1)-state lane knob (docs/services.md "O(1)-state
        # serving"): None defers to root.common.serving.state_cache
        # inside the RecurrentEngine
        self.state_cache = state_cache
        #: where ``POST <path>/profile`` writes its captures
        #: (``--profile-dir`` under ``--serve-generate``); None: the
        #: endpoint answers 403
        self.profile_dir = (profile_dir if profile_dir is not None
                            else serving_cfg.get("profile_dir", None))
        self._profile_lock = threading.Lock()
        self._profile_stop = threading.Event()
        self._engine = None
        self._service: Optional[HTTPService] = None
        #: serializes initialize()/stop(): a supervisor respawning a
        #: replica whose injected death is still tearing down must
        #: wait for the teardown, not interleave with it (the old
        #: stop() would otherwise kill the freshly built engine)
        self._lifecycle = threading.RLock()
        self._queue: list = []
        self._cv = threading.Condition()
        self._worker: Optional[threading.Thread] = None
        self._closing = False
        #: graceful drain: admission stopped, in-flight finishing —
        #: /readyz reports "draining" while /healthz stays green
        self._draining = False
        #: requests currently inside do_POST past admission (what a
        #: drain waits on before tearing the service down)
        self._inflight = 0
        self._uniq = 0
        self.requests_served = 0
        self.batches_run = 0
        self.max_batch = 0

    # -- request intake ------------------------------------------------------
    def _parse(self, body: Dict[str, Any]) -> Dict[str, Any]:
        prompt = body.get("prompt")
        if (not isinstance(prompt, list) or not prompt
                or not all(isinstance(t, int) for t in prompt)):
            raise ValueError("'prompt' must be a non-empty list of "
                             "token ids")
        n_new = body.get("n_new", 16)
        if not isinstance(n_new, int) or not 1 <= n_new <= self.max_new:
            raise ValueError("'n_new' must be an int in [1, %d]"
                             % self.max_new)
        mode = body.get("mode", "greedy")
        if mode not in self.MODES:
            raise ValueError("'mode' must be one of %s" % (self.MODES,))
        if mode == "speculative" and self.draft is None:
            raise ValueError("mode=speculative needs a draft model "
                             "configured on the server")
        # gamma/beam default to the ENGINE's fixed shapes, so a client
        # that omits them lands on the pooled plane whatever
        # --serve-spec-gamma/--serve-beam-width the server runs with
        # (a hard 4 would silently route such requests to the window
        # worker on any non-default server); without an engine the
        # window plane serves any width, 4 stays the wire default
        engine = self._engine
        try:
            temperature = float(body.get("temperature", 0.0))
            seed = int(body.get("seed", 0))
            gamma = int(body.get(
                "gamma", engine.spec_gamma if engine is not None
                else 4))
            beam = int(body.get(
                "beam", engine.beam_width if engine is not None
                else 4))
        except (TypeError, ValueError) as e:
            # float(None)/int({}) raise TypeError — it must surface as
            # a 400, not escape the handler as an unanswered traceback
            raise ValueError("non-numeric knob: %s" % e) from None
        if mode == "greedy":
            temperature = 0.0
        elif mode == "sample" and temperature <= 0:
            raise ValueError("mode=sample needs temperature > 0")
        eos_id = body.get("eos_id")
        if eos_id is not None and (isinstance(eos_id, bool)
                                   or not isinstance(eos_id, int)):
            # bool IS an int in python — JSON true/false must not pass
            # as token ids 1/0
            raise ValueError("'eos_id' must be an int token id")
        # a fleet router retrying a request on another replica sends
        # ITS id along — the ticket adopts it so every response body
        # (success, shed, expiry) correlates with the router's attempt
        request_id = body.get("request_id")
        if request_id is not None and (
                not isinstance(request_id, str)
                or not 1 <= len(request_id) <= 200):
            raise ValueError("'request_id' must be a non-empty string "
                             "of at most 200 chars")
        # fleet tracing (docs/observability.md "Fleet tracing"): the
        # router also forwards the trace_id it minted at admission and
        # the 1-based attempt number — the ticket adopts both, so this
        # replica's request spans and flight events stitch into the
        # router's route.attempt bracket in a merged fleet trace
        trace_id = body.get("trace_id")
        if trace_id is not None and (
                not isinstance(trace_id, str)
                or not 1 <= len(trace_id) <= 200):
            raise ValueError("'trace_id' must be a non-empty string "
                             "of at most 200 chars")
        attempt = body.get("attempt", 1)
        if isinstance(attempt, bool) or not isinstance(attempt, int) \
                or attempt < 1:
            raise ValueError("'attempt' must be an int >= 1")
        # token-level failover resume (docs/services.md "Lossless
        # request plane"): a retry of a died-mid-decode request
        # carries the tokens already emitted; they fold into the
        # prompt (re-prefilled in one bucketed pass — never
        # re-decoded) and n_new is the REMAINING budget. resume_k
        # tells the engine how far to advance the request's per-slot
        # PRNG stream so sampled resumes stay id-exact.
        resume_tokens = body.get("resume_tokens")
        if resume_tokens is not None:
            if (not isinstance(resume_tokens, list)
                    or not all(isinstance(t, int)
                               and not isinstance(t, bool)
                               for t in resume_tokens)):
                raise ValueError("'resume_tokens' must be a list of "
                                 "int token ids")
            if mode not in ("greedy", "sample"):
                raise ValueError(
                    "resume_tokens serve mode=greedy/sample only "
                    "(speculative/beam retries restart from scratch)")
        resume_tokens = [int(t) for t in (resume_tokens or ())]
        # token streaming (docs/services.md "Prefix sharing &
        # streaming"): stream=true answers with SSE events at step
        # boundaries instead of one buffered body. The knob
        # root.common.serving.stream (default on) can force buffered
        # answers fleet-wide without clients changing their requests.
        stream = body.get("stream", False)
        if not isinstance(stream, bool):
            raise ValueError("'stream' must be a boolean")
        if stream and not bool(root.common.serving.get("stream", True)):
            stream = False
        # QoS class + deadline (docs/services.md "Overload & QoS"):
        # unlabeled requests are interactive (batch is OPT-IN to
        # throttling/preemption); deadline_ms replaces the global
        # request_timeout for this request's queue sweep and handler
        # wait, capped by it — a client can only tighten
        from .serving.overload import QOS_PRIORITIES
        priority = body.get("priority", "interactive")
        if priority not in QOS_PRIORITIES:
            raise ValueError("'priority' must be one of %s"
                             % (QOS_PRIORITIES,))
        deadline_ms = body.get("deadline_ms")
        if deadline_ms is not None:
            if isinstance(deadline_ms, bool) \
                    or not isinstance(deadline_ms, (int, float)) \
                    or deadline_ms <= 0:
                raise ValueError("'deadline_ms' must be a positive "
                                 "number of milliseconds")
            deadline_ms = float(deadline_ms)
        req = {"prompt": [int(t) for t in prompt] + resume_tokens,
               "n_new": n_new, "resume_k": len(resume_tokens),
               "mode": mode, "temperature": temperature, "seed": seed,
               "gamma": gamma, "beam": beam, "eos_id": eos_id,
               "request_id": request_id, "trace_id": trace_id,
               "attempt": attempt, "stream": stream,
               "priority": priority, "deadline_ms": deadline_ms}
        if req["gamma"] < 1:
            raise ValueError("'gamma' must be >= 1")
        if req["beam"] < 1:
            raise ValueError("'beam' must be >= 1")
        if req["temperature"] > 0 and mode == "speculative":
            # stochastic SPECULATIVE decodes are never coalesced: the
            # rejection-sampling accept path draws batch-shaped noise,
            # so a request's tokens would depend on which strangers
            # arrived with it — seed determinism wins over batching
            # there. mode=sample HAS no such dependence any more:
            # sampling.generate draws per-row PRNG streams (a row's
            # noise is a pure function of its own seed), so sample
            # requests sharing a shape key batch exactly like greedy,
            # id-exact vs their solo decodes.
            with self._cv:
                self._uniq += 1
                req["_solo"] = self._uniq
        return req

    @staticmethod
    def _batch_key(req):
        """Requests sharing this key ride one batched decode — greedy,
        temperature-0 speculative AND mode=sample (per-row PRNG
        streams in sampling.generate make every row bit-identical to
        its solo decode, so batching never changes answers). Only
        stochastic speculative requests carry a unique _solo tag (see
        _parse) and form singleton groups."""
        return (req["mode"], len(req["prompt"]), req["n_new"],
                req["temperature"], req["gamma"], req["seed"],
                req.get("_solo"))

    # -- worker --------------------------------------------------------------
    @staticmethod
    def _trim_eos(tokens, eos_id):
        """Host-side stop-token truncation (through the first eos_id,
        inclusive): the decode itself runs the requested n_new — fixed
        shapes keep the compiled program shared — so per-request eos
        never fragments a batch and costs nothing device-side."""
        if eos_id is None:
            return list(tokens)
        out = []
        for t in tokens:
            out.append(t)
            if t == eos_id:
                break
        return out

    def _serve_group(self, reqs, tickets) -> None:
        from .nn import beam as beam_mod
        from .nn import sampling
        from .nn.speculative import generate_speculative
        mode = reqs[0]["mode"]
        try:
            if mode == "beam":
                # single-sequence search; stays per-request (beam has
                # NATIVE eos handling — frozen hypotheses)
                for req, ticket in zip(reqs, tickets):
                    toks, stats = beam_mod.beam_generate(
                        self.workflow, req["prompt"], req["n_new"],
                        beam=req["beam"], eos_id=req["eos_id"])
                    ticket.succeed(
                        {"tokens": [int(t) for t in toks],
                         "scores": [float(s) for s in
                                    stats["scores"]]})
                return
            prompts = [req["prompt"] for req in reqs]
            if mode == "speculative":
                rows, stats = generate_speculative(
                    self.workflow, self.draft, prompts,
                    reqs[0]["n_new"], gamma=reqs[0]["gamma"],
                    temperature=reqs[0]["temperature"],
                    seed=reqs[0]["seed"])
                for i, (req, ticket) in enumerate(zip(reqs, tickets)):
                    ticket.succeed({
                        "tokens": self._trim_eos(rows[i],
                                                 req["eos_id"]),
                        "acceptance": stats["acceptance"][i],
                        "rounds": stats["rounds"][i],
                        "batched_with": len(reqs) - 1})
                return
            rows = sampling.generate(
                self.workflow, prompts, reqs[0]["n_new"],
                temperature=reqs[0]["temperature"],
                seed=reqs[0]["seed"])
            for i, (req, ticket) in enumerate(zip(reqs, tickets)):
                ticket.succeed({
                    "tokens": self._trim_eos(rows[i], req["eos_id"]),
                    "batched_with": len(reqs) - 1})
        except Exception as e:        # noqa: BLE001 — answer, don't die
            # decoder-raised ValueError/VelesError on a parsed request
            # is the CLIENT's shape problem (beam > vocab, generation
            # past the positional table) — 400, not a server fault
            code = 400 if isinstance(e, (ValueError, VelesError)) \
                else 500
            for ticket in tickets:
                ticket.fail("%s: %s" % (type(e).__name__, e),
                            code=code)

    def _worker_loop(self) -> None:
        hb_name = "serve.%s" % self.name
        try:
            self._worker_iterations(hb_name)
        finally:
            # the worker's own exit drops its beat — a late beat after
            # stop()'s forget() must not leave an entry that ages into
            # a permanent /healthz failure
            health.heartbeats.unregister(hb_name)

    def _worker_iterations(self, hb_name: str) -> None:
        while True:
            if not self._closing:
                health.heartbeats.beat(hb_name)
            with self._cv:
                while not self._queue and not self._closing:
                    # bounded wait so the idle worker still beats the
                    # health registry (liveness, not just progress)
                    self._cv.wait(timeout=10.0)
                    if not self._closing:
                        health.heartbeats.beat(hb_name)
                if self._closing and not self._queue:
                    return
            # coalesce: let near-simultaneous requests join the batch
            if self.batch_window > 0:
                import time as _time
                _time.sleep(self.batch_window)
            with self._cv:
                pending, self._queue = self._queue, []
            # request_timeout holds while QUEUED, not just while
            # decoding: a ticket past its deadline is answered 503 +
            # Retry-After now, instead of burning a decode nobody is
            # waiting for (its handler would time out mid-batch) —
            # the same expiry answer the continuous engine gives
            pending, expired = split_expired(pending)
            shed_expired(expired)
            # queue exit is the window plane's admission boundary —
            # the queue-wait histogram sample for the live tickets
            # (expired ones above recorded their full wait instead)
            for _req, _ticket in pending:
                _ticket.mark_admitted()
            groups: Dict[Any, list] = {}
            for req, ticket in pending:
                groups.setdefault(self._batch_key(req),
                                  []).append((req, ticket))
            for group in groups.values():
                reqs = [r for r, _ in group]
                tickets = [t for _, t in group]
                self._serve_group(reqs, tickets)
                with self._cv:
                    self.batches_run += 1
                    self.max_batch = max(self.max_batch, len(reqs))
                    self.requests_served += len(reqs)

    # -- lifecycle -----------------------------------------------------------
    def _build_recurrent_engine(self):
        """Start the O(1)-state slot pool (serving/recurrent.py) for
        this API's workflow — raises :class:`VelesError` when the
        stack is not a recurrent LM chain (callers degrade)."""
        from .serving import RecurrentEngine
        engine = RecurrentEngine(
            self.workflow, max_slots=self.max_slots,
            max_context=self.max_context,
            decode_block=self.decode_block,
            page_size=self.page_size,
            state_cache=self.state_cache,
            artifact=self.artifact,
            name=self.name).start()
        engine.on_death = self._on_replica_death
        return engine

    def _metrics_gauges(self) -> Dict[str, Any]:
        """Gauge dict behind ``GET /metrics`` — also registered as this
        replica's watchtower gauge provider (telemetry/timeseries.py),
        so the sampled series and the scrape surface cannot drift."""
        gauges = {
            "veles_generate_requests_served": self.requests_served,
            "veles_generate_batches_run": self.batches_run,
            "veles_generate_max_batch": self.max_batch,
            "veles_generate_queue_depth": len(self._queue),
            "veles_generate_queue_bound": self.max_queue,
        }
        engine = self._engine          # stop() may null it mid-read
        if engine is not None:
            # continuous-batching occupancy (the gauges an operator
            # sizes max_slots/buckets with; the web_status surface
            # serves the same names suffixed _<engine-name> — this
            # port has ONE engine, so no suffix)
            st = engine.stats()
            gauges.update({
                "veles_serving_slots": st["slots"],
                "veles_serving_slots_busy": st["slots_busy"],
                "veles_serving_peak_slots": st["peak_slots"],
                "veles_serving_queue_depth": st["queue_depth"],
                "veles_serving_programs": st["programs"],
                # quantization/AOT mode gauges (veles_tpu/quant/):
                # 1 = the plane is active on this engine — dashboards
                # must know whether a throughput number is fp or int8,
                # live jit or artifact
                "veles_serving_artifact_mode": st["artifact_mode"],
                "veles_quant_weights_mode": st["quant_weights"],
                "veles_quant_kv_mode": st["quant_kv"],
                "veles_serving_kv_pool_bytes": st["kv_pool_bytes"],
                "veles_serving_kv_ring_bytes": st.get("kv_ring_bytes", 0),
                # prefix sharing & chunked prefill (docs/services.md
                # "Prefix sharing & streaming"): index occupancy and
                # the per-tick decode stall chunking bounds
                "veles_prefix_cache_enabled": st["prefix_cache"],
                "veles_prefix_cached_blocks": st["prefix_blocks"],
                "veles_serving_prefilling": st["prefilling"],
                "veles_serving_prefill_stall_seconds":
                    st["prefill_stall_seconds"],
                # mesh-slice width this replica spans (1 = solo chip).
                # fleet.merge folds it into veles_fleet_chips instead
                # of the generic gauge sum — N chips must never read
                # as N replicas in the fleet roll-up
                "veles_serving_tp": st.get("tp", 1),
            })
            if st.get("slot_kind", "paged") != "state":
                # paged-pool occupancy (serving/pages.py): the gauges
                # an operator sizes pages/page_size with —
                # fragmentation is the allocated-but-unoccupied
                # fraction of in-use pages (tail-of-page waste).
                # Rendered ONLY for paged engines: a pageless
                # O(1)-state replica must never put zero rows into
                # the fleet's page math
                gauges.update({
                    "veles_serving_pages_total": st["pages_total"],
                    "veles_serving_pages_in_use": st["pages_in_use"],
                    "veles_serving_page_size": st["page_size"],
                    "veles_serving_page_fragmentation":
                        st["page_fragmentation"],
                })
            else:
                # O(1)-state lane occupancy (serving/recurrent.py):
                # per-slot state HBM is CONSTANT in sequence length —
                # the gauges an operator sizes max_slots and the
                # state-cache budget with
                gauges.update({
                    "veles_o1_state_bytes_per_slot":
                        st["state_bytes_per_slot"],
                    "veles_o1_state_cache_blocks":
                        st["state_cache_blocks"],
                    "veles_o1_state_cache_bytes":
                        st["state_cache_bytes"],
                    "veles_o1_checkpoint_interval": st["page_size"],
                })
        # elastic training plane (resilience/elastic.py): generation/
        # world-size gauges ride this surface too (a training host can
        # serve status while elastic) — no rows while the plane is off
        from .resilience import elastic as _elastic
        gauges.update(_elastic.gauges())
        return gauges

    def initialize(self, **kwargs):
        with self._lifecycle:
            return self._initialize_locked(**kwargs)

    def _initialize_locked(self, **kwargs):
        res = super().initialize(**kwargs)
        if res:
            return res
        if self._service is not None:
            return None
        self._profile_stop.clear()
        if self.engine_kind == "recurrent" and self._engine is None:
            # operator pinned the O(1)-state lane: a non-recurrent
            # stack degrades to the window worker (same answers, no
            # in-flight batching) exactly like the continuous path's
            # VelesError degrade; geometry ValueErrors still propagate
            try:
                self._engine = self._build_recurrent_engine()
            except VelesError as e:
                self.warning("%s: O(1)-state serving unavailable "
                             "(%s); serving via the window worker",
                             self.name, e)
                self._engine = None
        if self.engine_kind == "continuous" and self._engine is None:
            from .serving import ContinuousEngine
            try:
                self._engine = ContinuousEngine(
                    self.workflow, max_slots=self.max_slots,
                    buckets=self.buckets,
                    max_context=self.max_context,
                    decode_block=self.decode_block,
                    page_size=self.page_size, pages=self.pages,
                    spec_gamma=self.spec_gamma,
                    beam_width=self.beam_width,
                    draft=self.draft,
                    quant_weights=self.quant_weights,
                    quant_kv=self.quant_kv,
                    artifact=self.artifact,
                    prefix_cache=self.prefix_cache,
                    prefill_chunk=self.prefill_chunk,
                    name=self.name).start()
                # the engine-side serve.replica_death site (fired per
                # decode tick) settles the in-flight tickets with
                # resume progress, then this hook tears the HTTP
                # front down — on its own thread: the tick thread
                # must not join itself through engine.stop()
                self._engine.on_death = self._on_replica_death
            except VelesError as e:
                # a stack the paged pool cannot serve may still be a
                # recurrent LM (Embedding → LSTM/SSM → LMHead): try
                # the O(1)-state slot pool before degrading to the
                # window worker — same request plane, pageless slots.
                # Knob-geometry mistakes (bucket > max_context,
                # max_slots < 1) raise ValueError and PROPAGATE: the
                # operator asked for slot-pool batching and must not
                # silently get the per-shape-compiling worker instead.
                try:
                    self._engine = self._build_recurrent_engine()
                    self.info("%s: recurrent stack (paged pool said: "
                              "%s); serving via the O(1)-state slot "
                              "pool", self.name, e)
                except VelesError:
                    self.warning("%s: continuous batching unavailable "
                                 "(%s); serving via the window worker",
                                 self.name, e)
                    self._engine = None
        api = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                api.debug("http: " + fmt, *args)

            def do_GET(self):
                if health.handle_health(self, self.path):
                    return
                if handle_trace_spans(self, self.path,
                                      name="serve.%s" % api.name):
                    return
                if handle_metrics_history(self, self.path,
                                          name="serve.%s" % api.name):
                    return
                if handle_alerts(self, self.path):
                    return
                if self.path == "/metrics":
                    # Prometheus scrape surface (telemetry counters —
                    # the structured successor of the /stats dict; the
                    # decode dispatch/token counters land here from
                    # nn/sampling.py + nn/speculative.py), plus this
                    # unit's serving gauges
                    from .telemetry.alerts import render_firing
                    from .telemetry.counters import (
                        METRICS_CONTENT_TYPE, metrics_text)
                    text = metrics_text(api._metrics_gauges()) \
                        + render_firing()
                    bytes_reply(self, 200, text.encode(),
                                METRICS_CONTENT_TYPE)
                    return
                # legacy ops surface: the micro-batcher's effectiveness
                # as one JSON dict (predates /metrics; kept for
                # dashboards that already read it)
                if self.path != api.path + "/stats":
                    self.send_error(404)
                    return
                engine = api._engine       # stop() may null it mid-GET
                stats = {
                    "requests_served": api.requests_served,
                    "batches_run": api.batches_run,
                    "max_batch": api.max_batch,
                    "queue_depth": len(api._queue),
                    "speculative_enabled": api.draft is not None,
                    "engine": ("continuous" if engine is not None
                               else "window"),
                    "modes": list(api.MODES)}
                if engine is not None:
                    stats["continuous"] = engine.stats()
                json_reply(self, 200, stats)

            def do_POST(self):
                arrived = time.perf_counter()
                if self.path == api.path + "/drain":
                    # admin face of the SIGTERM drain: flip /readyz
                    # to draining, stop admission, reply immediately —
                    # the drain itself (finish in-flight, tear down)
                    # runs on its own thread so this handler answers
                    started = api.begin_drain()
                    threading.Thread(target=api.drain, daemon=True,
                                     name=api.name + ".drain").start()
                    json_reply(self, 200, {
                        "status": "draining",
                        "already_draining": not started,
                        "in_flight": api._inflight,
                        "queue_depth": len(api._queue)})
                    return
                if self.path == api.path + "/profile":
                    try:
                        body = read_json_object(self)
                    except ValueError as e:
                        json_reply(self, 400, {"error":
                                               "bad request: %s" % e})
                        return
                    json_reply(self, *api.profile(body.get("seconds", 5)))
                    return
                if self.path != api.path:
                    self.send_error(404)
                    return
                try:
                    fire_fault("serve.request")
                except FaultInjected as e:
                    # injected serving faults DEGRADE (shed + Retry-
                    # After, counted), never escape as a traceback.
                    # No ticket exists yet — mint an id so even this
                    # shed is correlatable by a router retry
                    from .serving.scheduler import new_request_id
                    health.shed(self, retry_after=1.0, reason=str(e),
                                request_id=new_request_id())
                    return
                try:
                    req = api._parse(read_json_object(self))
                except (ValueError, KeyError) as e:
                    json_reply(self, 400, {"error":
                                           "bad request: %s" % e})
                    return
                # API admission assigns the request's id (threaded
                # through lifecycle spans, flight events and the
                # response body by the Ticket itself) — unless a fleet
                # router already assigned one upstream
                # the request's own deadline (when set) replaces the
                # global request_timeout for the queue sweep AND this
                # handler's wait — capped by the global so a client
                # can only tighten, never extend
                wait_budget = api.request_timeout
                if req.get("deadline_ms"):
                    wait_budget = min(wait_budget,
                                      req["deadline_ms"] / 1000.0)
                ticket = _Ticket(
                    deadline=time.time() + wait_budget,
                    request_id=req.get("request_id"),
                    mode=req.get("mode", "greedy"),
                    trace_id=req.get("trace_id"),
                    attempt=req.get("attempt", 1),
                    stream=bool(req.get("stream")),
                    arrived=arrived)
                if api._draining:
                    health.shed(self, retry_after=5.0,
                                reason="server draining",
                                request_id=ticket.request_id)
                    return
                engine = api._engine
                # every decode mode rides the slot pool when the
                # engine can hold it — speculative needs the pooled
                # draft + the engine's fixed gamma, beam the engine's
                # fixed width; anything else (and any geometry the
                # pool rejects) falls back to the window worker
                reject = (None if engine is None
                          else engine.accepts(req))
                via_engine = engine is not None and reject is None
                if reject is not None and not getattr(
                        engine, "window_fallback", True):
                    # the stack has a block the window plane cannot
                    # run: what its engine refuses is refused, with
                    # the engine's own line
                    json_reply(self, 400, {
                        "error": reject,
                        "request_id": ticket.request_id})
                    return
                if req.get("resume_k") and not via_engine \
                        and req["mode"] != "greedy":
                    # a sampled resume re-enters a per-slot PRNG
                    # stream only the slot pool owns — the window
                    # plane cannot honor it id-exactly (greedy is
                    # deterministic and MAY ride the window plane
                    # with its folded prompt). 409 tells the router:
                    # drop the resume, retry this request from
                    # scratch.
                    json_reply(self, 409, {
                        "error": "resume not servable here (%s); "
                                 "retry without resume_tokens"
                                 % (reject or "no continuous engine"),
                        "request_id": ticket.request_id})
                    return
                if via_engine:
                    # the continuous-batching plane: admitted into a
                    # KV-cache slot at the next step boundary; a full
                    # queue sheds exactly like the window plane
                    if api._closing:
                        health.shed(self, retry_after=5.0,
                                    reason="server shutting down",
                                    request_id=ticket.request_id)
                        return
                    if not engine.submit(req, ticket,
                                         max_queue=api.max_queue,
                                         checked=True):
                        # False means queue bound OR a closing engine
                        # (stop() racing this handler) — the shutdown
                        # answer must match the api._closing path above
                        if engine.closing:
                            health.shed(self, retry_after=5.0,
                                        reason="server shutting down",
                                        request_id=ticket.request_id)
                        else:
                            health.shed(
                                self, retry_after=1.0,
                                reason="generation queue full (%d/%d)"
                                % (engine.scheduler.queue_depth(),
                                   api.max_queue),
                                request_id=ticket.request_id)
                        return
                else:
                    with api._cv:
                        if api._closing:
                            health.shed(self, retry_after=5.0,
                                        reason="server shutting down",
                                        request_id=ticket.request_id)
                            return
                        if len(api._queue) >= api.max_queue:
                            health.shed(
                                self, retry_after=1.0,
                                reason="generation queue full (%d/%d)"
                                % (len(api._queue), api.max_queue),
                                request_id=ticket.request_id)
                            return
                        api._queue.append((req, ticket))
                        api._cv.notify()
                with api._cv:
                    api._inflight += 1
                try:
                    if ticket.stream:
                        self._stream_reply(ticket, via_engine,
                                           wait_budget)
                    else:
                        self._await_and_reply(ticket, via_engine,
                                              wait_budget)
                finally:
                    with api._cv:
                        api._inflight -= 1
                        api._cv.notify_all()

            def _await_and_reply(self, ticket, via_engine,
                                 wait_budget=None):
                if wait_budget is None:
                    wait_budget = api.request_timeout
                try:
                    # the replica-death chaos point, request-path
                    # site: the request IS in flight (admitted to a
                    # plane above) when the fault fires — raise tears
                    # this replica's HTTP front down. The teardown's
                    # abort settles every in-flight ticket with its
                    # resume progress, and this handler waits for
                    # that settle to emit the DYING GASP: a 503 whose
                    # body carries {resume: {tokens, tokens_done}},
                    # the record a failover retry continues from. A
                    # teardown too wedged to settle the ticket drops
                    # the connection as before (a true SIGKILL — the
                    # retry re-decodes from scratch); crash exits the
                    # process with the slave-death code either way.
                    fire_fault("serve.replica_death")
                except FaultInjected:
                    api.warning("%s: injected replica death — tearing "
                                "down the serving front mid-request",
                                api.name)
                    threading.Thread(target=api.stop, daemon=True,
                                     name=api.name + ".death").start()
                    self.close_connection = True
                    if not ticket.event.wait(10.0) \
                            or ticket.error is None:
                        return      # wedged: the client sees a dead peer
                    json_reply(self, ticket.code,
                               ticket.error_payload(),
                               headers={"Retry-After": "1"})
                    return
                # slack past the deadline: the queue-side expiry
                # (503 + Retry-After, counted) should win the race
                # against this handler's own last-resort 504
                if not ticket.event.wait(wait_budget + 1.0):
                    json_reply(self, 504,
                               {"error": "generation timed out",
                                "request_id": ticket.request_id})
                    return
                if via_engine and not (ticket.error is not None
                                       and ticket.code == 503):
                    # the window worker counts requests its batches
                    # actually decoded — decode errors included, but
                    # never 503 sheds/expiries (those are answered
                    # before any batch runs); engine answers are
                    # tallied here on the same terms so /stats compares
                    # the planes like for like. Handler threads run
                    # concurrently — the += must not lose updates
                    # against them or the worker.
                    with api._cv:
                        api.requests_served += 1
                if ticket.error is not None:
                    headers = None
                    # pressure-scaled backoff hint (no-op with QoS
                    # off: the hint equals the stamped value then)
                    retry_after = ticket.retry_after_hint()
                    if retry_after:
                        import math as _math
                        headers = {"Retry-After": str(max(1, int(
                            _math.ceil(retry_after))))}
                    json_reply(self, ticket.code,
                               ticket.error_payload(),
                               headers=headers)
                    return
                json_reply(self, 200, ticket.result)

            def _stream_reply(self, ticket, via_engine,
                              wait_budget=None):
                """``stream=true``: chunked-transfer SSE — one
                ``data: {tokens, i}`` event per step boundary (the
                engine pushes at chunk ends; window-plane requests
                burst once at completion) and a terminal
                ``data: {done: true, ...}`` event carrying the full
                result (success) or ``error_payload()`` (failure —
                resume progress included, so a router proxying this
                stream re-streams only the remainder after a replica
                death)."""
                if wait_budget is None:
                    wait_budget = api.request_timeout
                import queue as _q
                try:
                    # the replica-death chaos point, request-path
                    # site — same contract as the buffered path: the
                    # teardown's abort settles the ticket with resume
                    # progress, and the gasp goes out as the only
                    # (terminal) event of the stream
                    fire_fault("serve.replica_death")
                except FaultInjected:
                    api.warning("%s: injected replica death — tearing "
                                "down the serving front mid-request",
                                api.name)
                    threading.Thread(target=api.stop, daemon=True,
                                     name=api.name + ".death").start()
                    self.close_connection = True
                    if not ticket.event.wait(10.0) \
                            or ticket.error is None:
                        return
                    json_reply(self, ticket.code,
                               ticket.error_payload(),
                               headers={"Retry-After": "1"})
                    return
                from ._http import sse_event, sse_headers
                sse_headers(self)

                def event(payload):
                    # handler threads: the time the request plane is
                    # busy serialising and writing (a histogram too,
                    # telemetry/spans.py SPAN_HISTOGRAMS)
                    with span("serving.stream.write"):
                        sse_event(self, payload)

                sent = 0
                deadline = time.time() + wait_budget + 1.0
                try:
                    while True:
                        budget = deadline - time.time()
                        if budget <= 0:
                            event({"done": True, "code": 504,
                                   "error": "generation timed out",
                                   "request_id": ticket.request_id})
                            return
                        try:
                            item = ticket.next_stream_item(
                                timeout=min(budget, 2.0))
                        except _q.Empty:
                            continue
                        if item is None:
                            break
                        event({"tokens": item, "i": sent,
                               "request_id": ticket.request_id})
                        if not sent:
                            # the first token is on the wire: the last
                            # of a request's waits ends here
                            ticket.mark_first_write()
                        sent += len(item)
                    # /stats parity with the buffered path: count
                    # every via-engine terminal the batch actually
                    # decoded — decode errors included, never 503
                    # sheds/expiries
                    if via_engine and not (ticket.error is not None
                                           and ticket.code == 503):
                        with api._cv:
                            api.requests_served += 1
                    if ticket.error is not None:
                        event(dict(ticket.error_payload(),
                                   done=True, code=ticket.code))
                        return
                    result = ticket.result if isinstance(
                        ticket.result, dict) else {
                            "tokens": list(ticket.result or ())}
                    # window-plane (and early-retired) tokens the
                    # step-boundary pushes never covered burst out
                    # before the terminal event
                    tail = list(result.get("tokens") or ())[sent:]
                    if tail:
                        event({"tokens": tail, "i": sent,
                               "request_id": ticket.request_id})
                    event(dict(result, done=True))
                except (BrokenPipeError, ConnectionResetError,
                        OSError):
                    # client went away mid-stream: the decode settles
                    # the ticket on its own; nothing to answer
                    api.debug("%s: streaming client disconnected "
                              "(%s)", api.name, ticket.request_id)

        self._closing = False
        self._draining = False
        self._inflight = 0
        self._worker = threading.Thread(target=self._worker_loop,
                                        daemon=True,
                                        name=self.name + ".genworker")
        self._worker.start()
        self._service = HTTPService(Handler, self.port,
                                    self.name + ".http")
        self.port = self._service.port
        self._service.start_serving()
        # watchtower sampler (telemetry/timeseries.py): a no-op config
        # read unless root.common.telemetry.watch.enabled — the
        # provider shares _metrics_gauges with /metrics, so the ring
        # records exactly what a scrape would have seen
        from .telemetry import timeseries
        timeseries.add_gauge_provider("serve.%s" % self.name,
                                      self._metrics_gauges)
        timeseries.maybe_start()
        # a tensor-parallel engine publishes its mesh-slice shape on
        # /readyz so a fleet router learns replica = N-chip slice from
        # the probe it already makes (router.py folds it into
        # veles_router_chips; the replica count stays per-slice)
        if getattr(self._engine, "tp", 1) > 1:
            health.set_info("tp", {"devices": int(self._engine.tp),
                                   "axis": "model"})
        health.mark_ready("serve.%s" % self.name)
        self.info("%s: generation API on http://127.0.0.1:%d%s "
                  "(modes: %s%s)", self.name, self.port, self.path,
                  "/".join(self.MODES),
                  "" if self.draft is not None else "; no draft — "
                  "speculative disabled")
        return None

    def run(self) -> None:
        """Standalone service: nothing to do per graph pass."""

    # -- graceful drain ------------------------------------------------------
    #: the longest capture ``POST <path>/profile`` takes, seconds
    PROFILE_MAX_SECONDS = 60.0

    def profile(self, seconds) -> tuple:
        """``POST <path>/profile {"seconds": N}``: a ``jax.profiler``
        capture of this running server, N seconds (at most
        :data:`PROFILE_MAX_SECONDS`) into a fresh subdirectory of
        ``profile_dir``; the answer comes when the capture is written
        and names the directory (read it with ``veles_tpu trace
        self-time DIR``). The engine's tick spans are in it as host
        annotations, beside the device's operations. 403 unless the
        server was started with a profile directory, 409 while another
        capture runs, 400 for a bad ``seconds``. Returns (status,
        payload)."""
        if not self.profile_dir:
            return 403, {"error": "profiling is off: start the server "
                                  "with --profile-dir"}
        if isinstance(seconds, bool) \
                or not isinstance(seconds, (int, float)) \
                or not seconds > 0:
            return 400, {"error": "'seconds' must be a positive number"}
        seconds = min(float(seconds), self.PROFILE_MAX_SECONDS)
        if not self._profile_lock.acquire(blocking=False):
            return 409, {"error": "a profile capture is running"}
        try:
            import jax
            directory = os.path.join(
                self.profile_dir, "%s-%d" % (
                    time.strftime("%Y%m%d-%H%M%S"), next(_profile_ids)))
            os.makedirs(directory, exist_ok=True)
            try:
                jax.profiler.start_trace(directory)
            except RuntimeError as e:
                # the profiler is one to a process: someone else's
                # session (a benchmark's, say) holds it
                return 409, {"error": "profiler busy: %s" % e}
            try:
                # stop() ends a capture early rather than wait it out
                self._profile_stop.wait(seconds)
            finally:
                jax.profiler.stop_trace()
            self.info("%s: %.1f s profile capture -> %s", self.name,
                      seconds, directory)
            return 200, {"dir": directory, "seconds": seconds}
        finally:
            self._profile_lock.release()

    def begin_drain(self) -> bool:
        """Stop admission and flip ``/readyz`` to draining (the load
        balancer's cue to spill elsewhere) while in-flight tickets
        keep decoding; ``/healthz`` stays green throughout. True when
        this call started the drain, False when one was already under
        way. The actual wait + teardown is :meth:`drain`."""
        with self._cv:
            if self._draining:
                return False
            self._draining = True
        health.mark_draining("serve.%s" % self.name)
        self.info("%s: draining — admission stopped, %d in flight",
                  self.name, self._inflight)
        return True

    def _on_replica_death(self) -> None:
        """Engine-tick ``serve.replica_death`` hook: the engine has
        already settled every in-flight ticket with its resume
        progress (the dying gasp the waiting handlers reply with);
        tear the front down on a fresh thread — never the tick
        thread, which ``engine.stop()`` would join into itself."""
        threading.Thread(target=self.stop, daemon=True,
                         name=self.name + ".death").start()

    def drain(self, grace: Optional[float] = None,
              handoff: Optional[bool] = None) -> bool:
        """SIGTERM-grade graceful shutdown: :meth:`begin_drain`, then
        — with ``handoff`` (default
        ``root.common.serving.drain_handoff`` = True) — the engine
        HANDS BACK every in-flight request at the next step boundary:
        each ticket settles 503 + Retry-After with its emitted-token
        prefix attached, so a fleet router re-dispatches it elsewhere
        with ``resume_tokens`` and the drain's latency is bounded by
        a step boundary plus the handlers' replies — never by the
        longest co-tenant generation. Window-plane stragglers (and
        ``handoff=False`` drains) wait out up to ``grace`` seconds
        (default ``root.common.serving.drain_grace`` = 30) before
        ``stop()`` aborts them through the same first-terminal
        ``fail()`` path (503 + resume progress, counted once). True
        when nothing was still in flight at teardown."""
        self.begin_drain()
        if handoff is None:
            handoff = bool(root.common.serving.get("drain_handoff",
                                                   True))
        if handoff and self._engine is not None:
            handed = self._engine.handoff()
            if handed:
                self.info("%s: drain handed %d in-flight request(s) "
                          "back with resume progress", self.name,
                          handed)
        if grace is None:
            # no falsy-zero rewrite: drain_grace = 0 legitimately
            # means "abort stragglers immediately"
            grace = float(root.common.serving.get("drain_grace", 30.0))
        deadline = time.time() + grace
        with self._cv:
            while self._inflight and time.time() < deadline:
                self._cv.wait(timeout=min(
                    0.2, max(0.01, deadline - time.time())))
            drained = self._inflight == 0
        self.info("%s: drain %s (%d still in flight)", self.name,
                  "complete" if drained else "grace expired",
                  self._inflight)
        self.stop()
        return drained

    def stop(self) -> None:
        self._profile_stop.set()
        with self._lifecycle:
            from .telemetry import timeseries
            timeseries.remove_gauge_provider("serve.%s" % self.name)
            if self._service is not None:
                self._service.stop_serving()
                self._service = None
            with self._cv:
                self._closing = True
                self._cv.notify_all()
            if self._worker is not None:
                self._worker.join(timeout=5)
                self._worker = None
            if self._engine is not None:
                if getattr(self._engine, "tp", 1) > 1:
                    health.set_info("tp")
                self._engine.stop()
                self._engine = None
            # after the worker is down — its beats must not
            # re-register a heartbeat that would age out on a
            # long-lived process
            health.forget("serve.%s" % self.name)
