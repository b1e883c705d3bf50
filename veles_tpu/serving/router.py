"""Fault-tolerant serving fleet: the replica router.

ROADMAP item 1's topology made buildable: N engine replicas (each a
``GenerationAPI`` front over its own ``ContinuousEngine``) behind ONE
HTTP router that keeps the fleet answering while individual replicas
die, drain, or saturate. The reference platform's headline capability
was surviving scale-out — ~100 nodes under a master that tolerated
slave death (manualrst_veles_distributed_training.rst:6); this module
is that story for the serving side, assembled from parts that already
exist:

- **health-gated admission** — a background probe scrapes every
  replica's ``/readyz`` and ``/metrics`` (reusing
  :mod:`~veles_tpu.telemetry.fleet` parsing) and ranks replicas by
  slot occupancy, so the router spills load away from saturated
  replicas and never routes to a not-ready (or draining) one;
- **per-replica circuit breakers** — consecutive attempt failures
  open the breaker for a backoff interval computed by
  :class:`~veles_tpu.resilience.retry.RetryPolicy`'s seeded-jitter
  curve (fleet-wide probe herds decorrelate, seeded runs reproduce);
  after the interval ONE half-open probe request is allowed through —
  success closes the breaker, failure re-opens it for longer;
- **idempotent failover** — every routed request carries a
  process-unique ``request_id`` (minted here, adopted by the
  replica's Ticket, echoed in every response body — success, shed
  and expiry alike); an attempt that dies mid-decode (replica crash,
  timeout, 5xx) is retried on another replica under a bounded retry
  budget, and a first-terminal answer latch guarantees EXACTLY-ONCE
  response accounting: a slow-then-successful first attempt can
  never double-answer — the late result is dropped and counted
  (``veles_router_duplicate_answers_total``);
- **graceful drain** — SIGTERM (wired by the ``veles-tpu route``
  CLI) and the ``POST /drain`` admin endpoint flip ``/readyz`` to
  draining, stop admission (503 + Retry-After), finish in-flight
  requests, then exit — same contract the engine API honors;
- **supervised respawn** — :class:`ReplicaSupervisor` generalizes
  the PR 9 elastic ``Supervisor`` spawn/classify/respawn plane from
  training generations to long-lived serving replicas: training
  reaps the whole generation when one host dies (survivors are
  wedged in collectives), a serving fleet respawns ONLY the hole —
  with seeded backoff — while the router routes around it (AOT
  serve-artifacts make the respawned replica's cold start cheap).

Retryability policy: connection-level failures (refused, reset,
timeout, torn response) and every HTTP 5xx fail over; 2xx–4xx are
the replica's answer and are delivered as-is (a 400 is the client's
problem on every replica — retrying it is a retry storm, not
resilience).

Chaos surface: ``router.replica_request`` fires before every proxied
attempt (raise = the attempt fails like a dead replica);
``serve.replica_death`` (fired replica-side in the GenerationAPI
request path) makes a live replica ACTUALLY tear its HTTP front down
mid-decode. CLI: ``veles-tpu route URL [URL ...]``; operator guide:
docs/services.md "Serving fleet".
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

from .._http import (HTTPService, bytes_reply, handle_alerts,
                     handle_metrics_history, handle_trace_spans,
                     json_reply, read_json_object)
from ..config import root
from ..error import VelesError
from ..logger import Logger
from ..resilience import health
from ..resilience.faults import FaultInjected, fire as fire_fault
from ..resilience.retry import RetryPolicy
from ..telemetry import fleet
from ..telemetry.counters import (METRICS_CONTENT_TYPE, inc,
                                  metrics_text)
from .journal import RequestJournal
#: RESUME_MODES: the single source (scheduler.py) of which decode
#: modes' emitted-token prefix a failover retry can resume —
#: everything else retries from scratch
from .scheduler import RESUME_MODES as _RESUMABLE_MODES
from .scheduler import (new_request_id, new_trace_id,
                        request_tracing_enabled)
from ..telemetry.spans import emit as emit_span

#: every counter the fleet router increments — registered with HELP
#: strings in telemetry/counters.py DESCRIPTIONS and asserted zero in
#: non-fleet runs by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
ROUTER_COUNTERS = (
    "veles_router_requests_total",
    "veles_router_attempts_total",
    "veles_router_failovers_total",
    "veles_router_replica_errors_total",
    "veles_router_breaker_opens_total",
    "veles_router_duplicate_answers_total",
    "veles_router_respawns_total",
)


def _resume_budget(body: Dict) -> Tuple[List[int], Optional[int]]:
    """Parse a request body's client-supplied resume prefix and TOTAL
    generation budget (``n_new`` is the REMAINING budget when a
    prefix rides along), popping ``resume_tokens`` from the body —
    the retry loops recompute both per attempt so a dropped prefix
    (409) widens the retry back to a full redo, never delivers
    short. Unparsable resume/n_new disables router-side resume
    handling entirely (empty prefix, None budget): the body forwards
    as-is and the replica answers the 400. SINGLE SOURCE for
    :meth:`FleetRouter.route` and :meth:`FleetRouter.route_stream` —
    this arithmetic was review-hardened once and two copies must not
    drift."""
    try:
        prefix = [int(t) for t in (body.get("resume_tokens") or ())]
        total_new = int(body.get("n_new", 16)) + len(prefix)
    except (TypeError, ValueError):
        return [], None
    body.pop("resume_tokens", None)
    return prefix, total_new


def normalize_endpoint(url: str) -> str:
    """Roster entry → replica base URL: bare ``host:port`` gets
    ``http://``, trailing slashes and a trailing ``/metrics`` (the
    scrape-roster spelling) are dropped — so the router and
    ``veles-tpu metrics aggregate`` accept the same endpoint list."""
    url = str(url).strip()
    if "://" not in url:
        url = "http://" + url
    url = url.rstrip("/")
    if url.endswith("/metrics"):
        url = url[:-len("/metrics")]
    return url


def router_config() -> Dict[str, Any]:
    """The router knob block ``root.common.router.*`` (CLI flags of
    ``veles-tpu route`` override per invocation)."""
    node = root.common.router
    return {
        "probe_interval": float(node.get("probe_interval", 1.0) or 1.0),
        "probe_timeout": float(node.get("probe_timeout", 2.0) or 2.0),
        "failure_threshold": int(node.get("failure_threshold", 3) or 3),
        "retry_budget": int(node.get("retry_budget", 2)),
        "attempt_timeout": float(node.get("attempt_timeout", 10.0)
                                 or 10.0),
        "request_timeout": float(node.get("request_timeout", 120.0)
                                 or 120.0),
        # no falsy-zero rewrite here: drain_grace = 0 legitimately
        # means "abort stragglers immediately"
        "drain_grace": float(node.get("drain_grace", 30.0)),
        # durable request journal (serving/journal.py): empty = the
        # PR 12 memory-only admission plane
        "journal": str(node.get("journal", "") or ""),
    }


class CircuitBreaker:
    """Per-replica failure gate: ``failure_threshold`` consecutive
    failures open it; while open, :meth:`allow` refuses for a backoff
    interval riding :meth:`RetryPolicy.backoff`'s seeded-jitter curve
    (the interval grows with every re-open); after the interval ONE
    half-open probe is admitted — success closes the breaker and
    resets the curve, failure re-opens it for longer. Thread-safe;
    ``clock`` is injectable for deterministic tests."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, failure_threshold: int = 3,
                 backoff: Optional[RetryPolicy] = None,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.failure_threshold = max(1, int(failure_threshold))
        self.backoff = backoff if backoff is not None else RetryPolicy(
            base_delay=0.5, max_delay=30.0, name="breaker")
        self._clock = clock
        self._lock = threading.Lock()
        self.state = self.CLOSED
        self.failures = 0          # consecutive, resets on success
        self.trips = 0             # times opened — drives the curve
        self.open_until = 0.0
        self._probing = False      # half-open: one probe in flight

    def allow(self) -> bool:
        """May a request be routed here right now? Claims the single
        half-open probe slot when it grants one — the caller MUST
        follow through with an attempt (and settle it), or the slot
        stays claimed until the next open interval."""
        with self._lock:
            if self.state == self.CLOSED:
                return True
            if self.state == self.OPEN:
                if self._clock() < self.open_until:
                    return False
                self.state = self.HALF_OPEN
                self._probing = False
            if self._probing:
                return False
            self._probing = True
            return True

    def record_success(self) -> None:
        with self._lock:
            self.state = self.CLOSED
            self.failures = 0
            self.trips = 0
            self._probing = False

    def record_failure(self) -> bool:
        """Account one failed attempt; True when THIS failure opened
        (or re-opened) the breaker — the caller counts the
        transition."""
        with self._lock:
            self.failures += 1
            if self.state == self.HALF_OPEN or (
                    self.state == self.CLOSED
                    and self.failures >= self.failure_threshold):
                self.state = self.OPEN
                self.trips += 1
                # the attempt index is capped so the delay saturates
                # at max_delay instead of 2**trips overflowing
                self.open_until = self._clock() + self.backoff.backoff(
                    min(self.trips, 16))
                self._probing = False
                return True
            if self.state == self.OPEN:
                self._probing = False
            return False


class Replica:
    """One roster entry: the endpoint, its breaker, and the latest
    probe snapshot (readiness + occupancy) the admission ranking
    reads. Probe fields are written by the router's probe thread and
    read by handler threads — single-attribute writes, no torn
    state worth a lock."""

    def __init__(self, url: str,
                 breaker: Optional[CircuitBreaker] = None) -> None:
        self.url = normalize_endpoint(url)
        self.breaker = breaker if breaker is not None \
            else CircuitBreaker()
        self.up = False
        self.ready = False
        self.draining = False
        self.slots = 0
        self.slots_busy = 0
        self.queue_depth = 0
        #: mesh-slice width behind this endpoint (1 = solo chip): a
        #: tensor-parallel replica publishes {"tp": {"devices": N}} on
        #: /readyz — the roster counts it as ONE replica spanning N
        #: chips, never as N replicas
        self.tp_devices = 1
        self.probe_error: Optional[str] = None
        self.last_probe = 0.0

    def occupancy(self) -> float:
        """Busy fraction of the replica's slot pool (0 when unknown)
        — the spill ranking's primary key."""
        return self.slots_busy / self.slots if self.slots else 0.0

    def snapshot(self) -> Dict[str, Any]:
        return {
            "url": self.url, "up": self.up, "ready": self.ready,
            "draining": self.draining, "slots": self.slots,
            "slots_busy": self.slots_busy,
            "queue_depth": self.queue_depth,
            "tp_devices": self.tp_devices,
            "occupancy": round(self.occupancy(), 4),
            "breaker": self.breaker.state,
            "probe_error": self.probe_error,
        }


class _Answer:
    """First-terminal answer latch for one routed request — the
    router-side twin of ``Ticket``'s exactly-once transition: however
    many attempts eventually complete, exactly one :meth:`offer`
    wins; every loser is reported False (the caller counts it as a
    dropped duplicate). The embedded condition doubles as the
    routing loop's wakeup for attempt settles."""

    def __init__(self) -> None:
        self.cv = threading.Condition()
        self.done = False
        self.status: Optional[int] = None
        self.body: Optional[Dict] = None
        self.retry_after: Optional[str] = None
        self.replica: Optional[Replica] = None
        self.request_id: Optional[str] = None
        self.trace_id: Optional[str] = None
        #: replica attempts the routing loop dispatched for this
        #: request — stamped into the journal's terminal record
        self.attempts: int = 0
        #: why routing gave up, when ``done`` stays False
        self.reason: Optional[str] = None

    def offer(self, status: int, body: Dict,
              retry_after: Optional[str] = None,
              replica: Optional[Replica] = None) -> bool:
        with self.cv:
            first = not self.done
            if first:
                self.done = True
                self.status = int(status)
                self.body = body
                self.retry_after = retry_after
                self.replica = replica
            self.cv.notify_all()
            return first


class _Attempt:
    """One proxied attempt's settle state. Breaker/counter accounting
    happens exactly once per attempt, on the FIRST settle — whether
    that is the attempt thread reporting its outcome or the routing
    loop declaring an attempt timeout and moving on (the thread may
    still land a late answer through the latch afterwards)."""

    def __init__(self, replica: Replica, answered: _Answer) -> None:
        self.replica = replica
        self._answered = answered
        self._lock = threading.Lock()
        self.settled = False
        self.failed = False
        self.reason: Optional[str] = None
        #: a failed attempt's {tokens, tokens_done} resume record (a
        #: 5xx dying gasp / drain handoff) — the routing loop folds it
        #: into the next attempt's resume_tokens
        self.resume_payload: Optional[Dict] = None
        #: the replica answered 409 to a resume attempt: drop the
        #: accumulated prefix and retry from scratch
        self.drop_resume = False

    def _settle(self, failed: bool, reason: Optional[str],
                benign: bool = False) -> bool:
        with self._lock:
            if self.settled:
                return False
            self.settled = True
            self.failed = failed
            self.reason = reason
        if failed and not benign:
            inc("veles_router_replica_errors_total")
            if self.replica.breaker.record_failure():
                inc("veles_router_breaker_opens_total")
        elif not failed:
            self.replica.breaker.record_success()
        with self._answered.cv:
            self._answered.cv.notify_all()
        return True

    def fail(self, reason: str) -> bool:
        return self._settle(True, reason)

    def fail_benign(self, reason: str) -> bool:
        """Settle as failed WITHOUT breaker/error accounting — for a
        healthy answer that merely refuses this attempt's shape (a
        409 resume rejection is the replica being honest, not the
        replica being dead)."""
        return self._settle(True, reason, benign=True)

    def succeed(self) -> bool:
        return self._settle(False, None)


class FleetRouter(Logger):
    """HTTP front fanning a GenerationAPI-compatible surface out over
    N replica endpoints (module doc has the full story). Surfaces on
    the router port:

    - ``POST <path>`` (default ``/generate``) — route with failover;
    - ``GET /healthz`` / ``/readyz`` — the router's own health plane
      (``/readyz`` flips to draining during a drain);
    - ``GET /metrics`` — the router's counters + fleet gauges;
    - ``GET /fleet/metrics`` — live fleet-wide aggregation over the
      roster (telemetry/fleet.py merge, quantiles recomputed);
    - ``GET /roster`` — the replica roster as JSON (readiness,
      occupancy, breaker state); saved to a file it feeds
      ``veles-tpu metrics aggregate --endpoints-file`` directly;
    - ``POST /drain`` — graceful drain (also wired to SIGTERM by the
      CLI).
    """

    def __init__(self, endpoints: Sequence[str], port: int = 0,
                 path: str = "/generate",
                 probe_interval: Optional[float] = None,
                 probe_timeout: Optional[float] = None,
                 failure_threshold: Optional[int] = None,
                 retry_budget: Optional[int] = None,
                 attempt_timeout: Optional[float] = None,
                 request_timeout: Optional[float] = None,
                 journal_dir: Optional[str] = None,
                 journal_fsync: bool = True,
                 name: str = "router") -> None:
        super().__init__()
        cfg = router_config()
        urls = [normalize_endpoint(u) for u in endpoints]
        if not urls:
            raise VelesError("a fleet router needs at least one "
                             "replica endpoint")
        if len(set(urls)) != len(urls):
            raise VelesError("duplicate replica endpoints: %s" % urls)
        self.name = name
        self.path = path
        self.port = int(port)
        self.probe_interval = float(
            cfg["probe_interval"] if probe_interval is None
            else probe_interval)
        self.probe_timeout = float(
            cfg["probe_timeout"] if probe_timeout is None
            else probe_timeout)
        self.retry_budget = max(0, int(
            cfg["retry_budget"] if retry_budget is None
            else retry_budget))
        self.attempt_timeout = float(
            cfg["attempt_timeout"] if attempt_timeout is None
            else attempt_timeout)
        self.request_timeout = float(
            cfg["request_timeout"] if request_timeout is None
            else request_timeout)
        threshold = int(cfg["failure_threshold"]
                        if failure_threshold is None
                        else failure_threshold)
        self.replicas = [
            Replica(u, CircuitBreaker(failure_threshold=threshold))
            for u in urls]
        self._service: Optional[HTTPService] = None
        self._probe_thread: Optional[threading.Thread] = None
        self._replay_thread: Optional[threading.Thread] = None
        self._closing = False
        self._draining = False
        self._inflight = 0
        self._cv = threading.Condition()
        self._wake = threading.Event()
        self.requests_routed = 0
        # durable request journal (serving/journal.py): every
        # accepted request is on disk before its first dispatch and
        # marked terminal on answer — a router SIGKILL loses zero
        # accepted requests (start() replays the unanswered tail)
        jdir = (cfg["journal"] if journal_dir is None
                else (journal_dir or ""))
        self.journal: Optional[RequestJournal] = (
            RequestJournal(jdir, fsync=journal_fsync,
                           name=name + ".journal") if jdir else None)
        #: admits minus terminals since start (plus the replay
        #: backlog) — the journal-pending gauge without re-reading
        #: the segments on every /metrics scrape
        self._journal_outstanding = 0
        # overload governor (serving/overload.py, docs/services.md
        # "Overload & QoS"): None unless root.common.router.qos —
        # the feature-off router runs the exact pre-QoS path
        from .overload import governor_from_config
        self.governor = governor_from_config()

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "FleetRouter":
        if self._service is not None:
            return self
        self._closing = False
        self._draining = False
        self.probe_all()               # admission state before traffic
        self._wake.clear()
        self._probe_thread = threading.Thread(
            target=self._probe_loop, daemon=True,
            name=self.name + ".probe")
        self._probe_thread.start()
        self._service = HTTPService(self._make_handler(), self.port,
                                    self.name + ".http")
        self.port = self._service.port
        self._service.start_serving()
        # watchtower sampler (telemetry/timeseries.py): the router's
        # gauges() carries the fleet-level sums the probe loop keeps
        # fresh, so fleet series ride the same ring as local ones.
        # No-op unless root.common.telemetry.watch.enabled.
        from ..telemetry import timeseries
        timeseries.add_gauge_provider("router.%s" % self.name,
                                      self.gauges)
        timeseries.maybe_start()
        health.mark_ready("router.%s" % self.name)
        health.heartbeats.beat("router.%s" % self.name)
        self.info("%s: routing %s on http://127.0.0.1:%d%s "
                  "(retry budget %d, breaker threshold %d%s)",
                  self.name,
                  [r.url for r in self.replicas], self.port, self.path,
                  self.retry_budget,
                  self.replicas[0].breaker.failure_threshold,
                  ", journal %s" % self.journal.directory
                  if self.journal else "")
        if self.journal is not None:
            self._replay_thread = threading.Thread(
                target=self._replay_journal, daemon=True,
                name=self.name + ".replay")
            self._replay_thread.start()
        return self

    def stop(self) -> None:
        from ..telemetry import timeseries
        timeseries.remove_gauge_provider("router.%s" % self.name)
        self._closing = True
        self._wake.set()
        if self._probe_thread is not None:
            self._probe_thread.join(timeout=5)
            self._probe_thread = None
        if self._replay_thread is not None:
            self._replay_thread.join(timeout=10)
            self._replay_thread = None
        if self._service is not None:
            self._service.stop_serving()
            self._service = None
        if self.journal is not None:
            self.journal.close()
        health.forget("router.%s" % self.name)

    # -- journal replay ------------------------------------------------------
    def _replay_journal(self) -> None:
        """Re-dispatch every journaled-but-unanswered request from
        before the restart: ordered by ``enqueued_at``, idempotent by
        ``request_id`` (the journal's terminal records dedupe
        however many crash-loops re-ran), expired entries shed with
        a terminal 503 record carrying the id. Torn records were
        already quarantined (counted) by the journal's salvage pass —
        a damaged journal degrades, it never refuses to start."""
        try:
            pending = self.journal.pending()
        except Exception:       # noqa: BLE001 — degrade, don't die
            self.exception("%s: journal replay scan failed; serving "
                           "new traffic only", self.name)
            return
        if not pending:
            return
        with self._cv:
            self._journal_outstanding += len(pending)
        self.info("%s: replaying %d journaled request(s) from before "
                  "the restart", self.name, len(pending))
        t_replay = time.time()
        replayed = shed = 0
        try:
            replayed, shed = self._replay_pending(pending)
        finally:
            if request_tracing_enabled():
                # the journal-tail replay as one timeline event: a
                # restarted router's first seconds explain themselves
                emit_span("route.replay", t_replay,
                          time.time() - t_replay,
                          pending=len(pending), replayed=replayed,
                          shed=shed)

    def _replay_pending(self, pending) -> Tuple[int, int]:
        replayed = shed = 0
        for rec in pending:
            if self._closing or self._draining:
                # still journaled — the next start retries
                return replayed, shed
            rid = rec["request_id"]
            tid = rec.get("trace_id")
            body = rec.get("body")
            enqueued = float(rec.get("enqueued_at", 0.0) or 0.0)
            if not isinstance(body, dict):
                self.journal.done(rid, 400, "unreplayable",
                                  trace_id=tid)
                with self._cv:
                    self._journal_outstanding -= 1
                continue
            if time.time() > enqueued + self.request_timeout:
                # past its useful life: the shed a live router would
                # have answered, recorded with the id
                inc("veles_shed_requests_total")
                self.journal.done(rid, 503, "expired", trace_id=tid)
                shed += 1
                self.warning("%s: journaled request %s expired before "
                             "replay (enqueued %.0fs ago)", self.name,
                             rid, time.time() - enqueued)
                with self._cv:
                    self._journal_outstanding -= 1
                continue
            inc("veles_journal_replayed_total")
            try:
                # the replayed body resumes under its ORIGINAL
                # trace_id (the admit record's) — one trace tells the
                # whole story across the router restart. A journaled
                # stream=true request replays BUFFERED: its client is
                # gone, so replay only completes the work and records
                # the terminal — there is nobody to stream to.
                body = dict(body, request_id=rid)
                body.pop("stream", None)
                answered = self.route(body)
                status = answered.status if answered.done else 503
                outcome = ("replayed" if answered.done
                           else "unanswered: %s"
                           % (answered.reason or ""))
                self.journal.done(rid, int(status), outcome,
                                  trace_id=tid,
                                  attempts=answered.attempts)
                replayed += 1
            except Exception:   # noqa: BLE001 — replay must survive
                # one poisonous entry must not abandon the rest of
                # the backlog; it stays pending for the next start
                self.exception("%s: replay of %s failed; continuing "
                               "with the remaining backlog",
                               self.name, rid)
                continue
            with self._cv:
                self._journal_outstanding -= 1
        return replayed, shed

    # -- graceful drain ------------------------------------------------------
    def begin_drain(self) -> bool:
        """Stop admission and flip the router's ``/readyz`` to
        draining; in-flight requests keep being served. True when
        this call started the drain."""
        with self._cv:
            if self._draining:
                return False
            self._draining = True
        health.mark_draining("router.%s" % self.name)
        self.info("%s: draining — admission stopped, %d in flight",
                  self.name, self._inflight)
        return True

    def drain(self, grace: Optional[float] = None) -> bool:
        """SIGTERM-grade shutdown: :meth:`begin_drain`, wait up to
        ``grace`` seconds (default ``root.common.router.drain_grace``
        = 30) for in-flight requests, then :meth:`stop`. True when
        the drain emptied in time."""
        self.begin_drain()
        if grace is None:
            grace = router_config()["drain_grace"]
        deadline = time.time() + grace
        with self._cv:
            while self._inflight and time.time() < deadline:
                self._cv.wait(timeout=min(
                    0.2, max(0.01, deadline - time.time())))
            drained = self._inflight == 0
        self.info("%s: drain %s", self.name,
                  "complete" if drained else "grace expired")
        self.stop()
        return drained

    @property
    def draining(self) -> bool:
        return self._draining

    # -- health-gated admission ----------------------------------------------
    def _probe_loop(self) -> None:
        while not self._closing:
            if self._wake.wait(timeout=self.probe_interval):
                return
            self.probe_all()

    def probe_all(self) -> None:
        """One probe sweep: every replica's ``/readyz`` (admission
        gate) + ``/metrics`` (occupancy ranking, parsed by the fleet
        module), probed CONCURRENTLY so the sweep is bounded by the
        slowest single replica, not the sum — a hung replica must
        not stretch everyone else's staleness past
        ``probe_interval``. Also the router's own liveness beat."""
        threads = [threading.Thread(target=self._probe, args=(r,),
                                    daemon=True,
                                    name=self.name + ".probe1")
                   for r in self.replicas]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        health.heartbeats.beat("router.%s" % self.name)

    def _probe(self, replica: Replica) -> None:
        replica.last_probe = time.time()
        try:
            req = urllib.request.Request(replica.url + "/readyz")
            with urllib.request.urlopen(
                    req, timeout=self.probe_timeout) as r:
                code, payload = r.status, json.loads(r.read() or b"{}")
        except urllib.error.HTTPError as e:
            # 503 IS a readiness answer (not ready / draining)
            code = e.code
            try:
                payload = json.loads(e.read() or b"{}")
            except ValueError:
                payload = {}
        except Exception as e:  # noqa: BLE001 — a down replica is data
            replica.up = False
            replica.ready = False
            replica.draining = False
            replica.probe_error = "%s: %s" % (type(e).__name__, e)
            return
        replica.up = True
        replica.ready = code == 200
        replica.draining = payload.get("status") == "draining"
        replica.probe_error = None
        # replica = mesh slice: a TP engine rides its slice shape on
        # the /readyz payload (resilience/health.py set_info) — the
        # probe the router already makes learns the chip span for free
        try:
            tp_info = payload.get("tp")
            replica.tp_devices = max(1, int(
                (tp_info or {}).get("devices", 1)))
        except (TypeError, ValueError):
            replica.tp_devices = 1
        body, _err = fleet.scrape(replica.url,
                                  timeout=self.probe_timeout)
        if body is not None:
            gauges = fleet.parse_metrics_text(body)["gauges"]
            replica.slots = int(gauges.get("veles_serving_slots", 0))
            replica.slots_busy = int(
                gauges.get("veles_serving_slots_busy", 0))
            replica.queue_depth = int(
                gauges.get("veles_serving_queue_depth",
                           gauges.get("veles_generate_queue_depth",
                                      0)))
            if replica.tp_devices == 1:
                # older front without the readyz info key: the
                # veles_serving_tp gauge carries the same fact
                replica.tp_devices = max(1, int(
                    gauges.get("veles_serving_tp", 1)))

    def pick(self, exclude: Sequence[Replica] = ()) -> Optional[Replica]:
        """Least-occupied READY replica whose breaker admits a
        request — never a not-ready/draining one, never one already
        tried for this request. Breaker side effects make the order
        matter: candidates are ranked first, then asked, and the
        first to grant wins (a granted half-open probe slot is always
        used)."""
        ranked = sorted(
            (r for r in self.replicas
             if r not in exclude and r.ready),
            key=lambda r: (r.occupancy(), r.queue_depth, r.url))
        for replica in ranked:
            if replica.breaker.allow():
                return replica
        return None

    # -- routing -------------------------------------------------------------
    def _request_budget(self, body: Dict) -> float:
        """Per-request routing budget: a sane ``deadline_ms`` CAPS
        the global request_timeout (deadline propagation's router
        leg — the replica applies the same cap to its ticket, so one
        number bounds the whole client→router→replica→sweep chain); a
        client can only tighten, never extend. Garbage values fall
        back to the global (the replica's _parse answers the 400)."""
        budget = self.request_timeout
        dl = body.get("deadline_ms")
        if dl is None or isinstance(dl, bool):
            return budget
        try:
            dl = float(dl)
        except (TypeError, ValueError):
            return budget
        if dl > 0:
            budget = min(budget, dl / 1000.0)
        return budget

    def _attempt(self, replica: Replica, data: bytes, rid: str,
                 answered: _Answer, state: _Attempt,
                 timeout: float, prefix: Sequence[int] = (),
                 base_k: int = 0) -> None:
        try:
            fire_fault("router.replica_request")
        except FaultInjected as e:
            state.fail("injected replica failure: %s" % e)
            return
        try:
            req = urllib.request.Request(
                replica.url + self.path, data=data,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=timeout) as r:
                status = r.status
                body = json.loads(r.read() or b"{}")
                retry_after = r.headers.get("Retry-After")
        except urllib.error.HTTPError as e:
            status = e.code
            try:
                body = json.loads(e.read() or b"{}")
            except ValueError:
                body = {"error": "replica answered %d" % e.code}
            retry_after = e.headers.get("Retry-After")
        except Exception as e:      # noqa: BLE001 — the failure class
            # connection refused/reset, timeout, torn response: the
            # replica is (acting) dead — fail over (from scratch: a
            # dropped connection carries no progress)
            state.fail("%s: %s" % (type(e).__name__, e))
            return
        if status >= 500:
            # a dying gasp / drain handoff 503 carries the attempt's
            # emitted-token prefix — the routing loop folds it into
            # the NEXT attempt's resume_tokens so the failover
            # re-enters the decode at tokens_done, not token 0.
            # Validated ELEMENT-wise here: a garbage gasp from a
            # misbehaving replica must degrade to a from-scratch
            # retry, never throw inside route()/the replay thread
            resume = (body or {}).get("resume")
            if isinstance(resume, dict) \
                    and isinstance(resume.get("tokens"), list):
                try:
                    state.resume_payload = {
                        "tokens": [int(t) for t in resume["tokens"]]}
                except (TypeError, ValueError):
                    pass
            state.fail("replica %s answered %d (%s)"
                       % (replica.url, status,
                          (body or {}).get("error", "")))
            return
        if status == 409 and prefix:
            # the replica cannot honor this resume (no continuous
            # engine, geometry overflow): drop the prefix, the loop
            # retries from scratch — a 409 is an answer about the
            # RESUME, not about the replica's health, so it neither
            # advances the breaker nor burns the replica's roster
            # slot (the loop re-admits it for the scratch retry)
            state.drop_resume = True
            state.fail_benign("replica %s cannot resume (%s)"
                              % (replica.url,
                                 (body or {}).get("error", "")))
            return
        if status == 200 and (prefix or base_k) \
                and isinstance(body.get("tokens"), list):
            # stitch the resumed answer: the replica decoded only the
            # remaining budget — prepend the prefix, then drop the
            # first base_k tokens (a CLIENT-supplied resume base is
            # the client's own context: they asked for the remaining
            # n_new, not a re-delivery of what they already hold; a
            # dropped-and-redone base is sliced off the full redo the
            # same way, id-exact for seeded modes)
            stitched = [int(t) for t in prefix] + body["tokens"]
            body = dict(body, tokens=stitched[base_k:])
            if len(prefix) > base_k:
                body["resumed_from"] = len(prefix)
        # 2xx–4xx: the replica's answer, deliver as-is (first wins).
        # Offer BEFORE settling: settle notifies the routing loop,
        # and a loop that wakes to a settled-but-unanswered attempt
        # would dispatch a spurious extra attempt
        first = answered.offer(status, body, retry_after=retry_after,
                               replica=replica)
        state.succeed()
        if not first:
            inc("veles_router_duplicate_answers_total")
            self.warning("%s: duplicate answer for %s from %s "
                         "dropped (an earlier attempt already "
                         "answered)", self.name, rid, replica.url)

    def route(self, body: Dict) -> _Answer:
        """Route one parsed request body with health-gated selection,
        breaker-aware failover and the exactly-once answer latch.
        A failed attempt whose answer carried resume progress (a
        dying gasp, a drain handoff) makes the next attempt a
        token-level RESUME: ``resume_tokens`` + the remaining
        ``n_new`` ride the retry body, and the final answer is
        stitched back to the full sequence. Returns the latch —
        ``done`` False means no replica could answer inside the
        budget (the HTTP face sheds 503).

        Tracing: the router mints a ``trace_id`` at admission (or
        adopts the caller's) and forwards it — with the 1-based
        ``attempt`` number — in every attempt body, so every
        replica-side span and flight event of this request carries
        the fleet-wide key. The routing decisions themselves become
        spans (gated by ``root.common.trace.requests``, like the
        replica lifecycle spans): ``route.request`` brackets the
        whole route, ``route.attempt`` each replica try (endpoint,
        outcome, status, ``tokens_done`` carried into a resume),
        ``route.probe`` a half-open breaker's recovery attempt, and
        ``route.backoff`` the open interval a failure scheduled —
        failover/breaker/resume decisions are timeline events, not
        just counter increments."""
        rid = body.get("request_id") or new_request_id()
        tid = body.get("trace_id") or new_trace_id()
        body = dict(body, request_id=rid, trace_id=tid)
        mode = str(body.get("mode", "greedy"))
        resumable = mode in _RESUMABLE_MODES
        trace_on = request_tracing_enabled()
        # total generation budget: a client/replayed body may itself
        # carry a resume prefix (its n_new is then the REMAINING
        # budget) — _resume_budget pops it, shared with route_stream
        prefix, total_new = _resume_budget(body)
        #: the CLIENT's own resume base: sliced off the final answer
        #: (they asked for the remaining n_new, not a re-delivery)
        base_k = len(prefix)
        inc("veles_router_requests_total")
        answered = _Answer()
        answered.request_id = rid
        answered.trace_id = tid
        t_req = time.time()
        budget = self._request_budget(body)
        deadline = t_req + budget
        tried: List[Replica] = []
        n_attempts = 0
        last_reason = "no ready replica"
        while len(tried) <= self.retry_budget:
            remaining = deadline - time.time()
            if remaining <= 0:
                last_reason = ("request budget %.0fs exhausted"
                               % budget)
                break
            if tried and self.governor is not None \
                    and not self.governor.allow_retry():
                # the router-wide retry token bucket: a storm of
                # failing attempts must not amplify into a storm of
                # failover retries — deny and answer with the last
                # attempt's error
                last_reason = ("failover retry denied by the "
                               "router retry budget (storm control)")
                break
            replica = self.pick(exclude=tried)
            if replica is None:
                break
            # a granted half-open slot IS the breaker's recovery
            # probe — this attempt doubles as it (route.probe span)
            probing = replica.breaker.state \
                == CircuitBreaker.HALF_OPEN
            trips_before = replica.breaker.trips
            if tried:
                inc("veles_router_failovers_total")
                self.info("%s: failing %s over to %s (%s)%s",
                          self.name, rid, replica.url, last_reason,
                          " resuming at token %d" % len(prefix)
                          if prefix else "")
            tried.append(replica)
            inc("veles_router_attempts_total")
            n_attempts += 1
            tokens_done = len(prefix)
            attempt_body = dict(body, attempt=n_attempts)
            if total_new is not None:
                # n_new is recomputed from the TOTAL budget every
                # attempt: a dropped prefix (409) must widen the
                # retry back to a full redo, never deliver short
                attempt_body["n_new"] = total_new - len(prefix)
                if prefix:
                    attempt_body["resume_tokens"] = list(prefix)
                    inc("veles_resume_attempts_total")
            data = json.dumps(attempt_body).encode()
            state = _Attempt(replica, answered)
            t_att = time.time()
            threading.Thread(
                target=self._attempt,
                args=(replica, data, rid, answered, state,
                      max(0.1, remaining), tuple(prefix), base_k),
                daemon=True,
                name="%s.attempt" % self.name).start()
            # wait for THIS attempt to settle, anyone to answer, or
            # the per-attempt patience to run out (the thread keeps
            # running — a late success still wins the latch first-
            # come; the loop just stops waiting for it)
            wait_until = min(deadline,
                             time.time() + self.attempt_timeout)
            with answered.cv:
                while (not answered.done and not state.settled
                        and time.time() < wait_until):
                    answered.cv.wait(timeout=min(
                        0.05, max(0.005, wait_until - time.time())))
            # declare the timeout BEFORE emitting the attempt span,
            # so the span reads the outcome the loop acted on
            if not answered.done and not state.settled:
                if state.fail("attempt timed out after %.1fs on %s"
                              % (self.attempt_timeout, replica.url)):
                    last_reason = state.reason or "attempt timeout"
            if trace_on:
                self._note_attempt(replica, state, answered, rid,
                                   tid, n_attempts, t_att,
                                   tokens_done, probing, trips_before)
            if answered.done:
                break
            if state.settled and state.failed:
                last_reason = state.reason or "replica failure"
                if state.drop_resume:
                    # the 409 replica is healthy — give its roster
                    # slot back so the from-scratch retry may land
                    # on it again
                    prefix = []
                    if replica in tried:
                        tried.remove(replica)
                elif resumable and total_new is not None \
                        and state.resume_payload is not None:
                    gained = [int(t) for t in
                              state.resume_payload["tokens"]]
                    if gained and len(prefix) + len(gained) \
                            < total_new:
                        prefix = prefix + gained
                continue
        answered.attempts = n_attempts
        if not answered.done:
            answered.reason = last_reason
        if trace_on:
            now = time.time()
            tags: Dict[str, Any] = {
                "request_id": rid, "trace_id": tid, "mode": mode,
                "attempts": n_attempts,
                "outcome": ("answered" if answered.done
                            else "unanswered")}
            if answered.done:
                tags["status"] = int(answered.status)
            else:
                tags["reason"] = last_reason
            # the ROOT span of the fleet trace: one lane-topping
            # bracket per routed request, on the router's clock
            emit_span("route.request", t_req, now - t_req, **tags)
        return answered

    # -- streaming proxy ------------------------------------------------------
    class _ClientGone(Exception):
        """The CLIENT's socket died mid-stream. Distinct from replica
        failures on purpose: a closed browser tab must neither advance
        a healthy replica's circuit breaker nor trigger failover
        re-decodes — the routing loop just stops."""

    @staticmethod
    def _sse_events(resp):
        """Parse an SSE byte stream into JSON event dicts (lines the
        replica's ``data:`` framing carries; torn/non-JSON lines are
        skipped — the stream's health is judged by its terminal
        event, not by cosmetic damage)."""
        for line in resp:
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            try:
                ev = json.loads(line[5:].strip())
            except ValueError:
                continue
            if isinstance(ev, dict):
                yield ev

    def route_stream(self, body: Dict, handler) -> Tuple[int, str, int]:
        """Proxy one ``stream=true`` request: SSE events pipe from the
        serving replica to the client AS THEY ARRIVE; an attempt that
        dies mid-stream (replica crash, 5xx gasp, torn stream) fails
        over with ``resume_tokens`` = everything already forwarded, so
        the retry RE-STREAMS ONLY THE REMAINDER — the client's wire
        sees every token exactly once and one terminal event. A 409
        resume refusal drops the prefix and retries from scratch,
        skipping tokens the client already holds. Attempts are
        SEQUENTIAL (events already on the client's wire bind the
        stream to one replica at a time — no hedging; the buffered
        path keeps its latch-raced attempts). Returns
        ``(status, outcome, attempts)`` for the journal's terminal
        record. Response headers commit lazily: a request no replica
        could even start is shed as plain JSON 503."""
        rid = body["request_id"]
        tid = body["trace_id"]
        mode = str(body.get("mode", "greedy"))
        resumable = mode in _RESUMABLE_MODES
        trace_on = request_tracing_enabled()
        body = dict(body)
        prefix, total_new = _resume_budget(body)
        base_k = len(prefix)
        inc("veles_router_requests_total")
        t_req = time.time()
        budget = self._request_budget(body)
        deadline = t_req + budget
        state = {"headers": False, "sent": 0}

        def event(payload):
            from .._http import sse_event, sse_headers
            try:
                if not state["headers"]:
                    sse_headers(handler)
                    state["headers"] = True
                sse_event(handler, payload)
            except (BrokenPipeError, ConnectionResetError,
                    OSError) as e:
                # client-write failure, NOT a replica failure
                raise FleetRouter._ClientGone(str(e)) from e

        def emit_gap(full_toks):
            """Keep the client's INCREMENTAL wire complete: forward
            any absolute positions of ``full_toks`` it has not seen
            as one token event (tokens a dying replica decoded but
            never streamed arrive via its gasp; a buffered-200
            replica delivers everything this way)."""
            gap = [int(t) for t in full_toks[base_k + state["sent"]:]]
            if gap:
                event({"tokens": gap, "i": state["sent"],
                       "request_id": rid, "trace_id": tid})
                state["sent"] += len(gap)

        def finish(status, outcome, n_attempts, tags=None):
            if trace_on:
                t: Dict[str, Any] = {
                    "request_id": rid, "trace_id": tid, "mode": mode,
                    "attempts": n_attempts, "outcome": outcome,
                    "stream": 1}
                t.update(tags or {})
                if outcome == "answered":
                    t["status"] = int(status)
                emit_span("route.request", t_req,
                          time.time() - t_req, **t)
            return int(status), outcome, n_attempts

        tried: List[Replica] = []
        n_attempts = 0
        last_reason = "no ready replica"
        while len(tried) <= self.retry_budget:
            remaining = deadline - time.time()
            if remaining <= 0:
                last_reason = ("request budget %.0fs exhausted"
                               % budget)
                break
            if tried and self.governor is not None \
                    and not self.governor.allow_retry():
                last_reason = ("failover retry denied by the "
                               "router retry budget (storm control)")
                break
            replica = self.pick(exclude=tried)
            if replica is None:
                break
            if tried:
                inc("veles_router_failovers_total")
                self.info("%s: failing stream %s over to %s (%s)%s",
                          self.name, rid, replica.url, last_reason,
                          " resuming at token %d" % len(prefix)
                          if prefix else "")
            tried.append(replica)
            inc("veles_router_attempts_total")
            n_attempts += 1
            t_att = time.time()
            attempt_body = dict(body, attempt=n_attempts, stream=True)
            if total_new is not None:
                attempt_body["n_new"] = total_new - len(prefix)
                if prefix:
                    attempt_body["resume_tokens"] = list(prefix)
                    inc("veles_resume_attempts_total")
            attempt_tokens: List[int] = []
            failed_reason = None
            drop_resume = False
            done_event = None
            delivered = None      # (status, body) for a 4xx pass-through
            try:
                fire_fault("router.replica_request")
                req = urllib.request.Request(
                    replica.url + self.path,
                    data=json.dumps(attempt_body).encode(),
                    headers={"Content-Type": "application/json"})
                # the SOCKET timeout is per blocking read: a steadily
                # streaming replica never trips it, a wedged one
                # (accepted the connection, sends nothing) fails
                # after attempt_timeout so healthy replicas still get
                # tried inside the request budget — the buffered
                # path's per-attempt patience, stream-shaped
                resp = urllib.request.urlopen(
                    req, timeout=max(0.1, min(self.attempt_timeout,
                                              remaining)))
            except FaultInjected as e:
                failed_reason = "injected replica failure: %s" % e
            except urllib.error.HTTPError as e:
                status = e.code
                try:
                    err_body = json.loads(e.read() or b"{}")
                except ValueError:
                    err_body = {"error": "replica answered %d"
                                % status}
                if status == 409 and prefix:
                    drop_resume = True
                    failed_reason = ("replica %s cannot resume (%s)"
                                     % (replica.url,
                                        err_body.get("error", "")))
                elif status >= 500:
                    gasp = (err_body or {}).get("resume")
                    if resumable and isinstance(gasp, dict) \
                            and isinstance(gasp.get("tokens"), list):
                        try:
                            attempt_tokens = [int(t) for t in
                                              gasp["tokens"]]
                        except (TypeError, ValueError):
                            attempt_tokens = []
                    failed_reason = ("replica %s answered %d (%s)"
                                     % (replica.url, status,
                                        err_body.get("error", "")))
                else:
                    delivered = (status, err_body)
            except Exception as e:  # noqa: BLE001 — the failure class
                failed_reason = "%s: %s" % (type(e).__name__, e)
            else:
                # `with resp`: the upstream socket closes on EVERY
                # exit — terminal break, mid-stream failure, client
                # gone — never left to GC (one leaked fd per attempt
                # would EMFILE a long-lived router)
                with resp:
                    ctype = resp.headers.get("Content-Type", "")
                    if "event-stream" not in ctype:
                        # buffered 200 (replica streams disabled): one
                        # burst + terminal, stitched like the latch
                        # path
                        try:
                            full = json.loads(resp.read() or b"{}")
                        except ValueError:
                            full = {}
                        if isinstance(full.get("tokens"), list):
                            attempt_tokens = [int(t) for t in
                                              full["tokens"]]
                            done_event = dict(full, done=True)
                        else:
                            failed_reason = (
                                "replica %s answered a bodyless 200"
                                % replica.url)
                    else:
                        try:
                            for ev in self._sse_events(resp):
                                if ev.get("done"):
                                    done_event = ev
                                    break
                                toks = ev.get("tokens")
                                if not isinstance(toks, list):
                                    continue
                                abs_start = len(prefix) \
                                    + len(attempt_tokens)
                                attempt_tokens.extend(int(t)
                                                      for t in toks)
                                # forward only what the client has
                                # not seen (a scratch retry after a
                                # dropped resume re-emits the whole
                                # sequence)
                                skip = (base_k + state["sent"]) \
                                    - abs_start
                                out = [int(t)
                                       for t in toks[max(0, skip):]]
                                if out:
                                    event({"tokens": out,
                                           "i": state["sent"],
                                           "request_id": rid,
                                           "trace_id": tid})
                                    state["sent"] += len(out)
                        except FleetRouter._ClientGone as e:
                            # the CLIENT died, not the replica: no
                            # breaker advance, no failover re-decode —
                            # just stop (the replica settles its
                            # ticket on its own)
                            self.debug("%s: streaming client for %s "
                                       "disconnected (%s)", self.name,
                                       rid, e)
                            return finish(
                                499, "client disconnected mid-stream",
                                n_attempts)
                        except Exception as e:  # noqa: BLE001
                            failed_reason = (
                                "stream from %s died: %s: %s"
                                % (replica.url, type(e).__name__, e))
                        if done_event is None \
                                and failed_reason is None:
                            failed_reason = (
                                "stream from %s ended without a "
                                "terminal event" % replica.url)
            if done_event is not None and failed_reason is None \
                    and done_event.get("error") is not None:
                # the replica's dying gasp arrived AS the terminal
                # stream event: a failed attempt whose resume record
                # covers everything it decoded
                gasp = done_event.get("resume")
                if resumable and isinstance(gasp, dict) \
                        and isinstance(gasp.get("tokens"), list):
                    try:
                        gained = [int(t) for t in gasp["tokens"]]
                        if len(gained) >= len(attempt_tokens):
                            attempt_tokens = gained
                    except (TypeError, ValueError):
                        pass
                failed_reason = ("replica %s failed mid-stream (%s)"
                                 % (replica.url,
                                    done_event.get("error")))
                done_event = None
            if trace_on:
                try:
                    emit_span(
                        "route.attempt", t_att, time.time() - t_att,
                        endpoint=replica.url, attempt=n_attempts,
                        request_id=rid, trace_id=tid, stream=1,
                        tokens_done=len(prefix),
                        outcome=("answered" if done_event is not None
                                 or delivered is not None
                                 else "failed"),
                        **({"reason": failed_reason}
                           if failed_reason else {}))
                except Exception:   # noqa: BLE001 — observability only
                    pass
            if delivered is not None:
                # 2xx–4xx non-stream answers are the replica's word
                replica.breaker.record_success()
                status, err_body = delivered
                try:
                    if state["headers"]:
                        event(dict(err_body, done=True, code=status))
                    else:
                        json_reply(handler, status, err_body)
                except (FleetRouter._ClientGone, BrokenPipeError,
                        ConnectionResetError, OSError):
                    pass        # the answer existed; client left
                return finish(status, "answered", n_attempts)
            if done_event is not None:
                replica.breaker.record_success()
                full_toks = prefix + attempt_tokens
                final = dict(done_event)
                final["tokens"] = full_toks[base_k:]
                final.setdefault("request_id", rid)
                final.setdefault("trace_id", tid)
                if len(prefix) > base_k:
                    final["resumed_from"] = len(prefix)
                try:
                    # complete the incremental wire first (tokens a
                    # buffered-200 replica or a tail-in-done-only
                    # stream never sent as token events), THEN the
                    # authoritative terminal
                    emit_gap(full_toks)
                    event(final)
                except FleetRouter._ClientGone:
                    self.debug("%s: streaming client for %s went "
                               "away before the terminal event",
                               self.name, rid)
                return finish(200, "answered", n_attempts)
            # failed attempt: breaker + resume accounting, then retry
            last_reason = failed_reason or "replica failure"
            if drop_resume:
                prefix = []
                if replica in tried:
                    tried.remove(replica)
            else:
                inc("veles_router_replica_errors_total")
                if replica.breaker.record_failure():
                    inc("veles_router_breaker_opens_total")
                if resumable and total_new is not None \
                        and attempt_tokens \
                        and len(prefix) + len(attempt_tokens) \
                        < total_new:
                    # a gasp may carry tokens the stream never
                    # delivered — forward them BEFORE resuming past
                    # them, so the client's incremental wire has no
                    # hole (the retry decodes only the remainder)
                    try:
                        emit_gap(prefix + attempt_tokens)
                    except FleetRouter._ClientGone as e:
                        self.debug("%s: streaming client for %s "
                                   "disconnected (%s)", self.name,
                                   rid, e)
                        return finish(
                            499, "client disconnected mid-stream",
                            n_attempts)
                    prefix = prefix + attempt_tokens
        # nobody could answer
        if state["headers"]:
            try:
                event({"done": True, "code": 503,
                       "error": "no replica could answer: %s"
                                % last_reason,
                       "request_id": rid, "retry_after": 1.0})
            except FleetRouter._ClientGone:
                pass
            return finish(503, "unanswered: %s" % last_reason,
                          n_attempts)
        health.shed(handler, retry_after=1.0,
                    reason="no replica could answer: %s" % last_reason,
                    request_id=rid)
        return finish(503, "unanswered: %s" % last_reason, n_attempts)

    def _note_attempt(self, replica: Replica, state: _Attempt,
                      answered: _Answer, rid: str, tid: str,
                      attempt_no: int, t0: float, tokens_done: int,
                      probing: bool, trips_before: int) -> None:
        """Retrospective span emission for one settled-or-abandoned
        attempt: ``route.attempt`` always (endpoint, outcome, http
        status when this replica answered, the resume prefix length
        carried in), ``route.probe`` when the attempt was a
        half-open breaker probe, and ``route.backoff`` when THIS
        failure opened the breaker (the span covers the scheduled
        open interval, so the failover gap is a visible timeline
        event). Never raises — observability only."""
        try:
            now = time.time()
            if answered.done and answered.replica is replica:
                outcome: str = "answered"
                status: Optional[int] = answered.status
            elif state.settled and state.failed:
                outcome, status = "failed", None
            elif state.settled:
                # settled-success without winning the latch: succeed()
                # runs only after offer(), which sets done+replica
                # together — so this replica cannot be the winner
                # here; its answer was the dropped duplicate
                outcome, status = "duplicate", None
            else:
                # still running when the loop moved on (late answers
                # may yet win the latch)
                outcome, status = "pending", None
            tags: Dict[str, Any] = {
                "endpoint": replica.url, "attempt": attempt_no,
                "request_id": rid, "trace_id": tid,
                "tokens_done": tokens_done, "outcome": outcome}
            if status is not None:
                tags["status"] = int(status)
            if state.reason:
                tags["reason"] = state.reason
            emit_span("route.attempt", t0, now - t0, **tags)
            if probing:
                emit_span("route.probe", t0, now - t0,
                          endpoint=replica.url, attempt=attempt_no,
                          request_id=rid, trace_id=tid,
                          outcome=outcome)
            breaker = replica.breaker
            if breaker.trips > trips_before \
                    and breaker.state == CircuitBreaker.OPEN:
                # the scheduled open interval, emitted at open time:
                # an interval on this host's wall clock equal to the
                # breaker's monotonic hold
                hold = max(0.0, breaker.open_until - breaker._clock())
                emit_span("route.backoff", now, hold,
                          endpoint=replica.url, request_id=rid,
                          trace_id=tid, trips=breaker.trips)
        except Exception:       # noqa: BLE001 — observability only
            pass

    # -- surfaces ------------------------------------------------------------
    def gauges(self) -> Dict[str, Any]:
        ready = sum(1 for r in self.replicas if r.ready)
        open_breakers = sum(1 for r in self.replicas
                            if r.breaker.state != CircuitBreaker.CLOSED)
        gauges = {
            "veles_router_replicas":
                (len(self.replicas), "Replica endpoints this router "
                                     "fans out over (a tensor-"
                                     "parallel mesh slice counts "
                                     "once, however many chips it "
                                     "spans)"),
            "veles_router_chips":
                (sum(max(1, r.tp_devices) for r in self.replicas),
                 "Accelerator chips behind the roster (each "
                 "replica's mesh-slice width, 1 for a solo engine)"),
            "veles_router_replicas_ready":
                (ready, "Replicas currently admitting (ready, per "
                        "the last /readyz probe)"),
            "veles_router_breakers_open":
                (open_breakers, "Replicas whose circuit breaker is "
                                "open or half-open"),
            "veles_router_inflight":
                (self._inflight, "Requests currently being routed"),
            "veles_router_draining":
                (1 if self._draining else 0,
                 "1 while the router is draining (admission "
                 "stopped, in-flight finishing)"),
            # fleet-level occupancy: sums of the probe-thread
            # snapshots across the roster — the series the
            # watchtower's fleet rules (queue_depth_high) and the
            # `veles-tpu watch` dashboard read from the router
            "veles_fleet_slots":
                (sum(r.slots for r in self.replicas),
                 "Decode slots across all roster replicas (last "
                 "probe)"),
            "veles_fleet_slots_busy":
                (sum(r.slots_busy for r in self.replicas),
                 "Busy decode slots across all roster replicas "
                 "(last probe)"),
            "veles_fleet_queue_depth":
                (sum(r.queue_depth for r in self.replicas),
                 "Queued requests across all roster replicas (last "
                 "probe)"),
        }
        if self.journal is not None:
            gauges["veles_router_journal_pending"] = (
                max(0, self._journal_outstanding),
                "Journaled requests admitted but not yet terminal "
                "(in flight or awaiting replay)")
            gauges["veles_router_journal_enabled"] = (
                1, "1 when the durable request journal is on")
        if self.governor is not None:
            snap = self.governor.snapshot()
            gauges["veles_qos_admit_rate"] = (
                snap["veles_qos_admit_rate"],
                "AIMD batch admission rate (1.0 = unthrottled, "
                "falls multiplicatively while TTFT p99 exceeds "
                "the SLO)")
            gauges["veles_qos_brownout_level"] = (
                snap["veles_qos_brownout_level"],
                "Brownout ladder level (0 normal, 1 cap n_new, "
                "2 no speculative, 3 shed batch)")
            gauges["veles_qos_retry_tokens"] = (
                snap["veles_qos_retry_tokens"],
                "Failover retry tokens currently available in the "
                "router-wide storm-control bucket")
        return gauges

    def roster(self) -> Dict[str, Any]:
        """The live replica roster — saved to a file this is directly
        consumable by ``veles-tpu metrics aggregate
        --endpoints-file`` (fleet scraping and routing share one
        roster)."""
        return {
            "router": self.name,
            "path": self.path,
            "draining": self._draining,
            "endpoints": [r.snapshot() for r in self.replicas],
        }

    def _make_handler(self):
        router = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                router.debug("http: " + fmt, *args)

            def do_GET(self):
                if health.handle_health(self, self.path):
                    return
                if handle_trace_spans(self, self.path,
                                      name="router.%s" % router.name):
                    return
                if handle_metrics_history(self, self.path,
                                          name="router.%s"
                                          % router.name):
                    return
                if handle_alerts(self, self.path):
                    return
                if self.path == "/metrics":
                    from ..telemetry.alerts import render_firing
                    text = metrics_text(router.gauges()) \
                        + render_firing()
                    bytes_reply(self, 200, text.encode(),
                                METRICS_CONTENT_TYPE)
                    return
                if self.path == "/fleet/metrics":
                    # live fleet-wide aggregation over the roster —
                    # counters/buckets summed, quantiles recomputed
                    # (telemetry/fleet.py), scraped on demand
                    agg = fleet.aggregate(
                        [r.url for r in router.replicas],
                        timeout=router.probe_timeout)
                    bytes_reply(self, 200,
                                fleet.render(agg).encode(),
                                METRICS_CONTENT_TYPE)
                    return
                if self.path == "/roster":
                    json_reply(self, 200, router.roster())
                    return
                self.send_error(404)

            def do_POST(self):
                if self.path == "/drain":
                    started = router.begin_drain()
                    threading.Thread(target=router.drain,
                                     daemon=True,
                                     name=router.name
                                     + ".drain").start()
                    json_reply(self, 200, {
                        "status": "draining",
                        "already_draining": not started,
                        "in_flight": router._inflight})
                    return
                if self.path != router.path:
                    self.send_error(404)
                    return
                if router._draining or router._closing:
                    health.shed(self, retry_after=5.0,
                                reason="router draining",
                                request_id=new_request_id())
                    return
                try:
                    body = read_json_object(self)
                except ValueError as e:
                    json_reply(self, 400,
                               {"error": "bad request: %s" % e})
                    return
                if not isinstance(body.get("stream", False), bool):
                    # the replica's _parse would answer this 400 —
                    # the router must not coerce a truthy non-bool
                    # ("false", 1) into an SSE stream the replica
                    # would have refused
                    json_reply(self, 400,
                               {"error": "bad request: 'stream' "
                                         "must be a boolean"})
                    return
                gov = router.governor
                if gov is not None:
                    # adaptive admission BEFORE the durability
                    # boundary: a throttled request was never
                    # accepted, so nothing to journal or replay.
                    # Interactive always passes; brownout mutations
                    # (n_new cap, speculative off) apply to whatever
                    # is admitted
                    reason = gov.admit(body)
                    if reason is not None:
                        health.shed(self,
                                    retry_after=gov.retry_after(),
                                    reason=reason,
                                    request_id=body.get("request_id")
                                    or new_request_id())
                        return
                    gov.degrade(body)
                # the durability boundary: the request exists in the
                # journal BEFORE its first dispatch, so a router
                # SIGKILL after this line loses nothing — restart
                # replays it. An injected append failure refuses the
                # admission (shed, with the id) rather than accept a
                # request durability cannot cover.
                rid = body.get("request_id") or new_request_id()
                # the trace_id is minted HERE, with the request_id,
                # so the journal's admit record carries it and a
                # replayed request resumes under its original trace
                tid = body.get("trace_id") or new_trace_id()
                body = dict(body, request_id=rid, trace_id=tid)
                if router.journal is not None:
                    try:
                        router.journal.admit(rid, body, time.time(),
                                             trace_id=tid)
                    except Exception as e:  # noqa: BLE001 — fail closed
                        # durability contract: cannot journal ⇒ do
                        # not accept — an injected append fault and a
                        # real I/O error (ENOSPC, read-only dir)
                        # shed alike, never acknowledge un-journaled
                        health.shed(self, retry_after=1.0,
                                    reason="request journal "
                                           "unavailable: %s" % e,
                                    request_id=rid)
                        return
                    with router._cv:
                        router._journal_outstanding += 1
                if body.get("stream"):
                    # streaming proxy: events pipe through as they
                    # arrive, mid-stream failover resumes from the
                    # forwarded prefix; the journal terminal mirrors
                    # the buffered path's
                    with router._cv:
                        router._inflight += 1
                    try:
                        status, outcome, attempts = \
                            router.route_stream(body, self)
                    finally:
                        with router._cv:
                            router._inflight -= 1
                            router.requests_routed += 1
                            router._cv.notify_all()
                    if router.journal is not None:
                        try:
                            router.journal.done(rid, int(status),
                                                outcome, trace_id=tid,
                                                attempts=attempts)
                            with router._cv:
                                router._journal_outstanding -= 1
                        except Exception as e:  # noqa: BLE001
                            router.warning(
                                "%s: journal terminal for %s failed "
                                "(%s: %s); the entry stays pending — "
                                "a restart replays it idempotently",
                                router.name, rid, type(e).__name__, e)
                    return
                with router._cv:
                    router._inflight += 1
                try:
                    answered = router.route(body)
                finally:
                    with router._cv:
                        router._inflight -= 1
                        router.requests_routed += 1
                        router._cv.notify_all()
                # the answer — success and shed alike — is terminal:
                # replay must never re-run it. (A route that RAISED
                # never reaches this line: the entry stays pending
                # and the next start replays it, idempotent by id.)
                if router.journal is not None:
                    try:
                        router.journal.done(
                            rid,
                            int(answered.status) if answered.done
                            else 503,
                            "answered" if answered.done
                            else "unanswered",
                            trace_id=tid,
                            attempts=answered.attempts)
                        with router._cv:
                            router._journal_outstanding -= 1
                    except Exception as e:  # noqa: BLE001
                        # a failed terminal append (injected fault,
                        # full disk) must NOT drop the answer we
                        # already computed — the client still gets
                        # its reply below; the entry stays pending
                        # (and counted in the gauge) so a restart
                        # re-runs it idempotently by id
                        router.warning(
                            "%s: journal terminal for %s failed "
                            "(%s: %s); the entry stays pending — a "
                            "restart replays it idempotently",
                            router.name, rid, type(e).__name__, e)
                if not answered.done:
                    health.shed(
                        self, retry_after=1.0,
                        reason="no replica could answer: %s"
                        % getattr(answered, "reason",
                                  "no ready replica"),
                        request_id=answered.request_id)
                    return
                headers = None
                if answered.retry_after:
                    headers = {"Retry-After": str(answered.retry_after)}
                reply = answered.body
                if isinstance(reply, dict):
                    # the client learns the fleet trace key with its
                    # answer — `veles-tpu trace fleet --request` takes
                    # either this or the request_id
                    reply = dict(reply)
                    reply.setdefault("trace_id", tid)
                json_reply(self, answered.status, reply,
                           headers=headers)

        return Handler


class ReplicaSupervisor(Logger):
    """Spawn/classify/respawn plane for long-lived serving replicas —
    the PR 9 elastic :class:`~veles_tpu.resilience.elastic.Supervisor`
    generalized from training generations: training reaps the WHOLE
    generation when one host dies (its survivors are wedged in
    collectives), a serving fleet respawns ONLY the hole while the
    router routes around it.

    ``spawn(index, incarnation)`` builds replica ``index``'s process
    (or in-process stand-in) and returns a handle exposing
    ``poll() -> Optional[int]`` (None while alive, else the exit
    code) and, optionally, ``kill()``. Exit classification:

    - ``0`` — a deliberate, drained shutdown: the replica stays down
      (scaling in is not a failure);
    - anything else (``faults.CRASH_EXIT_CODE``, a signal, an OOM
      kill) — a death: the replica is respawned after a
      :meth:`RetryPolicy.backoff` delay (seeded jitter, growing with
      consecutive deaths; a replica that comes back and dies again
      immediately backs off harder), counted in
      ``veles_router_respawns_total``, up to ``max_respawns`` —
      after which the supervisor gives up on that index and the
      router simply keeps routing around it.

    ``clock`` is injectable; :meth:`check` performs one non-blocking
    sweep so tests drive classification deterministically."""

    def __init__(self, spawn: Callable[[int, int], Any],
                 n_replicas: int, max_respawns: int = 8,
                 poll_interval: float = 0.2,
                 backoff: Optional[RetryPolicy] = None,
                 clock: Callable[[], float] = time.monotonic,
                 name: str = "fleet") -> None:
        super().__init__()
        if n_replicas < 1:
            raise VelesError("a supervised fleet needs >= 1 replica")
        self._spawn = spawn
        self.n_replicas = int(n_replicas)
        self.max_respawns = int(max_respawns)
        self.poll_interval = float(poll_interval)
        self.backoff = backoff if backoff is not None else RetryPolicy(
            base_delay=0.1, max_delay=5.0, name="respawn")
        self._clock = clock
        self.name = name
        self.handles: List[Any] = [None] * self.n_replicas
        self.incarnations = [0] * self.n_replicas
        #: deliberate exits (code 0) — never respawned
        self.stopped = [False] * self.n_replicas
        #: respawn budget exhausted — the router routes around it
        self.given_up = [False] * self.n_replicas
        #: index -> monotonic time its pending respawn fires
        self._restart_at: Dict[int, float] = {}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._closing = threading.Event()

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "ReplicaSupervisor":
        with self._lock:
            for i in range(self.n_replicas):
                if self.handles[i] is None and not self.stopped[i] \
                        and not self.given_up[i] \
                        and i not in self._restart_at:
                    self._spawn_one(i)
        self._closing.clear()
        self._thread = threading.Thread(target=self._watch,
                                        daemon=True,
                                        name=self.name + ".supervise")
        self._thread.start()
        return self

    def stop(self, kill: bool = False) -> None:
        self._closing.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if kill:
            with self._lock:
                for handle in self.handles:
                    killer = getattr(handle, "kill", None)
                    if handle is not None and callable(killer):
                        try:
                            killer()
                        except OSError:
                            pass

    def _watch(self) -> None:
        while not self._closing.wait(timeout=self.poll_interval):
            self.check()

    # -- classify + respawn --------------------------------------------------
    def _spawn_one(self, i: int) -> None:
        self.incarnations[i] += 1
        self._restart_at.pop(i, None)
        self.handles[i] = self._spawn(i, self.incarnations[i])

    def check(self, now: Optional[float] = None) -> List[str]:
        """One supervision sweep: classify exits, schedule + perform
        respawns. Returns human-readable event strings (tests and the
        CLI log them)."""
        now = self._clock() if now is None else now
        events: List[str] = []
        with self._lock:
            for i in range(self.n_replicas):
                handle = self.handles[i]
                if handle is None:
                    due = self._restart_at.get(i)
                    if due is not None and now >= due:
                        try:
                            self._spawn_one(i)
                        except Exception as e:  # noqa: BLE001
                            # the respawn itself failed (port still
                            # held, artifact missing): back off and
                            # try again — the watch thread survives,
                            # and failed attempts still count toward
                            # the give-up budget
                            if self.incarnations[i] > self.max_respawns:
                                self.given_up[i] = True
                                events.append(
                                    "replica %d respawn failed (%s) — "
                                    "giving up" % (i, e))
                            else:
                                self._restart_at[i] = now \
                                    + self.backoff.backoff(
                                        min(self.incarnations[i], 16))
                                events.append(
                                    "replica %d respawn failed (%s) — "
                                    "retrying" % (i, e))
                            self.warning("%s: %s", self.name,
                                         events[-1])
                            continue
                        inc("veles_router_respawns_total")
                        events.append(
                            "respawned replica %d (incarnation %d)"
                            % (i, self.incarnations[i]))
                        self.info("%s: %s", self.name, events[-1])
                    continue
                code = handle.poll()
                if code is None:
                    continue
                self.handles[i] = None
                if code == 0:
                    self.stopped[i] = True
                    events.append("replica %d exited cleanly "
                                  "(drained)" % i)
                    self.info("%s: %s", self.name, events[-1])
                    continue
                deaths = self.incarnations[i]
                if deaths > self.max_respawns:
                    self.given_up[i] = True
                    events.append(
                        "replica %d died (exit %s) after %d "
                        "incarnations — giving up, the router "
                        "routes around it" % (i, code, deaths))
                    self.warning("%s: %s", self.name, events[-1])
                    continue
                delay = self.backoff.backoff(min(deaths, 16))
                self._restart_at[i] = now + delay
                events.append(
                    "replica %d died (exit %s) — respawn in %.2fs"
                    % (i, code, delay))
                self.warning("%s: %s", self.name, events[-1])
        return events

    def alive(self) -> int:
        with self._lock:
            return sum(1 for h in self.handles
                       if h is not None and h.poll() is None)
