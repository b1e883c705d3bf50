"""Fault-tolerant serving fleet (veles_tpu/serving/router.py): the
replica router with health-gated failover, idempotent retry, graceful
drain, and supervised respawn.

The contract under test: the router routes to the least-occupied READY
replica and never to a not-ready/draining one; consecutive attempt
failures open a per-replica circuit breaker riding RetryPolicy's
seeded backoff (half-open probes close it); a request that dies
mid-decode is retried on another replica keyed on its request_id with
EXACTLY-ONCE response accounting (a slow-then-successful first attempt
can never double-answer); SIGTERM / POST /drain stop admission, flip
/readyz to draining, finish in-flight tickets and exit 0; and the
ReplicaSupervisor respawns dead replicas while the router routes
around the hole — driven by the registered serve.replica_death /
router.replica_request fault points, no ad-hoc monkeypatching.

Budget discipline: everything above the chaos drill is jax-free (fake
HTTP replicas, fake clocks); the drill itself uses one tiny char_lm
workflow shared across its replicas.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy
import pytest

import veles_tpu as vt
from veles_tpu.resilience import health
from veles_tpu.resilience.retry import RetryPolicy
from veles_tpu.serving.router import (CircuitBreaker, FleetRouter,
                                      ReplicaSupervisor, _Answer,
                                      normalize_endpoint)
from veles_tpu.telemetry.counters import counters
from veles_tpu.telemetry.fleet import read_endpoints

from conftest import import_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _post(url, payload, timeout=30.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(url, timeout=10.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# -- endpoint + config parsing (no jax, no HTTP) ------------------------------

def test_normalize_endpoint_forms():
    assert normalize_endpoint("127.0.0.1:8080") \
        == "http://127.0.0.1:8080"
    assert normalize_endpoint("http://h:1/") == "http://h:1"
    # the scrape-roster spelling is accepted: routing and metrics
    # aggregation share one endpoint list
    assert normalize_endpoint("h:1/metrics") == "http://h:1"
    assert normalize_endpoint("https://h:1/metrics") == "https://h:1"


def test_router_rejects_empty_and_duplicate_rosters():
    from veles_tpu.error import VelesError
    with pytest.raises(VelesError):
        FleetRouter([])
    with pytest.raises(VelesError):
        FleetRouter(["h:1", "http://h:1"])


def test_read_endpoints_plain_lines(tmp_path):
    f = tmp_path / "fleet.txt"
    f.write_text("# the fleet\n127.0.0.1:1\n\nhttp://h:2  # replica\n")
    assert read_endpoints(str(f)) == ["127.0.0.1:1", "http://h:2"]


def test_read_endpoints_json_forms(tmp_path):
    f = tmp_path / "fleet.json"
    f.write_text(json.dumps(["h:1", "h:2"]))
    assert read_endpoints(str(f)) == ["h:1", "h:2"]
    # the router's GET /roster output saved to disk feeds the same
    # reader — fleet scraping and routing share one roster format
    f.write_text(json.dumps({"router": "r", "endpoints": [
        {"url": "http://h:1", "ready": True}, "h:2"]}))
    assert read_endpoints(str(f)) == ["http://h:1", "h:2"]
    f.write_text(json.dumps({"endpoints": [{"ready": True}]}))
    with pytest.raises(ValueError):
        read_endpoints(str(f))


# -- circuit breaker (fake clock, pinned backoff) -----------------------------

def _breaker(threshold=2, base=1.0):
    clock = {"t": 0.0}
    policy = RetryPolicy(base_delay=base, max_delay=8 * base,
                         jitter=False, name="t")
    return CircuitBreaker(failure_threshold=threshold, backoff=policy,
                          clock=lambda: clock["t"]), clock


def test_breaker_opens_at_threshold_and_backs_off():
    br, clock = _breaker(threshold=2, base=1.0)
    assert br.allow()
    assert br.record_failure() is False          # 1 of 2
    assert br.state == CircuitBreaker.CLOSED
    assert br.record_failure() is True           # threshold: OPEN
    assert br.state == CircuitBreaker.OPEN
    assert br.open_until == pytest.approx(1.0)   # backoff(1) = base
    assert not br.allow()                        # open: refused
    clock["t"] = 1.5
    assert br.allow()                            # half-open probe
    assert br.state == CircuitBreaker.HALF_OPEN
    assert not br.allow()                        # ONE probe at a time
    br.record_success()                          # probe succeeded
    assert br.state == CircuitBreaker.CLOSED
    assert br.allow()


def test_breaker_half_open_failure_reopens_longer():
    br, clock = _breaker(threshold=1, base=1.0)
    assert br.record_failure() is True           # trip 1: open 1s
    clock["t"] = 2.0
    assert br.allow()                            # half-open
    assert br.record_failure() is True           # probe failed: re-open
    assert br.state == CircuitBreaker.OPEN
    # trip 2 backs off harder: backoff(2) = base * 2
    assert br.open_until == pytest.approx(2.0 + 2.0)
    clock["t"] = 3.0
    assert not br.allow()
    # success resets the whole curve, not just the state
    clock["t"] = 10.0
    assert br.allow()
    br.record_success()
    assert br.trips == 0 and br.failures == 0


# -- the exactly-once answer latch --------------------------------------------

def test_answer_latch_first_offer_wins():
    a = _Answer()
    assert a.offer(200, {"tokens": [1]}) is True
    assert a.offer(200, {"tokens": [2]}) is False   # duplicate dropped
    assert a.body == {"tokens": [1]}
    assert a.done and a.status == 200


# -- supervised respawn (fake handles, fake clock) ----------------------------

class _FakeHandle:
    def __init__(self):
        self.code = None

    def poll(self):
        return self.code


def _supervisor(n=2, max_respawns=2, base=1.0):
    clock = {"t": 0.0}
    spawned = []
    handles = {}

    def spawn(i, incarnation):
        spawned.append((i, incarnation))
        handles[i] = _FakeHandle()
        return handles[i]

    sup = ReplicaSupervisor(
        spawn, n, max_respawns=max_respawns,
        backoff=RetryPolicy(base_delay=base, max_delay=8 * base,
                            jitter=False, name="t"),
        clock=lambda: clock["t"], name="t")
    # spawn without the watch thread — tests drive check() directly
    with sup._lock:
        for i in range(n):
            sup._spawn_one(i)
    return sup, clock, spawned, handles


def test_supervisor_respawns_death_after_backoff():
    sup, clock, spawned, handles = _supervisor()
    before = counters.get("veles_router_respawns_total")
    assert sup.alive() == 2
    handles[0].code = 42                         # death (crash code)
    events = sup.check()
    assert any("died" in e for e in events)
    assert sup.alive() == 1
    # the respawn waits out the backoff (incarnation 1 -> base delay)
    assert sup.check() == []
    clock["t"] = 1.1
    events = sup.check()
    assert any("respawned replica 0" in e for e in events)
    assert sup.alive() == 2
    assert spawned == [(0, 1), (1, 1), (0, 2)]
    assert counters.get("veles_router_respawns_total") - before == 1


def test_supervisor_clean_exit_stays_down():
    sup, clock, spawned, handles = _supervisor()
    handles[1].code = 0                          # drained on purpose
    events = sup.check()
    assert any("cleanly" in e for e in events)
    clock["t"] = 100.0
    assert sup.check() == []                     # never respawned
    assert sup.stopped[1] and sup.alive() == 1


def test_supervisor_gives_up_after_max_respawns():
    sup, clock, spawned, handles = _supervisor(max_respawns=2)
    for _ in range(2):
        handles[0].code = 1
        sup.check()
        clock["t"] += 100.0
        sup.check()                              # respawn
    handles[0].code = 1                          # third death
    events = sup.check()
    assert any("giving up" in e for e in events)
    assert sup.given_up[0]
    clock["t"] += 100.0
    assert sup.check() == []                     # stays down
    assert sup.incarnations[0] == 3


# -- routing over fake HTTP replicas (no jax) ---------------------------------

def _fake_replica(state=None):
    """A GenerationAPI-shaped fake: POST /generate answers with the
    request_id echoed (optionally after ``delay``), GET /readyz +
    /metrics render the knobs in ``state`` — the router's whole
    probe/admission surface without a model."""
    state = dict({"ready": True, "draining": False, "dead": False,
                  "slots": 4, "busy": 0, "delay": 0.0,
                  "served": [], "status_code": 200}, **(state or {}))

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if self.path == "/readyz":
                ok = state["ready"] and not state["draining"]
                payload = {"status": ("ok" if ok else
                                      "draining" if state["draining"]
                                      else "not ready")}
                self._reply(200 if ok else 503, payload)
            elif self.path == "/metrics":
                text = (
                    "# TYPE veles_serving_slots gauge\n"
                    "veles_serving_slots %d\n"
                    "# TYPE veles_serving_slots_busy gauge\n"
                    "veles_serving_slots_busy %d\n"
                    "# TYPE veles_serving_queue_depth gauge\n"
                    "veles_serving_queue_depth 0\n"
                    % (state["slots"], state["busy"]))
                body = text.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_error(404)

        def do_POST(self):
            if state["dead"]:
                # a crashed replica from the client's view: the
                # connection dies without a response
                self.close_connection = True
                return
            n = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(n) or b"{}")
            if state["delay"]:
                time.sleep(state["delay"])
            state["served"].append(req.get("request_id"))
            code = state["status_code"]
            if code >= 400:
                self._reply(code, {"error": "replica unhappy",
                                   "request_id": req.get("request_id")})
                return
            self._reply(200, {"tokens": [1, 2, 3],
                              "request_id": req.get("request_id"),
                              "port": self.server.server_port})

        def _reply(self, code, payload):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, state


@pytest.fixture
def fake_fleet():
    a_srv, a = _fake_replica({"busy": 3})
    b_srv, b = _fake_replica({"busy": 0})
    router = None
    try:
        router = FleetRouter(
            ["127.0.0.1:%d" % a_srv.server_port,
             "127.0.0.1:%d" % b_srv.server_port],
            probe_interval=0.1, probe_timeout=2.0,
            failure_threshold=2, retry_budget=2,
            attempt_timeout=5.0, request_timeout=20.0,
            name="test_router").start()
        yield router, (a_srv, a), (b_srv, b)
    finally:
        if router is not None:
            router.stop()
        a_srv.shutdown()
        b_srv.shutdown()


def test_routes_to_least_occupied_ready_replica(fake_fleet):
    router, (a_srv, a), (b_srv, b) = fake_fleet
    code, body, _ = _post(
        "http://127.0.0.1:%d/generate" % router.port,
        {"prompt": [1], "n_new": 2})
    assert code == 200
    assert body["port"] == b_srv.server_port      # B idle, A busy
    assert body["request_id"].startswith("req-")
    # flip the occupancy: the router spills to the other replica
    a["busy"], b["busy"] = 0, 4
    router.probe_all()
    code, body, _ = _post(
        "http://127.0.0.1:%d/generate" % router.port,
        {"prompt": [1], "n_new": 2})
    assert code == 200 and body["port"] == a_srv.server_port


def test_never_routes_to_not_ready_or_draining(fake_fleet):
    router, (a_srv, a), (b_srv, b) = fake_fleet
    url = "http://127.0.0.1:%d/generate" % router.port
    b["draining"] = True                          # readyz 503 draining
    router.probe_all()
    for _ in range(3):
        code, body, _ = _post(url, {"prompt": [1], "n_new": 2})
        assert code == 200 and body["port"] == a_srv.server_port
    roster = _get("http://127.0.0.1:%d/roster" % router.port)[1]
    by_url = {e["url"]: e for e in roster["endpoints"]}
    assert by_url["http://127.0.0.1:%d" % b_srv.server_port][
        "draining"] is True
    # both gone -> 503 + Retry-After + request_id, never a silent 504
    a["ready"] = False
    router.probe_all()
    code, body, headers = _post(url, {"prompt": [1], "n_new": 2})
    assert code == 503
    assert "request_id" in body
    assert int(headers.get("Retry-After")) >= 1


def test_failover_keeps_request_id_and_opens_breaker(fake_fleet):
    router, (a_srv, a), (b_srv, b) = fake_fleet
    url = "http://127.0.0.1:%d/generate" % router.port
    b["dead"] = True                    # B ranks first (idle), dies
    fo = counters.get("veles_router_failovers_total")
    er = counters.get("veles_router_replica_errors_total")
    code, body, _ = _post(url, {"prompt": [1], "n_new": 2,
                                "request_id": "req-up-1"})
    assert code == 200
    assert body["port"] == a_srv.server_port      # failed over
    assert body["request_id"] == "req-up-1"       # id survives retry
    assert counters.get("veles_router_failovers_total") - fo == 1
    assert counters.get("veles_router_replica_errors_total") - er == 1
    # threshold 2: one more failed attempt opens B's breaker, after
    # which pick() skips B entirely (no more attempts land on it)
    bo = counters.get("veles_router_breaker_opens_total")
    _post(url, {"prompt": [1], "n_new": 2})
    assert counters.get("veles_router_breaker_opens_total") - bo == 1
    dead = [r for r in router.replicas
            if r.url.endswith(str(b_srv.server_port))][0]
    assert dead.breaker.state == CircuitBreaker.OPEN
    attempts_before = counters.get("veles_router_attempts_total")
    code, body, _ = _post(url, {"prompt": [1], "n_new": 2})
    assert code == 200 and body["port"] == a_srv.server_port
    assert counters.get("veles_router_attempts_total") \
        - attempts_before == 1                    # straight to A


def test_5xx_fails_over_4xx_delivered(fake_fleet):
    router, (a_srv, a), (b_srv, b) = fake_fleet
    url = "http://127.0.0.1:%d/generate" % router.port
    b["status_code"] = 503                        # shedding replica
    code, body, _ = _post(url, {"prompt": [1], "n_new": 2})
    assert code == 200 and body["port"] == a_srv.server_port
    # a 400 is the client's problem on EVERY replica: delivered as-is
    b["status_code"] = 200
    a["status_code"] = 400
    a["busy"], b["busy"] = 0, 4
    router.probe_all()
    code, body, _ = _post(url, {"prompt": [1], "n_new": 2})
    assert code == 400 and "request_id" in body


def test_slow_first_attempt_never_double_answers(fake_fleet):
    """THE idempotent-failover race: attempt 1 outlives the router's
    patience, attempt 2 answers — when attempt 1 then completes, the
    exactly-once latch drops it (counted), and the client saw exactly
    one response."""
    router, (a_srv, a), (b_srv, b) = fake_fleet
    router.attempt_timeout = 0.3
    url = "http://127.0.0.1:%d/generate" % router.port
    b["delay"] = 1.5                              # slow, ranks first
    dup = counters.get("veles_router_duplicate_answers_total")
    code, body, _ = _post(url, {"prompt": [1], "n_new": 2,
                                "request_id": "req-slow-1"})
    assert code == 200
    assert body["port"] == a_srv.server_port      # the failover won
    assert body["request_id"] == "req-slow-1"
    # the slow replica's late success lands in the latch and is
    # dropped as a duplicate — wait for it, then assert exactly one
    deadline = time.time() + 10
    while counters.get("veles_router_duplicate_answers_total") == dup \
            and time.time() < deadline:
        time.sleep(0.05)
    assert counters.get("veles_router_duplicate_answers_total") \
        - dup == 1
    assert b["served"] == ["req-slow-1"]          # it DID serve it


def test_injected_replica_request_fault_drives_failover(fake_fleet,
                                                        monkeypatch):
    """The router.replica_request fault point is the chaos surface:
    an armed raise fails the attempt like a dead replica — counted,
    breaker advanced, failover — with both fakes perfectly healthy."""
    router, (a_srv, a), (b_srv, b) = fake_fleet
    url = "http://127.0.0.1:%d/generate" % router.port
    fo = counters.get("veles_router_failovers_total")
    inj = counters.get("veles_faults_injected_total")
    monkeypatch.setenv("VELES_FAULTS",
                       "router.replica_request:raise:times=1")
    code, body, _ = _post(url, {"prompt": [1], "n_new": 2})
    assert code == 200
    assert counters.get("veles_router_failovers_total") - fo == 1
    assert counters.get("veles_faults_injected_total") - inj == 1


def test_router_drain_sheds_and_finishes_inflight(fake_fleet):
    router, (a_srv, a), (b_srv, b) = fake_fleet
    base = "http://127.0.0.1:%d" % router.port
    b["delay"] = 0.8
    results = {}

    def slow_post():
        results["slow"] = _post(base + "/generate",
                                {"prompt": [1], "n_new": 2})

    t = threading.Thread(target=slow_post)
    t.start()
    time.sleep(0.2)                     # the request is in flight
    code, body, _ = _post(base + "/drain", {})
    assert code == 200 and body["status"] == "draining"
    # /readyz reports draining while the in-flight request finishes
    code, payload = _get(base + "/readyz")
    assert code == 503 and payload["status"] == "draining"
    # new admission is refused with the drain answer
    code, body, headers = _post(base + "/generate",
                                {"prompt": [1], "n_new": 2})
    assert code == 503 and "draining" in body["error"]
    assert "request_id" in body
    t.join(timeout=10)
    code, body, _ = results["slow"]
    assert code == 200                  # in-flight ticket finished
    # the drain thread tears the service down once empty
    deadline = time.time() + 10
    while router._service is not None and time.time() < deadline:
        time.sleep(0.05)
    assert router._service is None


def test_fleet_metrics_and_roster_share_the_roster(fake_fleet,
                                                   tmp_path):
    router, (a_srv, a), (b_srv, b) = fake_fleet
    base = "http://127.0.0.1:%d" % router.port
    # /fleet/metrics is the live fleet-wide aggregation (summed
    # gauges, per-endpoint up rows) over the router's own roster
    with urllib.request.urlopen(base + "/fleet/metrics",
                                timeout=10) as r:
        text = r.read().decode()
    assert "veles_fleet_endpoints 2" in text
    assert "veles_serving_slots 8" in text        # 4 + 4 summed
    assert text.count('veles_fleet_endpoint_up{') == 2
    # the saved /roster page feeds `veles-tpu metrics aggregate
    # --endpoints-file` unchanged: one roster, both consumers
    roster = _get(base + "/roster")[1]
    f = tmp_path / "roster.json"
    f.write_text(json.dumps(roster))
    from veles_tpu.__main__ import main
    import io
    from contextlib import redirect_stdout
    out = io.StringIO()
    with redirect_stdout(out):
        rc = main(["metrics", "aggregate", "--endpoints-file", str(f)])
    assert rc == 0
    assert "veles_serving_slots 8" in out.getvalue()
    # the router's own /metrics page carries its gauges
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        text = r.read().decode()
    assert "veles_router_replicas 2" in text
    assert "veles_router_draining 0" in text


# -- the route CLI: SIGTERM drains and exits 0 --------------------------------

@pytest.mark.skipif(sys.platform.startswith("win"),
                    reason="SIGTERM semantics")
def test_route_cli_sigterm_drains_inflight_and_exits_zero(tmp_path):
    """The acceptance drill's drain leg, end to end on the real CLI:
    `veles-tpu route` under SIGTERM flips /readyz to draining, lets
    the in-flight request finish (200, not a dropped connection), and
    exits 0."""
    srv, state = _fake_replica({"delay": 2.0})
    endpoints = tmp_path / "fleet.txt"
    endpoints.write_text("127.0.0.1:%d\n" % srv.server_port)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "veles_tpu", "route",
         "--endpoints-file", str(endpoints), "--port", "0",
         "--probe-interval", "0.2", "--drain-grace", "30"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO)
    try:
        line = proc.stdout.readline()
        assert line.startswith("ROUTING port="), line
        port = int(line.split("port=")[1].split()[0])
        base = "http://127.0.0.1:%d" % port
        code, payload = _get(base + "/readyz")
        assert code == 200
        results = {}

        def slow_post():
            results["r"] = _post(base + "/generate",
                                 {"prompt": [1], "n_new": 2},
                                 timeout=30)

        t = threading.Thread(target=slow_post)
        t.start()
        time.sleep(0.5)                 # in flight on the replica
        proc.send_signal(signal.SIGTERM)
        # /readyz reports draining while the in-flight ticket decodes
        saw_draining = False
        deadline = time.time() + 10
        while time.time() < deadline and not saw_draining:
            try:
                code, payload = _get(base + "/readyz", timeout=2)
                saw_draining = (code == 503
                                and payload["status"] == "draining")
            except Exception:           # noqa: BLE001 — gone already
                break
            time.sleep(0.05)
        assert saw_draining, "never observed /readyz draining"
        t.join(timeout=30)
        code, body, _ = results["r"]
        assert code == 200 and body["tokens"] == [1, 2, 3]
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        srv.shutdown()


# -- the chaos drill: replica death mid-decode over real engines --------------

@pytest.fixture(scope="module")
def lm_wf():
    lm = import_model("char_lm")
    from veles_tpu import prng
    prng.seed_all(2025)
    wf = lm.build_workflow(epochs=1, minibatch_size=32, n_blocks=1,
                           dim=32, n_train=64, n_valid=32)
    wf.initialize(device=vt.XLADevice(mesh_axes={"data": 1}))
    return lm, wf


def test_replica_death_failover_respawn_exactly_once(lm_wf,
                                                     monkeypatch):
    """THE acceptance chaos drill: a 2-replica fleet, serve.replica_death
    injected mid-decode → the router opens the breaker and retries the
    in-flight request on the survivor, the Supervisor plane respawns
    the dead replica, and every request is answered exactly once —
    responses keyed by request_id, tokens identical to the solo
    decode, no duplicates, no silent 504s."""
    from veles_tpu.nn import sampling
    from veles_tpu.resilience import faults
    lm, wf = lm_wf
    apis = [vt.GenerationAPI(wf, port=0, engine="continuous",
                             max_slots=2, buckets=(8,), max_context=24,
                             name="drill_%d" % i) for i in range(2)]

    class Handle:
        def __init__(self, api):
            self.api = api

        def poll(self):
            return (None if self.api._service is not None
                    else faults.CRASH_EXIT_CODE)

    def spawn(i, _incarnation):
        apis[i].initialize()
        return Handle(apis[i])

    rng = numpy.random.RandomState(31)
    prompts = [[int(t) for t in rng.randint(0, lm.VOCAB, 5 + i)]
               for i in range(6)]
    expected = [sampling.generate(wf, p, 4, temperature=0)
                for p in prompts]
    sup = ReplicaSupervisor(spawn, 2, poll_interval=0.1,
                            name="drill_sup")
    router = None
    fo = counters.get("veles_router_failovers_total")
    bo = counters.get("veles_router_breaker_opens_total")
    rs = counters.get("veles_router_respawns_total")
    try:
        sup.start()
        router = FleetRouter(
            ["127.0.0.1:%d" % api.port for api in apis],
            probe_interval=0.2, failure_threshold=1, retry_budget=2,
            attempt_timeout=60.0, request_timeout=120.0,
            name="drill_router").start()
        url = "http://127.0.0.1:%d/generate" % router.port
        # warm both engines' programs outside the armed window
        code, body, _ = _post(url, {"prompt": prompts[0], "n_new": 4},
                              timeout=120)
        assert code == 200
        # the 3rd replica-side request dies mid-decode, exactly once
        monkeypatch.setenv(
            "VELES_FAULTS", "serve.replica_death:raise:after=2,times=1")
        answers = {}
        for i, prompt in enumerate(prompts):
            code, body, _ = _post(
                url, {"prompt": prompt, "n_new": 4}, timeout=120)
            assert code == 200, (i, body)         # no dropped requests
            rid = body["request_id"]
            assert rid not in answers             # no double answers
            answers[rid] = body["tokens"]
            assert body["tokens"] == expected[i]  # failover is id-exact
        assert len(answers) == len(prompts)
        assert counters.get("veles_router_failovers_total") - fo >= 1
        assert counters.get("veles_router_breaker_opens_total") \
            - bo >= 1
        monkeypatch.delenv("VELES_FAULTS")
        # the supervisor respawns the hole... (the respawn counter is
        # the event — alive() alone is racy: the dying replica's
        # teardown may still be in flight when the load finishes)
        deadline = time.time() + 60
        while counters.get("veles_router_respawns_total") - rs < 1 \
                and time.time() < deadline:
            time.sleep(0.1)
        assert counters.get("veles_router_respawns_total") - rs >= 1, \
            "dead replica never respawned"
        deadline = time.time() + 30
        while sup.alive() < 2 and time.time() < deadline:
            time.sleep(0.1)
        assert sup.alive() == 2
        # ...and the respawned replica actually serves again
        router.probe_all()
        dead_idx = [i for i, api in enumerate(apis)
                    if sup.incarnations[i] > 1]
        assert len(dead_idx) == 1
        code, body, _ = _post(
            "http://127.0.0.1:%d/generate" % apis[dead_idx[0]].port,
            {"prompt": prompts[0], "n_new": 4}, timeout=120)
        assert code == 200 and body["tokens"] == expected[0]
    finally:
        if router is not None:
            router.stop()
        sup.stop()
        for api in apis:
            api.stop()


def test_generation_api_drain_finishes_inflight(lm_wf):
    """The engine-API side of the LEGACY drain contract
    (handoff=False — the wait-out-the-grace drain): begin_drain stops
    admission (503 "draining" + request_id) and flips /readyz to
    draining while the in-flight ticket keeps decoding to a 200;
    drain() then returns True and tears the service down. The
    default drain-by-handoff path is tests/test_lossless.py's."""
    lm, wf = lm_wf
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           max_slots=2, buckets=(8,), max_context=24,
                           name="drain_api")
    api.initialize()
    base = "http://127.0.0.1:%d" % api.port
    try:
        code, body, _ = _post(base + "/generate",
                              {"prompt": [1, 2, 3], "n_new": 2},
                              timeout=120)          # warm the engine
        assert code == 200
        results = {}

        def slow_post():
            results["r"] = _post(base + "/generate",
                                 {"prompt": [1, 2, 3, 4], "n_new": 12},
                                 timeout=120)

        t = threading.Thread(target=slow_post)
        t.start()
        deadline = time.time() + 10
        while not api._inflight and time.time() < deadline:
            time.sleep(0.005)                       # it IS in flight
        assert api.begin_drain() is True
        assert api.begin_drain() is False           # idempotent
        code, payload = _get(base + "/readyz")
        assert code == 503 and payload["status"] == "draining"
        assert payload["components"]["serve.drain_api"] == "draining"
        code, _b = _get(base + "/healthz")
        assert code == 200                          # alive throughout
        code, body, headers = _post(base + "/generate",
                                    {"prompt": [5, 6], "n_new": 2})
        assert code == 503 and "draining" in body["error"]
        assert "request_id" in body
        assert int(headers.get("Retry-After")) >= 1
        assert api.drain(grace=60, handoff=False) is True   # finished
        t.join(timeout=30)
        code, body, _ = results["r"]
        assert code == 200 and len(body["tokens"]) == 12
        assert api._service is None
        # gone: readiness mark and heartbeat both dropped
        assert "serve.drain_api" not in health.readiness()
    finally:
        api.stop()


def test_generation_api_drain_endpoint(lm_wf):
    lm, wf = lm_wf
    api = vt.GenerationAPI(wf, port=0, engine="continuous",
                           max_slots=2, buckets=(8,), max_context=24,
                           name="drain_ep")
    api.initialize()
    base = "http://127.0.0.1:%d" % api.port
    try:
        code, body, _ = _post(base + "/generate/drain", {})
        assert code == 200 and body["status"] == "draining"
        deadline = time.time() + 15
        while api._service is not None and time.time() < deadline:
            time.sleep(0.05)
        assert api._service is None                 # drained + stopped
    finally:
        api.stop()
