"""The parent's side of a serving cell: load from one process, few threads.

Streams ``POST /generate`` over real HTTP to the child's server, times
every token on the client's side, and hands ``run.py`` the requests of the
window. No jax here. The child is told when the window opens and closes
with SIGUSR1 (it starts and stops the profiler and reads the counters),
and is stopped with SIGTERM, which drains the server as in production.
"""

import http.client
import json
import os
import random
import signal
import statistics
import threading
import time

from chipbench import reduce, traffic as traffic_mod

WARM_TIMEOUT = 1100.0
REQUEST_TIMEOUT = 150.0


def stream_request(port, item, record, clock=time.perf_counter):
    """One streamed request. ``record`` gets ``sent``, ``first``, ``last``,
    ``stamps`` (arrival of every token), ``tokens``, ``ok``, ``error``."""
    body = {"prompt": item["prompt"], "n_new": item["n_new"], "stream": True,
            "seed": item["seed"]}
    if item["sampled"]:
        body.update(mode="sample", temperature=item["temperature"])
    record.update(tokens=[], stamps=[], ok=False, error=None, first=None,
                  last=None)
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT)
    try:
        record["sent"] = clock()
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            record["error"] = "HTTP %d %s" % (resp.status,
                                              resp.read(300)[:300])
            return
        for raw in resp:
            if not raw.startswith(b"data:"):
                continue
            event = json.loads(raw[5:])
            now = clock()
            if event.get("done"):
                if event.get("error") or event.get("code", 200) != 200:
                    record["error"] = str(event)[:300]
                else:
                    final = event.get("tokens")
                    if final is not None and list(final) != record["tokens"]:
                        record["error"] = "streamed tokens differ from the "\
                            "terminal event's"
                    else:
                        record["ok"] = True
                return
            toks = event.get("tokens") or []
            if toks and record["first"] is None:
                record["first"] = now
            if toks:
                record["last"] = now
                record["tokens"].extend(toks)
                record["stamps"].extend([now] * len(toks))
        record["error"] = "the stream ended without a terminal event"
    except (OSError, ValueError, http.client.HTTPException) as e:
        record["error"] = "%s: %s" % (type(e).__name__, e)
    finally:
        conn.close()


def http_get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        return conn.getresponse().read().decode(errors="replace")
    finally:
        conn.close()


def scrape(port):
    """{series: value} of the server's /metrics page (unlabelled series
    and histogram _sum/_count)."""
    out = {}
    for line in http_get(port, "/metrics").splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            pass
    return out


def closed_loop(port, items, clients, t0, seconds, clock):
    """``clients`` callers, no think time: each takes the next request of
    the list when its reply is complete, until the window closes. A
    request in flight at the close is let finish."""
    records, lock, nxt = [], threading.Lock(), [0]

    def caller():
        while clock() - t0 < seconds:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            item = items[i % len(items)]
            rec = {"i": i, "due": clock(), "prompt_len": len(item["prompt"]),
                   "n_new": item["n_new"], "sampled": item["sampled"],
                   "item": item}
            with lock:
                records.append(rec)
            stream_request(port, item, rec, clock)
    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    return threads, records


def drive(child, spec):
    """Warm up, open the window, offer the load, close, pick the sample
    that the child checks, stop the server. Returns what run.py adds to
    the child's report."""
    wl, cfg = spec["workload"], spec["config"]
    tr = wl["traffic"]
    clock = time.perf_counter
    line = child.wait_for("SERVING port=", WARM_TIMEOUT)
    port = int(line.split("SERVING port=")[1].split()[0])
    buckets = wl["cli"][wl["cli"].index("--serve-buckets") + 1]
    for item in traffic_mod.warmup_requests(
            tr, [int(b) for b in buckets.split(",")], cfg["vocab_size"]):
        rec = {}
        stream_request(port, item, rec, clock)
        if not rec["ok"]:
            raise RuntimeError("warm-up request failed: %s" % rec["error"])
    seconds = spec["seconds"]
    items = traffic_mod.schedule(tr, spec["seed"], cfg["vocab_size"])
    os.kill(child.proc.pid, signal.SIGUSR1)
    child.wait_for("chipbench: window open", 120)
    setup_s = time.time() - spec["t_start"]
    t0 = clock()
    threads, records = closed_loop(port, items, tr["clients"], t0, seconds,
                                   clock)
    piece = None
    if spec["trace"]:
        # the window is as long as without; the profiler is on for a slice
        # of ``trace_seconds`` in its middle, past the ramp, and the
        # per-layer metrics are of that slice
        length = min(seconds, wl.get("trace_seconds", seconds))
        time.sleep(max(0.0, t0 + (seconds - length) / 2.0 - clock()))
        os.kill(child.proc.pid, signal.SIGUSR1)
        child.wait_for("chipbench: trace on", 120)
        before, a = scrape(port), clock()
        time.sleep(length)
        b, after = clock(), scrape(port)
        os.kill(child.proc.pid, signal.SIGUSR1)
        child.wait_for("chipbench: trace off", 120)
        piece = {"from_s": a - t0, "to_s": b - t0, "window_s": b - a,
                 "counters": reduce.counters_rise(before, after)}
    time.sleep(max(0.0, t0 + seconds - clock()))
    t1 = clock()
    os.kill(child.proc.pid, signal.SIGUSR1)
    child.wait_for("chipbench: window closed", 120)
    # every request that was due in the window is waited for: one that
    # comes late is late, not wrong
    for t in list(threads):
        t.join(REQUEST_TIMEOUT)
    requests = []
    for r in sorted(records, key=lambda r: r["i"]):
        requests.append({
            "i": r["i"], "due": r["due"] - t0,
            "sent": r.get("sent", r["due"]) - t0,
            "first": None if r.get("first") is None else r["first"] - t0,
            "last": None if r.get("last") is None else r["last"] - t0,
            "tokens": len(r.get("tokens", ())),
            "stamps": [s - t0 for s in r.get("stamps", ())],
            "tokens_in_window": sum(1 for s in r.get("stamps", ())
                                    if s <= t1),
            "ok": bool(r.get("ok")), "error": r.get("error"),
            "prompt_len": r["prompt_len"], "n_new": r["n_new"],
            "sampled": r["sampled"]})
    late = [1000.0 * (r["sent"] - r["due"]) for r in requests]
    sample = pick_sample(records, spec["seed"], wl.get("check_requests", 6))
    with open(spec["sample_path"], "w") as f:
        json.dump(sample, f)
    os.kill(child.proc.pid, signal.SIGTERM)
    return {"requests": requests, "window_s": t1 - t0, "setup_s": setup_s,
            "slice": piece,
            "generator_late_ms": [statistics.median(late), max(late)],
            "attempted": len(requests),
            "failed": sum(1 for r in requests if not r["ok"])}


def pick_sample(records, seed, n):
    """Greedy requests that finished, the longest served among them and
    ``n - 1`` more drawn from the seed: prompts with their served tokens."""
    done = [r for r in records if r.get("ok") and not r["sampled"]
            and r.get("tokens")]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    random.Random(int(seed) ^ 0x5A17).shuffle(rest)
    return [{"i": r["i"], "prompt": r["item"]["prompt"],
             "served": r["tokens"]} for r in [longest] + rest[:n - 1]]
