#!/usr/bin/env python3
"""chip_smoke.py — does the LM train-and-serve path still start on the chip?

Drives the system's main path once, through the entry points a user
calls, at the full width of the widest model the repo ships (``lm_big``:
T=2048, dim 1024, 8 blocks, ffn 4096, 16 heads of 64, vocab 256; random
weights and synthetic tokens from a seed — no network, no dataset):

- train: ``python -m veles_tpu chip_smoke.py --backend tpu --mesh data=1
  --mixed-precision`` takes two epochs of four steps; losses must be
  finite, parameters must move, and the Pallas flash kernel must have
  been traced compiled (never interpreted);
- serve: the same widths under ``--serve-generate``; eight concurrent
  ``POST /generate`` over real HTTP must come back right from the
  ``continuous`` plane, and SIGTERM must drain to exit 0;
- on a host with four or more chips, both again over all of them
  (``--mesh data=4`` and ``--serve-tp 4``).

One process holds a chip at a time, so this parent never imports jax or
veles_tpu: the device probe and every phase are child processes, run one
after another. This file is also the model file those children load (the
CLI's ``run(load, main)`` protocol), which is how a child reports what
only it can see: parameter movement, counters, compile seconds, memory.

It measures nothing: no rate or utilization is printed. Any failed
phase — and any machine without a TPU — exits non-zero; nothing here
ever runs a phase on the CPU, and a failure prints no verdict. On success
the last two lines of stdout are ``SMOKE_SUMMARY {..., "claim": null}``
(cache, compile seconds, planes) and the verdict, one JSON object with
exactly these keys: ``{"ok": true, "device": {"platform": ..., "kind":
..., "count": ...}}``, the device as jax reports it.
"""

import hashlib
import json
import math
import os
import random
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
BACKEND = "tpu"
#: the widest shipped LM; nothing is cut
MODEL = dict(seq_len=2048, dim=1024, n_blocks=8, ffn_hidden=4096,
             n_heads=16, vocab=256)
TRAIN_STEPS, TRAIN_EPOCHS = 4, 2
MINIBATCH = {1: 4, 4: 8}          # by devices in the data mesh
SERVE_ARGS = ["--serve-slots", "8", "--serve-buckets", "128,512,1024,2048",
              "--serve-max-context", "4096"]
BUCKETS = (128, 512, 1024, 2048)
#: (name, prompt length, n_new, temperature). "dup" repeats "short"
#: byte for byte; "exact" is the one prompt that takes the flash forward
#: (bucket 2048 unpadded); "straddle" pads across the flash crossover,
#: which the engine hands to another plane (serving/engine.py
#: _kernel_straddle) — its plane is printed, not required
REQUESTS = [("short", 100, 32, 0.0), ("dup", 100, 32, 0.0),
            ("mid", 300, 48, 0.0), ("edge", 512, 32, 0.0),
            ("sampled", 700, 40, 0.8), ("long", 900, 64, 0.0),
            ("exact", 2048, 32, 0.0), ("straddle", 1500, 32, 0.0)]
CHILD_ENV = "CHIP_SMOKE_CHILD"
PHASE_TIMEOUT = 900.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# child side: this file as the model the CLI loads
# ---------------------------------------------------------------------------

def _build_workflow(model, minibatch_size, steps, epochs):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "char_lm", os.path.join(HERE, "models", "char_lm.py"))
    char_lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(char_lm)
    wf = char_lm.build_bench_workflow(
        minibatch_size=minibatch_size, n_train=steps * minibatch_size,
        n_valid=minibatch_size, **model)
    wf.decision.max_epochs = epochs
    return wf


def _params(wf):
    """{tensor name: (digest of its bytes, all finite?)} — digests, not
    copies: the model is ~100 M parameters."""
    import numpy
    import veles_tpu as vt
    units = vt.collect_state(wf)["__units__"]
    return {"%s.%s" % (f.name, k): (
                hashlib.sha1(numpy.ascontiguousarray(v).view(
                    numpy.uint8)).hexdigest(),
                bool(numpy.isfinite(numpy.asarray(v, numpy.float32)).all()))
            for f in wf.forwards if f.PARAMETERIZED
            for k, v in units[f.name].items()}


def _device_memory():
    import jax
    return [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.local_devices()]


def run(load, main):
    """Entry point the CLI calls in each phase's child (veles_tpu/
    __main__.py, reference-style model protocol): build the workflow,
    let ``main()`` drive it exactly as for any model file, then print
    one ``SMOKE_CHILD {json}`` line with what only this process saw."""
    import jax
    from veles_tpu.telemetry.counters import counters
    cfg = json.loads(os.environ[CHILD_ENV])
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    wf, _ = load(_build_workflow, **cfg["workflow"])
    before = {}
    if cfg["phase"] == "train":
        initialize = wf.initialize

        def initialize_and_remember(**kwargs):
            initialize(**kwargs)
            before.update(_params(wf))
        wf.initialize = initialize_and_remember
    # the parent asks a live server for its memory with SIGUSR1: after
    # main() returns the engine has already released its pool
    signal.signal(signal.SIGUSR1, lambda *_: print(
        "SMOKE_MEMORY " + json.dumps(_device_memory()), flush=True))
    main()
    report = {"compile_seconds": round(compile_s[0], 2),
              "memory": _device_memory(),
              "flash_traces": counters.get(
                  "veles_flash_attention_traces_total"),
              "flash_interpreted": counters.get(
                  "veles_flash_attention_interpret_traces_total")}
    if cfg["phase"] == "train":
        after = _params(wf)
        report["params"] = len(after)
        report["params_changed"] = sum(
            after[k][0] != before[k][0] for k in after)
        report["params_finite"] = all(ok for _, ok in after.values())
    print("SMOKE_CHILD " + json.dumps(report), flush=True)


# ---------------------------------------------------------------------------
# parent side: no jax, no veles_tpu
# ---------------------------------------------------------------------------

def _tail(path, n=60):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return "(no log: %s)" % e


def _fail(what, log=None):
    msg = "chip_smoke: FAILED — %s" % what
    if log:
        msg += "\n--- tail of %s ---\n%s" % (log, _tail(log))
    raise SmokeFailure(msg)


def _spawn(args, log, phase=None, minibatch_size=MINIBATCH[1]):
    env = dict(os.environ)
    if phase is not None:
        env[CHILD_ENV] = json.dumps({"phase": phase, "workflow": dict(
            model=MODEL, minibatch_size=minibatch_size, steps=TRAIN_STEPS,
            epochs=TRAIN_EPOCHS)})
    out = open(log, "w")
    try:
        return subprocess.Popen(
            [sys.executable] + args, cwd=HERE, env=env, stdout=out,
            stderr=subprocess.STDOUT, start_new_session=True)
    finally:
        out.close()


def _stop(proc):
    """Whatever happened, nothing this script started outlives it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def _wait_exit(proc, log, what, timeout):
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _fail("%s: no exit within %.0f s" % (what, timeout), log)
    finally:
        _stop(proc)


def _tagged(log, tag):
    """The last ``TAG {json}`` line a child printed, or None."""
    found = None
    with open(log, errors="replace") as f:
        for line in f:
            if line.startswith(tag + " "):
                found = json.loads(line[len(tag) + 1:])
    return found


def _wait_for(log, proc, needle, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        with open(log, errors="replace") as f:
            for line in f:
                if needle in line:
                    return line
        if proc.poll() is not None:
            _fail("child exited %d before printing %r"
                  % (proc.returncode, needle), log)
        time.sleep(0.5)
    _fail("no %r within %.0fs" % (needle, timeout), log)


def probe_device():
    """What jax sees, asked of a child so this process never holds the
    chip. No accelerator → non-zero exit here, before any phase."""
    code = ("import json, jax; d = jax.devices(); print('SMOKE_DEVICE ' + "
            "json.dumps({'jax': jax.__version__, 'platform': d[0].platform,"
            " 'kind': d[0].device_kind, 'count': len(d)}))")
    log = os.path.join(LOG_DIR, "probe.log")
    proc = _spawn(["-c", code], log)
    _wait_exit(proc, log, "device probe", 300)
    seen = _tagged(log, "SMOKE_DEVICE") if proc.returncode == 0 else None
    if seen is None:
        _fail("jax could not enumerate devices (exit %s)"
              % proc.returncode, log)
    if seen["platform"] != BACKEND:
        _fail("no %s device: jax %s sees %d x %s (%s); this smoke never "
              "runs on anything else" % (BACKEND, seen["jax"], seen["count"],
                                         seen["platform"], seen["kind"]))
    return seen


def _check_child(log, leg):
    report = _tagged(log, "SMOKE_CHILD")
    if report is None:
        _fail("%s: child printed no SMOKE_CHILD report" % leg, log)
    if report["flash_traces"] < 1:
        _fail("%s: the flash kernel was never traced "
              "(veles_flash_attention_traces_total = 0)" % leg, log)
    if report["flash_interpreted"]:
        _fail("%s: %d flash trace(s) ran with interpret=True"
              % (leg, report["flash_interpreted"]), log)
    return report


def _check_backend(log, leg):
    """The child's own account of its device (veles_tpu/backends.py
    logs one ``XLA backend:`` line): the platform must be the chip's.
    Returns the compile-cache directory that line names."""
    with open(log, errors="replace") as f:
        lines = [line for line in f if "XLA backend: " in line]
    if not lines or " %s device(s)" % BACKEND not in lines[0]:
        _fail("%s: the log names no %s backend (%r)"
              % (leg, BACKEND, lines[:1]), log)
    return lines[0].rsplit("compile cache ", 1)[1].strip()


def _check_memory(leg, memory, n_devices):
    print("  bytes_in_use per device: %s" % memory)
    if len(memory) < n_devices or not all(memory[:n_devices]):
        _fail("%s: not every one of %d devices holds a share: %s"
              % (leg, n_devices, memory))


def train_phase(leg, n_devices):
    log = os.path.join(LOG_DIR, leg + ".log")
    result_file = os.path.join(LOG_DIR, leg + ".result.json")
    if os.path.exists(result_file):
        os.remove(result_file)
    t0 = time.time()
    proc = _spawn(
        ["-m", "veles_tpu", os.path.abspath(__file__), "--backend", BACKEND,
         "--mesh", "data=%d" % n_devices, "--mixed-precision", "-v",
         "--result-file", result_file], log, "train", MINIBATCH[n_devices])
    _wait_exit(proc, log, leg, PHASE_TIMEOUT)
    if proc.returncode != 0:
        _fail("%s: child exited %d" % (leg, proc.returncode), log)
    cache = _check_backend(log, leg)
    with open(result_file) as f:
        results = json.load(f)
    errs = [e for series in results["err_history"].values() for e in series]
    if len(errs) != 2 * TRAIN_EPOCHS or results["epochs"] != TRAIN_EPOCHS:
        _fail("%s: expected %d epochs of train+validation errors, got %r"
              % (leg, TRAIN_EPOCHS, results), log)
    numbers = errs + [results["best_err"]]
    if not all(isinstance(x, (int, float)) and math.isfinite(x)
               for x in numbers):
        _fail("%s: non-finite loss/err in %s: %r"
              % (leg, result_file, results), log)
    report = _check_child(log, leg)
    if not report["params_finite"]:
        _fail("%s: non-finite parameters after training" % leg, log)
    if report["params_changed"] != report["params"]:
        _fail("%s: only %d of %d parameter tensors changed"
              % (leg, report["params_changed"], report["params"]), log)
    print("%s: ok — %d epochs x %d steps at %s, mb %d, mesh data=%d; "
          "err %s; %d/%d parameter tensors moved; flash traces %d "
          "(interpreted 0)"
          % (leg, TRAIN_EPOCHS, TRAIN_STEPS, MODEL,
             MINIBATCH[n_devices], n_devices,
             results["err_history"], report["params_changed"],
             report["params"], report["flash_traces"]))
    if n_devices > 1:
        _check_memory(leg, report["memory"], n_devices)
    return {"compile_seconds": report["compile_seconds"],
            "wall_seconds": round(time.time() - t0, 1), "cache": cache}


def _http(port, path, body=None, timeout=400.0):
    """(status, parsed JSON or text). Real HTTP to the child's server."""
    req = urllib.request.Request(
        "http://127.0.0.1:%d%s" % (port, path),
        data=None if body is None else json.dumps(body).encode())
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode(errors="replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def _ask(port, name, length, n_new, temperature, answers):
    rng = random.Random("short" if name == "dup" else name)
    body = {"prompt": [rng.randrange(MODEL["vocab"]) for _ in range(length)],
            "n_new": n_new, "temperature": temperature, "seed": 7}
    if temperature > 0:
        body["mode"] = "sample"
    # a cold server compiles each program inside the first request that
    # needs it, which can outlast the server's own request deadline: one
    # retry, and it is printed
    for tries in (1, 2):
        status, reply = _http(port, "/generate", body)
        if status not in (503, 504):
            break
    answers[name] = (status, reply, tries)


def serve_phase(leg, tp):
    log = os.path.join(LOG_DIR, leg + ".log")
    args = ["-m", "veles_tpu", os.path.abspath(__file__), "--backend",
            BACKEND, "-v", "--serve-generate", "0"] + SERVE_ARGS
    # one chip: a one-device mesh, said out loud (the default mesh would
    # span every chip of the host under a tp=1 engine). tp: the default
    # mesh, whose first ``tp`` devices the engine shards over
    args += ["--serve-tp", str(tp)] if tp > 1 else ["--mesh", "data=1"]
    t0 = time.time()
    proc = _spawn(args, log, "serve")
    try:
        line = _wait_for(log, proc, "SERVING port=", PHASE_TIMEOUT)
        port = int(line.split("SERVING port=")[1].split()[0])
        answers = {}
        threads = [threading.Thread(target=_ask, args=(port,) + r
                                    + (answers,)) for r in REQUESTS]
        for t in threads:
            t.start()
        for t in threads:
            t.join(PHASE_TIMEOUT)
        if any(t.is_alive() for t in threads):
            _fail("%s: a request got no answer in %.0fs"
                  % (leg, PHASE_TIMEOUT), log)
        used, planes = set(), {}
        for name, length, n_new, temperature in REQUESTS:
            status, reply, tries = answers[name]
            if status != 200:
                _fail("%s: request %s -> HTTP %d %r"
                      % (leg, name, status, reply), log)
            # the window plane's answers carry no "engine" field
            plane = planes[name] = reply.get("engine", "window")
            bucket = next((b for b in BUCKETS if b >= length), None)
            print("  %-8s prompt %4d  bucket %4s  n_new %2d  plane %-10s"
                  " tries %d" % (name, length, bucket, n_new, plane, tries))
            toks = reply.get("tokens")
            if (not isinstance(toks, list) or len(toks) != n_new
                    or not all(isinstance(t, int)
                               and 0 <= t < MODEL["vocab"] for t in toks)):
                _fail("%s: request %s wants %d tokens in [0, %d), got %r"
                      % (leg, name, n_new, MODEL["vocab"], toks), log)
            if name == "straddle":
                continue
            if plane != "continuous":
                _fail("%s: request %s was answered by the %s plane"
                      % (leg, name, plane), log)
            used.add(bucket)
        if answers["short"][1]["tokens"] != answers["dup"][1]["tokens"]:
            _fail("%s: the same greedy prompt gave different tokens: %r vs "
                  "%r" % (leg, answers["short"][1]["tokens"],
                          answers["dup"][1]["tokens"]), log)
        status, stats = _http(port, "/generate/stats")
        pool = stats.get("continuous", {}) if status == 200 else {}
        # one prefill per bucket, and the decode step at both rungs of
        # its view ladder (PR 35): "exact" passes half of max_context,
        # the other requests stay under it
        programs = len(used) + 2
        if (pool.get("slot_kind") != "paged" or pool.get("tp") != tp
                or pool.get("compiled_live") != programs):
            _fail("%s: /generate/stats wants slot_kind paged, tp %d, "
                  "compiled_live %d; got %r" % (leg, tp, programs, stats),
                  log)
        status, metrics = _http(port, "/metrics")
        dispatches = [float(m.split()[1]) for m in str(metrics).splitlines()
                      if m.startswith("veles_decode_dispatches_total ")]
        if status != 200 or not dispatches or dispatches[0] <= 0:
            _fail("%s: /metrics shows no veles_decode_dispatches_total > 0"
                  % leg, log)
        os.kill(proc.pid, signal.SIGUSR1)
        _wait_for(log, proc, "SMOKE_MEMORY ", 60)
        os.kill(proc.pid, signal.SIGTERM)
        _wait_exit(proc, log, leg + " after SIGTERM", 120)
    finally:
        _stop(proc)
    if proc.returncode != 0:
        _fail("%s: server exited %d after SIGTERM" % (leg, proc.returncode),
              log)
    cache = _check_backend(log, leg)
    report = _check_child(log, leg)
    print("%s: ok — %d requests answered, %d from the continuous plane "
          "(compiled_live %d, tp %d, %d decode dispatches), straddling "
          "prompt answered by the %s plane, drained to exit 0"
          % (leg, len(REQUESTS), len(REQUESTS) - 1, programs, tp,
             dispatches[0], planes["straddle"]))
    if tp > 1:
        _check_memory(leg, _tagged(log, "SMOKE_MEMORY"), tp)
    return {"compile_seconds": report["compile_seconds"],
            "wall_seconds": round(time.time() - t0, 1), "cache": cache,
            "straddle_plane": planes["straddle"],
            "tokens": {n: answers[n][1]["tokens"] for n, *_ in REQUESTS}}


def _cache_entries(path):
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def main():
    for needed in ("veles_tpu/__main__.py", "models/char_lm.py"):
        if not os.path.exists(os.path.join(HERE, needed)):
            print("chip_smoke: FAILED — %s is not beside this script: run "
                  "it from a checkout of the repo" % needed, file=sys.stderr)
            return 1
    os.makedirs(LOG_DIR, exist_ok=True)
    sys.stdout.reconfigure(line_buffering=True)   # survive a time-limit kill
    # a SIGTERM (a time limit) unwinds through the finally blocks that
    # stop the child in flight; children sit in their own sessions
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        device = probe_device()
        print("jax %s, platform %s, device_kind %s, %d device(s)"
              % (device["jax"], device["platform"], device["kind"],
                 device["count"]))
        phases = {"train_1chip": train_phase("train_1chip", 1)}
        cache = phases["train_1chip"]["cache"]
        print("compile cache: %s (%d entries after the first phase)"
              % (cache, _cache_entries(cache)))
        phases["serve_1chip"] = serve_phase("serve_1chip", 1)
        if device["count"] >= 4:
            phases["train_4chip"] = train_phase("train_4chip", 4)
            phases["serve_tp4"] = serve_phase("serve_tp4", 4)
            solo = phases["serve_1chip"]["tokens"]
            pooled = phases["serve_tp4"]["tokens"]
            same = [n for n in solo if solo[n] == pooled[n]]
            # reported, not required (ROADMAP D2 owns that contract)
            print("tp=4 tokens equal the one-chip tokens for %d of %d "
                  "requests (differ: %s)" % (len(same), len(solo), sorted(
                      set(solo) - set(same)) or "none"))
            tokens_match = len(same) == len(solo)
        else:
            print("found %d device(s): the four-chip leg needs 4 and was "
                  "not run" % device["count"])
            tokens_match = None
    except SmokeFailure as e:
        print(e, file=sys.stderr)
        return 1
    entries = _cache_entries(cache)
    print("compile cache: %s, %d entries" % (cache, entries))
    for name, phase in phases.items():
        print("%s: compile %.1f s of %.1f s wall"
              % (name, phase["compile_seconds"], phase["wall_seconds"]))
    print("SMOKE_SUMMARY " + json.dumps({
        "jax": device["jax"],
        "compile_cache": {"dir": cache, "entries": entries},
        "phases": {name: {k: phase[k] for k in
                          ("compile_seconds", "wall_seconds")}
                   for name, phase in phases.items()},
        "straddle_plane": phases["serve_1chip"]["straddle_plane"],
        "tp4_tokens_match": tokens_match,
        "claim": None}))
    # the verdict line is the driver's contract: these keys and no others
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
