#!/usr/bin/env python3
"""chipbench/run.py: one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

This parent never imports jax: one process holds the chip, and that is
the child it starts through the program's own CLI (``python -m veles_tpu
chipbench/model_file.py --backend tpu ...``). The parent reads the cell's
data files, makes the traffic from ``--seed`` where the cell serves
requests, waits, and reduces what came back to the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last the numbers that decided
``correct``, each beside its limit. A machine without a TPU, a trace
without device streams, or a tree without the program is an error: the
exit code is not 0 and no result is printed. There is no CPU fallback;
``--rehearse`` (see README.md) runs a tiny cell on the CPU and prints a
line that carries no metric.
"""

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import check, reduce, serve_client, work  # noqa: E402
from chipbench.model_file import CHILD_TAG, SPEC_ENV  # noqa: E402

OUT_DIR = os.path.join(ROOT, "chipbench_out")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CHILD_TIMEOUT = 1150.0


class BenchError(Exception):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name):
    """(manifest, the cell's entry there or None, workload file,
    configuration file)."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    path = os.path.join(HERE, "workloads", name + ".json")
    if not os.path.exists(path):
        raise BenchError("no workload file %s" % path)
    wl = load_json(path)
    cfg_entry = next((c for c in manifest["configs"]
                      if c["name"] == wl["config"]), None)
    cfg_path = (os.path.join(ROOT, cfg_entry["file"]) if cfg_entry else
                os.path.join(HERE, "configs", wl["config"] + ".json"))
    wl["chips"] = entry["chips"] if entry else wl.get("chips", 1)
    return manifest, entry, wl, load_json(cfg_path)


def metric_reader(name):
    """``chipbench/metrics/<name>.py``, or for a split family
    ``<family>.<cell kind>`` the family's file."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise BenchError("no reader chipbench/metrics/%s.py" % name)


def cell_metrics(manifest, cell, group):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(manifest, cell, group, ctx):
    out = {}
    for m in cell_metrics(manifest, cell, group):
        value = metric_reader(m["name"])(dict(ctx, metric=m["name"]))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def child_env(spec):
    env = dict(os.environ)
    env[SPEC_ENV] = json.dumps(spec)
    # the compile cache: where JAX_COMPILATION_CACHE_DIR says if it is set,
    # else inside the checkout at a fixed path (the path is part of the
    # cache's key). It takes every program, however quick to compile
    cache = env.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.makedirs(cache, exist_ok=True)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env.pop("BENCH_RUN", None)
    if spec["platform"] == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def child_args(spec):
    """The program's own CLI with the model file, and then whatever the
    cell's file gives under ``cli`` (mesh, precision, engine flags),
    passed on as it stands."""
    return [sys.executable, "-m", "veles_tpu",
            os.path.join("chipbench", "model_file.py"),
            "--backend", spec["platform"],
            "--random-seed", str(spec["seed"] & 0x7FFFFFFF)
            ] + [str(a) for a in spec["workload"]["cli"]]


class Child:
    """The one process that holds the chip. Its output goes to a log that
    the parent reads; whatever happens, it does not outlive the parent."""

    def __init__(self, spec, log_path):
        self.log_path = log_path
        self._seen = 0
        out = open(log_path, "w")
        try:
            self.proc = subprocess.Popen(
                child_args(spec), cwd=ROOT, env=child_env(spec), stdout=out,
                stderr=subprocess.STDOUT, start_new_session=True)
        finally:
            out.close()

    def lines(self):
        """Whole lines the child has printed since the last call."""
        with open(self.log_path, "rb") as f:
            f.seek(self._seen)
            data = f.read()
        end = data.rfind(b"\n") + 1
        self._seen += end
        return data[:end].decode(errors="replace").splitlines()

    def wait_for(self, needle, timeout, echo=True):
        """The first line that holds ``needle``; every ``chipbench:`` line
        on the way is shown. The child ending first is an error."""
        deadline = time.time() + timeout
        while True:
            for line in self.lines():
                if echo and line.startswith("chipbench: "):
                    print(line, flush=True)
                if needle in line:
                    return line
            if self.proc.poll() is not None:
                for line in self.lines():
                    if needle in line:
                        return line
                raise BenchError("the child exited %d before %r\n%s" % (
                    self.proc.returncode, needle, self.tail()))
            if time.time() > deadline:
                raise BenchError("no %r within %.0f s\n%s"
                                 % (needle, timeout, self.tail()))
            time.sleep(0.05)

    def tail(self, n=40):
        with open(self.log_path, errors="replace") as f:
            return "--- end of %s ---\n%s" % (
                self.log_path, "".join(f.readlines()[-n:]))

    def stop(self, grace=60.0):
        if self.proc.poll() is None:
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        return self.proc.returncode


def run_cell(args):
    t_start = time.time()
    manifest, entry, wl, cfg = load_cell(args.workload)
    if entry is None and not (args.rehearse or args.study):
        raise BenchError("%s is no cell of BENCHMARK.json" % args.workload)
    if not os.path.exists(os.path.join(ROOT, "veles_tpu", "__main__.py")):
        raise BenchError("the program (veles_tpu/) is not in this tree")
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = "%s.%d.%d" % (args.workload, args.seed, args.trace)
    trace_dir = os.path.join(OUT_DIR, "trace." + tag)
    shutil.rmtree(trace_dir, ignore_errors=True)
    spec = {"workload": wl, "config": cfg, "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace,
            "trace_dir": trace_dir, "t_start": t_start,
            "platform": "cpu" if args.rehearse else "tpu",
            "study": args.study,
            "sample_path": os.path.join(OUT_DIR, "sample.%s.json" % tag),
            "keep": os.path.abspath(args.keep) if args.keep else None}
    print("chipbench: cell %s, config %s (depth %d), seed %d, %.0f s, "
          "trace %d" % (args.workload, wl["config"],
                        cfg["num_hidden_layers"], args.seed, args.seconds,
                        args.trace), flush=True)
    child = Child(spec, os.path.join(OUT_DIR, tag + ".log"))
    try:
        extra = ({} if wl["kind"] == "train"
                 else serve_client.drive(child, spec))
        line = child.wait_for(CHILD_TAG, CHILD_TIMEOUT)
        report = json.loads(line[line.index(CHILD_TAG) + len(CHILD_TAG):])
        rc = child.stop()
    finally:
        child.stop(grace=0.0)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if rc != 0:
        raise BenchError("the child exited %d\n%s" % (rc, child.tail()))
    report.update(extra)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep, "report.%s.json" % tag), "w") as f:
            json.dump(report, f)
    return manifest, wl, cfg, report


def result_line(args, manifest, wl, cfg, report):
    device = report["device"]
    ctx = {"report": report, "cfg": cfg, "wl": wl, "work": work,
           "chips": wl["chips"]}
    ctx["peaks"] = (None if args.rehearse
                    else work.load_peaks(device["kind"]))
    group = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(manifest, args.workload, group, ctx)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": report["memory"]["peak_bytes_in_use"]}
    line = {"correct": bool(report["correct"]),
            "attempted": report["attempted"], "failed": report["failed"],
            "metrics": metrics, "device": dev}
    if args.trace:
        trace = report["trace"]
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = {"device_ops": reduce.top_ops(trace),
                             "idle_gaps": trace["idle_gaps"]}
    if args.rehearse:
        # a CPU run is a rehearsal of the control flow, never a measurement
        line["rehearsal"] = True
        line["rehearsal_numbers"] = line["metrics"]
        line["metrics"] = {}
    line["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                      for k, v in report["checks"].items()}
    return line


def earlier_lines(wl, cfg, report):
    mem = report["memory"]
    print("chipbench: device %s; depth %d, minibatch %s"
          % (json.dumps(report["device"]), cfg["num_hidden_layers"],
             wl.get("minibatch", "-")))
    print("chipbench: peak_bytes_in_use %d (%.1f%% of %d), in use at the "
          "close %d" % (mem["peak_bytes_in_use"],
                        100.0 * mem["peak_bytes_in_use"]
                        / max(mem["bytes_limit"], 1),
                        mem["bytes_limit"], mem["bytes_in_use"]))
    print("chipbench: setup %.2f s, of it compiling %.2f s in %d programs; "
          "compilations inside the window: %d; window %.3f s; reference "
          "%.2f s" % (report["setup_s"], report["compile_s"],
                      report["compiles"], report["compiles_in_window"],
                      report["window_s"], report.get("reference_s", 0.0)))
    if report.get("slice"):
        print("chipbench: the profiler was on for %.3f s from %.3f s into "
              "the window; the per-layer metrics are of that slice"
              % (report["slice"]["window_s"], report["slice"]["from_s"]))
    if "generator_late_ms" in report:
        print("chipbench: the load generator ran late by %.3f ms at the "
              "median, %.3f ms at the worst"
              % tuple(report["generator_late_ms"]))
    if "requests" in report:
        reqs = report["requests"]
        ttft = reduce.latency_samples(reqs, "ttft")
        tpot = reduce.latency_samples(reqs, "tpot")
        print("chipbench: %d requests due in the window, %d failed; %.1f "
              "tokens/s received in it; ttft p50 %.1f p95 %.1f ms; tpot p50 "
              "%.1f p95 %.1f ms; last answer %.1f s after the close"
              % (len(reqs), report["failed"],
                 sum(q["tokens_in_window"] for q in reqs)
                 / report["window_s"],
                 reduce.tail_ms(ttft, 50), reduce.tail_ms(ttft, 95),
                 reduce.tail_ms(tpot, 50), reduce.tail_ms(tpot, 95),
                 max([q["last"] or 0.0 for q in reqs] + [0.0])
                 - report["window_s"]))
    for key in ("program", "reference", "study"):
        if key in report:
            print("chipbench: %s %s" % (key, json.dumps(report[key])))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="run on the CPU; the line carries no metric")
    p.add_argument("--study", action="store_true",
                   help="also read the control and the planted faults "
                        "(for setting limits; not a benchmark run)")
    p.add_argument("--keep", metavar="DIR",
                   help="also write the child's whole report, and with "
                        "--trace 1 the start of the trace, into DIR")
    args = p.parse_args(argv)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        manifest, wl, cfg, report = run_cell(args)
        if report["compiles_in_window"]:
            raise BenchError("%d compilation(s) inside the measured window"
                             % report["compiles_in_window"])
        earlier_lines(wl, cfg, report)
        line = result_line(args, manifest, wl, cfg, report)
    except BenchError as e:
        print("chipbench: FAILED: %s" % e, file=sys.stderr)
        return 1
    sys.stdout.flush()
    for text in check.report_lines(report["checks"]):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
