"""CLI entry: ``python -m veles_tpu MODEL.py [CONFIG] [overrides] [flags]``.

Equivalent of the reference's veles/__main__.py:136-867 (Main): argv →
config → model import → Launcher boot → run → results. Model contract
(both reference styles supported):
- ``build_workflow(**kwargs) -> Workflow``  (preferred, simple), or
- ``run(load, main)``: the reference's canonical protocol
  (veles/__main__.py:591-627) — the module calls ``load(WorkflowClass,
  **kw)`` to construct/resume and ``main(**kw)`` to initialize+run.
"""

from __future__ import annotations

import logging
import sys

from .cmdline import (apply_config_overrides, make_parser, parse_args,
                      parse_mesh)
from .config import root
from .error import VelesError
from .import_file import import_file_as_module
from .launcher import Launcher
from .logger import setup_logging


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "trace":
        # telemetry subcommand family (no model/workflow involved):
        #   veles-tpu trace export RUN.jsonl TRACE.json
        return _trace_cli(argv[1:])
    if argv and argv[0] == "metrics":
        # fleet observability subcommand family (telemetry/fleet.py):
        #   veles-tpu metrics aggregate URL [URL ...]
        from .telemetry import fleet
        return fleet.main(argv[1:])
    if argv and argv[0] == "watch":
        # watchtower live dashboard (telemetry/timeseries.py):
        #   veles-tpu watch URL [URL ...] [--endpoints-file ROSTER]
        return _watch_cli(argv[1:])
    if argv and argv[0] == "alerts":
        # watchtower rule states (telemetry/alerts.py):
        #   veles-tpu alerts URL
        return _alerts_cli(argv[1:])
    if argv and argv[0] == "route":
        # serving-fleet front (serving/router.py):
        #   veles-tpu route URL [URL ...] [--port P] [...]
        return _route_cli(argv[1:])
    if argv and argv[0] == "faults":
        # resilience subcommand family:
        #   veles-tpu faults list
        return _faults_cli(argv[1:])
    if argv and argv[0] == "loadgen":
        # load/chaos harness (veles_tpu/loadgen/):
        #   veles-tpu loadgen URL [--requests N] [--rate R] [...]
        return _loadgen_cli(argv[1:])
    if argv and argv[0] == "blackbox":
        # flight-recorder subcommand family (telemetry/recorder.py):
        #   veles-tpu blackbox dump [--out PATH]
        #   veles-tpu blackbox inspect BLACKBOX.jsonl
        return _blackbox_cli(argv[1:])
    if argv and argv[0] == "quantize":
        # quantization subcommand family (veles_tpu/quant/):
        #   veles-tpu quantize SNAPSHOT [--out PATH] [--granularity G]
        return _quantize_cli(argv[1:])
    if argv and argv[0] == "export":
        # export subcommand family (veles_tpu/export/):
        #   veles-tpu export serve-artifact MODEL.py --out DIR [...]
        return _export_cli(argv[1:])
    if argv and argv[0] == "linalg":
        # distributed linear-algebra family (veles_tpu/linalg/):
        #   veles-tpu linalg bench [--m M --k K --n N] [--grid PRxPC]
        #   veles-tpu linalg solve [--n N] [--precondition]
        return _linalg_cli(argv[1:])
    parser = make_parser()
    # intermixed parsing: config overrides (positionals) may appear
    # between/after flags — see cmdline.parse_args
    args = parse_args(parser, argv)
    if args.serve_draft_snapshot and not args.serve_draft:
        # argv-detectable misuse fails BEFORE any (possibly minutes-
        # long) initialize/restore — and regardless of --serve-generate
        parser.error("--serve-draft-snapshot needs --serve-draft")
    if args.serve_draft and args.serve_generate is None:
        parser.error("--serve-draft needs --serve-generate")
    # serving knobs land in the config tree; GenerationAPI (and any
    # programmatic ContinuousEngine) reads root.common.serving.*
    from .config import root as _root
    if args.serve_engine:
        _root.common.serving.engine = args.serve_engine
    if args.serve_slots is not None:
        _root.common.serving.max_slots = args.serve_slots
    if args.serve_buckets is not None:
        _root.common.serving.buckets = args.serve_buckets
    if args.serve_max_context is not None:
        _root.common.serving.max_context = args.serve_max_context
    if args.serve_page_size is not None:
        _root.common.serving.page_size = args.serve_page_size
    if args.serve_pages is not None:
        _root.common.serving.pages = args.serve_pages
    if args.serve_spec_gamma is not None:
        _root.common.serving.spec_gamma = args.serve_spec_gamma
    if args.serve_beam_width is not None:
        _root.common.serving.beam_width = args.serve_beam_width
    if args.serve_artifact:
        _root.common.serving.artifact = args.serve_artifact
    if args.serve_prefix_cache is not None:
        _root.common.serving.prefix_cache = \
            args.serve_prefix_cache == "on"
    if args.serve_prefill_chunk is not None:
        _root.common.serving.prefill_chunk = args.serve_prefill_chunk
    if args.serve_tp is not None:
        _root.common.serving.tp = args.serve_tp
    if args.serve_state_cache is not None:
        _root.common.serving.state_cache = \
            args.serve_state_cache == "on"
    if args.serve_stream is not None:
        _root.common.serving.stream = args.serve_stream == "on"
    if args.serve_drain_grace is not None:
        _root.common.serving.drain_grace = args.serve_drain_grace
    if args.serve_drain_handoff is not None:
        _root.common.serving.drain_handoff = \
            args.serve_drain_handoff == "on"
    if args.serve_qos is not None:
        _root.common.serving.qos = args.serve_qos == "on"
    if args.profile_dir and args.serve_generate is not None:
        # a server never returns from a run() to bracket: the flag
        # arms POST /generate/profile instead
        _root.common.serving.profile_dir = args.profile_dir
    if args.router_qos is not None:
        _root.common.router.qos = args.router_qos == "on"
    if args.router_slo_ttft_ms is not None:
        _root.common.router.slo_ttft_ms = args.router_slo_ttft_ms
    # quantization policy (veles_tpu/quant/): the flags arm the config
    # tree; the serving engine (and any programmatic consumer) reads
    # root.common.quant.*
    if args.quant_weights:
        _root.common.quant.weights = True
    if args.quant_kv:
        _root.common.quant.kv = True
    level = (logging.WARNING, logging.INFO,
             logging.DEBUG)[min(args.verbose, 2)]
    setup_logging(level=level, tracefile=args.trace_file)
    if args.trace_file:
        # telemetry spans stream into the same JSONL file as the logger
        # events (span records carry name+ts+dur, events name+time —
        # `trace export` picks out the spans); one --trace-file, one
        # observability stream
        from .telemetry.spans import recorder
        recorder.set_sink(args.trace_file)
    if args.debug:
        from .logger import enable_debug
        enable_debug(args.debug)

    # config layering: file, then inline overrides; a bare root.x=y in the
    # config position is an override, not a file
    if args.config and "=" in args.config:
        args.config_list.insert(0, args.config)
        args.config = None
    if args.config:
        root.update_from_file(args.config)
    if args.config_list:
        apply_config_overrides(root, args.config_list)
    if args.force_numpy:
        root.common.engine.force_numpy = True
    if args.mixed_precision:
        root.common.engine.mixed_precision = True
    if args.backend in ("cpu", "numpy"):
        # keep jax away from the chip (one process holds it at a time)
        # when the user explicitly asked for a host backend
        import jax
        jax.config.update("jax_platforms", "cpu")
    if args.slave_death_probability:
        root.common.slave_death_probability = args.slave_death_probability
    if args.elastic:
        # elastic generation controller (resilience/elastic.py): host
        # loss ends a generation, not the run
        root.common.resilience.elastic.enabled = True
    if args.job_timeout:
        root.common.job_timeout = args.job_timeout
    if args.snapshot_dir:
        root.common.dirs.snapshots = args.snapshot_dir
    if args.tensormon or args.nan_policy:
        # model-health taps (telemetry/tensormon.py): --nan-policy
        # implies monitoring — a sentinel with no taps would be inert
        root.common.telemetry.tensormon.enabled = True
        if args.nan_policy:
            root.common.telemetry.tensormon.nan_policy = args.nan_policy
    if args.blackbox:
        root.common.telemetry.recorder.autodump = True
    if args.overlap:
        # the overlap engine (veles_tpu/overlap/): async side-plane +
        # non-blocking checkpoints; prefetch depth rides its own flag
        root.common.overlap.enabled = True
        root.common.overlap.async_snapshots = True
    if args.prefetch_depth is not None:
        root.common.overlap.prefetch_depth = int(args.prefetch_depth)
    if args.timings:
        root.common.trace.timings = True
    if args.dump_config:
        root.print_()
        return 0

    launcher = Launcher(
        backend=args.backend,
        mesh=parse_mesh(args.mesh) if args.mesh else None,
        coordinator=args.coordinator, num_processes=args.num_processes,
        process_id=args.process_id, random_seed=args.random_seed,
        test_mode=args.test,
        graphics=args.graphics, plots_dir=args.plots_dir,
        status_url=args.status_url,
        notification_interval=args.status_interval,
        profile_dir=args.profile_dir)

    module = import_file_as_module(args.model)
    # a model module may (re)set config keys at import time (including
    # Range markers); the user's config FILE and inline overrides must
    # win — re-apply both, in layering order
    if args.config:
        root.update_from_file(args.config)
    if args.config_list:
        apply_config_overrides(root, args.config_list)

    if args.optimize or args.ensemble_train or args.ensemble_test:
        return _run_meta(launcher, module, args)

    _materialize(args)

    if hasattr(module, "run"):
        # reference-style protocol
        state = {}

        def load(workflow_cls, **kwargs):
            state["workflow"] = workflow_cls(**kwargs)
            return state["workflow"], bool(args.snapshot)

        def main_(**kwargs):
            return _drive(launcher, state["workflow"], args)
        module.run(load, main_)
        return 0
    if hasattr(module, "build_workflow"):
        workflow = module.build_workflow()
        _drive(launcher, workflow, args)
        return 0
    raise VelesError(
        "%s defines neither build_workflow() nor run(load, main)"
        % args.model)


def _trace_cli(argv) -> int:
    """``veles-tpu trace export RUN.jsonl TRACE.json`` — convert a
    span JSONL stream (--trace-file output, or a
    telemetry.spans.recorder.to_jsonl dump) into Chrome trace_event
    JSON viewable in Perfetto / chrome://tracing.

    ``veles-tpu trace self-time DIR`` — read the capture in a
    ``jax.profiler`` log directory (``--profile-dir``, ``POST
    /generate/profile``) and print device time by program, by named
    scope within each program, and the device's idle gaps by the
    program's own span that covers each (telemetry/devtime.py). A
    Chrome trace file ``TRACE.json[.gz]`` gives per-stream and
    per-span device self-time."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="veles_tpu trace",
        description="telemetry trace tools (veles_tpu/telemetry/)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    exp = sub.add_parser(
        "export", help="span JSONL -> Chrome trace_event JSON")
    exp.add_argument("jsonl", help="span JSONL (from --trace-file)")
    exp.add_argument("out", help="trace_event JSON to write")
    exp.add_argument("--request", default=None, metavar="ID",
                     help="export only spans tagged with this "
                          "request_id (one serving request's "
                          "timeline — no hand-grepping the JSONL)")
    fl = sub.add_parser(
        "fleet",
        help="pull span rings from a router + its replicas "
             "(GET /trace/spans), align clocks, merge into ONE "
             "Chrome trace — one lane per process "
             "(docs/observability.md 'Fleet tracing')")
    fl.add_argument("urls", nargs="*", metavar="URL",
                    help="endpoint serving /trace/spans (the router "
                         "and/or replicas; bare host:port accepted)")
    fl.add_argument("--endpoints-file", default=None, metavar="FILE",
                    help="replica roster file — same format as "
                         "`route`/`metrics aggregate` (plain lines, "
                         "or a saved GET /roster page); the router's "
                         "own URL still goes in positionally")
    fl.add_argument("--out", required=True, metavar="TRACE.json",
                    help="merged Chrome trace to write (open in "
                         "Perfetto)")
    fl.add_argument("--request", default=None, metavar="ID",
                    help="keep one request's story only: a "
                         "request_id or trace_id — the whole fleet "
                         "trace of that request (queue, attempts, "
                         "backoff, resume) across every process")
    fl.add_argument("--timeout", type=float, default=5.0,
                    help="per-endpoint pull timeout, seconds")
    st = sub.add_parser(
        "self-time",
        help="device self-time summary of a captured trace "
             "(docs/perf.md 'Device-time measurement plane')")
    st.add_argument("trace",
                    help="a jax.profiler log directory, or a Chrome "
                         "trace-event JSON[.gz]")
    st.add_argument("--depth", type=int, default=2, metavar="N",
                    help="scope levels kept in the by-scope table")
    st.add_argument("--top", type=int, default=12, metavar="N",
                    help="print at most N rows per table")
    args = parser.parse_args(argv)
    if args.cmd == "fleet":
        return _trace_fleet(args)
    if args.cmd == "self-time":
        return _trace_self_time(args)
    from .telemetry import chrome_trace
    try:
        n = chrome_trace.export(args.jsonl, args.out,
                                request_id=args.request)
    except (OSError, ValueError) as e:
        print("trace export failed: %s" % e, file=sys.stderr)
        return 1
    print("exported %d spans%s -> %s (open in Perfetto: "
          "https://ui.perfetto.dev)"
          % (n, " for request %s" % args.request if args.request
             else "", args.out))
    return 0


def _trace_fleet(args) -> int:
    """``veles-tpu trace fleet URL... --out trace.json`` — pull the
    span ring of every listed process (router + replicas), estimate
    per-process clock offsets by bracketing alignment
    (``route.attempt`` spans contain the replica ``request`` spans
    they proxied — telemetry/fleet.py), and write ONE merged Chrome
    trace with one lane per process. With ``--request ID`` the trace
    is a single request's full cross-fleet story."""
    import json as _json
    from .telemetry import fleet as _fleet
    urls = list(args.urls)
    if args.endpoints_file:
        from .telemetry.fleet import read_endpoints
        try:
            urls += read_endpoints(args.endpoints_file)
        except (OSError, ValueError) as e:
            print("trace fleet: bad --endpoints-file: %s" % e,
                  file=sys.stderr)
            return 1
    if not urls:
        print("trace fleet: no endpoints (positional URLs and/or "
              "--endpoints-file)", file=sys.stderr)
        return 1
    try:
        doc, summary = _fleet.trace_fleet(
            urls, request=args.request, timeout=args.timeout)
    except ValueError as e:
        print("trace fleet failed: %s" % e, file=sys.stderr)
        return 1
    with open(args.out, "w") as fout:
        _json.dump(doc, fout)
    down = [s for s in summary.get("endpoints", ())
            if not s["up"]]
    print("fleet trace: %d span(s) over %d process lane(s)%s -> %s "
          "(open in Perfetto: https://ui.perfetto.dev)"
          % (summary["spans"], summary["processes"],
             " for %s" % "/".join(summary.get("trace_ids", ()))
             if args.request else "", args.out))
    for key, info in sorted(summary["offsets"].items(),
                            key=lambda kv: str(kv[0])):
        pid = info.get("pid", key)
        if info.get("reference"):
            print("  pid %-8s reference clock (the router's)" % pid)
        elif info["pairs"]:
            print("  pid %-8s offset %+0.6fs over %d bracketing "
                  "pair(s), +/-%.6fs" % (pid, info["offset"],
                                         info["pairs"],
                                         info["bound"] or 0.0))
        else:
            print("  pid %-8s no bracketing pair — own clock "
                  "(offset unknown)" % pid)
    for s in down:
        print("  down: %s (%s)" % (s["url"], s["error"]),
              file=sys.stderr)
    return 0


def _trace_self_time(args) -> int:
    """A profiler log directory: the three tables of its ``.xplane.pb``
    (device time by program, by scope, idle gaps by host span). A
    Chrome trace-event file (torn/truncated files are salvaged with a
    counted warning, like ``spans.read_jsonl``): per-stream device
    self-time, and per span for the spans the trace itself holds."""
    import os as _os
    from .telemetry import devtime
    top = max(0, args.top)
    try:
        capture = (devtime.find_capture(args.trace)
                   if _os.path.isdir(args.trace) else None)
        if capture is not None:
            summary = devtime.summarize_capture(
                devtime.load_capture(capture), depth=args.depth)
            print("capture %s" % capture)
            print("\n".join(devtime.format_capture(summary, top)))
            return 0
        if _os.path.isdir(args.trace):
            events = devtime.load_profile_dir(args.trace)
        else:
            events = devtime.load_trace_events(args.trace)
    except (OSError, ValueError) as e:
        print("trace self-time failed: %s" % e, file=sys.stderr)
        return 1
    st = devtime.device_self_time(events)
    print("device self-time: %.6f s over %d device-stream event(s)"
          % (st["device_time_s"], st["n_events"]))
    if not st["n_events"]:
        print("  (no device streams — a host-only capture)")
    rows = sorted(st["by_stream"].items(), key=lambda kv: -kv[1])
    for label, secs in rows[:top]:
        print("  %-40s %.6f s" % (label, secs))
    per = devtime.attribute_spans(events)
    if per:
        print("per-span device self-time (%d span name(s)):"
              % len(per))
        rows = sorted(per.items(),
                      key=lambda kv: -kv[1]["device_time_s"])
        for name, row in rows[:top]:
            print("  %-40s %.6f s over %d span(s)"
                  % (name, row["device_time_s"], row["spans"]))
    return 0


def _faults_cli(argv) -> int:
    """``veles-tpu faults list`` — print the registered fault-injection
    points of the resilience plane (veles_tpu/resilience/faults.py) and
    the spec that is currently armed, if any."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="veles_tpu faults",
        description="deterministic fault-injection plane "
                    "(docs/resilience.md)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    sub.add_parser("list", help="print registered injection points")
    parser.parse_args(argv)
    from .resilience import faults
    print("registered injection points (arm via VELES_FAULTS or "
          "root.common.resilience.faults):")
    for name, desc in sorted(faults.list_points().items()):
        print("  %-17s %s" % (name, desc))
    print("clause grammar: point:action[:p=P,after=N,times=N,"
          "delay=S,window=T0:T1]")
    print("  window=T0:T1 arms the action only between the T0-th and "
          "T1-th trigger\n  of the point (then it heals) — the timed "
          "chaos-storm form `veles-tpu\n  loadgen --storm` requires")
    spec = faults.plane.current_spec()
    print("active spec: %s" % (spec or "(none)"))
    return 0


def _linalg_cli(argv) -> int:
    """``veles-tpu linalg bench|solve`` — the distributed
    linear-algebra workload family (veles_tpu/linalg/,
    docs/workloads.md) from the command line.

    ``bench`` runs the blocked kernels (block-cyclic SUMMA matmul,
    right-looking Cholesky solve) over the device mesh, checks each
    against the dense ``numpy.linalg`` reference within the stated
    dtype tolerance, and prints one JSON line with the relative
    errors, the achieved MFU graded against the dtype-correct peak
    table and the stated SUMMA step-time prediction.

    ``solve`` runs conjugate gradient on the 5-point Poisson model
    problem as a Workflow graph (``--precondition`` arms the 2-level
    multigrid V-cycle) and prints the per-iteration residual story."""
    import argparse
    import json as _json
    import time as _time
    parser = argparse.ArgumentParser(
        prog="veles_tpu linalg",
        description="distributed linear-algebra workloads "
                    "(docs/workloads.md)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    bench = sub.add_parser(
        "bench", help="blocked kernels vs the dense reference + MFU")
    bench.add_argument("--m", type=int, default=512)
    bench.add_argument("--k", type=int, default=512)
    bench.add_argument("--n", type=int, default=512)
    bench.add_argument("--cholesky", type=int, default=256,
                       metavar="N",
                       help="SPD factor/solve size (0 skips it)")
    bench.add_argument("--block", type=int, default=None,
                       help="block size (default linalg.DEFAULT_BLOCK)")
    bench.add_argument("--grid", default=None, metavar="PRxPC",
                       help="device grid, e.g. 2x4 (default: squarest "
                            "factorization of the visible devices)")
    bench.add_argument("--dtype", default="float32",
                       choices=("float32", "float64"),
                       help="computation dtype (grades MFU against "
                            "the matching peak table)")
    bench.add_argument("--seed", type=int, default=0)
    solve = sub.add_parser(
        "solve", help="CG on the Poisson problem as a Workflow graph")
    solve.add_argument("--n", type=int, default=64, metavar="N",
                       help="interior grid side (N^2 unknowns)")
    solve.add_argument("--tol", type=float, default=1e-6)
    solve.add_argument("--max-iters", type=int, default=500)
    solve.add_argument("--precondition", action="store_true",
                       help="2-level multigrid V-cycle preconditioner "
                            "(needs even --n)")
    solve.add_argument("--grid", default=None, metavar="PRxPC")
    solve.add_argument("--block", type=int, default=None)
    solve.add_argument("--seed", type=int, default=0)
    solve.add_argument("--json", default=None, metavar="PATH",
                       help="write {iterations, residual, history} "
                            "as JSON")
    args = parser.parse_args(argv)
    import numpy
    from .linalg import (DEFAULT_BLOCK, LinalgError, TwoLevelPoisson,
                         blocked_matmul, build_cg_workflow,
                         cholesky_solve, default_tolerance,
                         linalg_mesh, poisson2d_matvec,
                         predict_summa_time)
    grid = None
    if args.grid:
        try:
            pr, _, pc = args.grid.lower().partition("x")
            grid = (int(pr), int(pc))
        except ValueError:
            parser.error("--grid wants PRxPC, e.g. 2x4")
    block = args.block or DEFAULT_BLOCK
    mesh = linalg_mesh(grid)
    rng = numpy.random.RandomState(args.seed)
    if args.cmd == "solve":
        rhs = rng.standard_normal(args.n * args.n).astype(numpy.float32)
        precond = None
        if args.precondition:
            precond = TwoLevelPoisson(args.n, block=block, mesh=mesh)
        wf = build_cg_workflow(poisson2d_matvec(args.n), rhs,
                               tol=args.tol, max_iters=args.max_iters,
                               preconditioner=precond)
        wf.initialize()
        try:
            wf.run()
        except LinalgError as e:
            print("linalg solve FAILED verification: %s" % e,
                  file=sys.stderr)
            return 1
        res = wf.cg_decision.get_metric_values()
        print("poisson %dx%d (%d unknowns)%s: %s in %d iteration(s), "
              "recurrence residual %.3e, verified true residual %s"
              % (args.n, args.n, args.n * args.n,
                 " + multigrid V-cycle" if precond else "",
                 "converged" if res["converged"] else
                 "DID NOT CONVERGE", res["iterations"],
                 res["residual"],
                 "%.3e" % res["true_residual"]
                 if res["true_residual"] is not None else "(skipped)"))
        history = res["residual_history"]
        for i in range(0, len(history),
                       max(1, len(history) // 10) or 1):
            print("  iter %-4d residual %.3e" % (i, history[i]))
        if args.json:
            with open(args.json, "w") as fh:
                _json.dump(res, fh, indent=2, sort_keys=True)
            print("report written: %s" % args.json)
        return 0 if res["converged"] else 1
    # bench
    from .telemetry.cost import UnknownDevice, peak_flops_entry
    dtype = numpy.dtype(args.dtype)
    tol = default_tolerance(dtype)
    a = rng.standard_normal((args.m, args.k)).astype(dtype)
    b = rng.standard_normal((args.k, args.n)).astype(dtype)
    c = numpy.asarray(blocked_matmul(a, b, block=block, mesh=mesh))
    ref = a.astype(numpy.float64) @ b.astype(numpy.float64)
    mm_err = float(numpy.linalg.norm(c - ref) / numpy.linalg.norm(ref))
    t0 = _time.perf_counter()
    blocked_matmul(a, b, block=block, mesh=mesh)
    step_s = max(_time.perf_counter() - t0, 1e-9)
    pgrid = tuple(mesh.devices.shape)
    report = {
        "grid": "%dx%d" % pgrid,
        "dtype": args.dtype,
        "block": block,
        "matmul": {"m": args.m, "k": args.k, "n": args.n,
                   "rel_err": mm_err, "tolerance": tol,
                   "step_s": step_s},
        "predicted": predict_summa_time(args.m, args.k, args.n, pgrid,
                                        t1_step_s=step_s, dtype=dtype),
    }
    try:
        peak_source, peak = peak_flops_entry(dtype)
    except UnknownDevice:
        pass        # e.g. the CPU: no peak on file, so no utilization
    else:
        report["matmul"]["mfu"] = (2.0 * args.m * args.n * args.k) / (
            step_s * peak * mesh.size)
        report.update(peak_flops_used=peak, peak_source=peak_source)
    failed = not mm_err < tol
    if args.cholesky:
        g = rng.standard_normal((args.cholesky,
                                 args.cholesky)).astype(dtype)
        spd = g @ g.T + args.cholesky * numpy.eye(args.cholesky,
                                                  dtype=dtype)
        rhs = rng.standard_normal((args.cholesky, 1)).astype(dtype)
        try:
            x = numpy.asarray(cholesky_solve(spd, rhs, block=block,
                                             mesh=mesh, check=True))
            xref = numpy.linalg.solve(spd.astype(numpy.float64),
                                      rhs.astype(numpy.float64))
            ch_err = float(numpy.linalg.norm(x - xref)
                           / numpy.linalg.norm(xref))
            report["cholesky"] = {"n": args.cholesky,
                                  "rel_err": ch_err, "tolerance": tol}
            failed = failed or not ch_err < tol
        except LinalgError as e:
            report["cholesky"] = {"n": args.cholesky, "error": str(e)}
            failed = True
    print(_json.dumps(report))
    if failed:
        print("linalg bench FAILED the dense-reference tolerance",
              file=sys.stderr)
    return 1 if failed else 0


def _alerts_url(url: str) -> str:
    url = url.strip()
    if "://" not in url:
        url = "http://" + url
    url = url.rstrip("/")
    if url.endswith("/metrics"):
        url = url[:-len("/metrics")]
    return url + "/alerts"


def _fetch_alerts(urls, timeout: float = 5.0):
    """First answering ``GET /alerts`` page across ``urls`` →
    (payload, url) — or (None, None) when nobody answered."""
    import json as _json
    import urllib.request
    for url in urls:
        try:
            with urllib.request.urlopen(_alerts_url(url),
                                        timeout=timeout) as r:
                return _json.loads(r.read() or b"{}"), url
        except Exception:        # noqa: BLE001 — a down endpoint is data
            continue
    return None, None


def _watch_frame(rep, agg, alerts) -> str:
    """One dashboard frame (``veles-tpu watch``): fleet rates +
    windowed quantiles from the client-side SeriesStore, roster
    health, and the firing-alert block."""
    def fmt(v, unit="", nd=None):
        if v is None:
            return "-"
        if nd is not None:
            v = round(v, nd)
        return "%g%s" % (v, unit)
    lines = ["veles-tpu watch  %s/%s endpoint(s) up"
             % (fmt(rep["up"]), fmt(rep["endpoints"]))]
    lines.append("  qps %-8s tok/s %-8s shed/s %s"
                 % (fmt(rep["qps"]), fmt(rep["tok_s"]),
                    fmt(rep["shed_s"])))
    lines.append("  ttft p50/p99 %s/%s   tpot p50/p99 %s/%s   "
                 "e2e p99 %s"
                 % (fmt(rep["ttft_p50"], "s"), fmt(rep["ttft_p99"], "s"),
                    fmt(rep["tpot_p50"], "s"), fmt(rep["tpot_p99"], "s"),
                    fmt(rep["e2e_p99"], "s")))
    lines.append("  slots busy %s/%s   queue %s   brownout L%s   "
                 "admit %s"
                 % (fmt(rep["slots_busy"]), fmt(rep["slots"]),
                    fmt(rep["queue_depth"]), fmt(rep["brownout"]),
                    fmt(rep["admit_rate"], nd=3)))
    for ep in agg["endpoints"]:
        lines.append("  %-4s %s%s"
                     % ("up" if ep["up"] else "DOWN", ep["url"],
                        "" if ep["up"] else "  (%s)" % ep["error"]))
    if alerts is None:
        lines.append("  alerts: no /alerts endpoint answered")
    elif not alerts.get("enabled"):
        lines.append("  alerts: watchtower off "
                     "(root.common.telemetry.watch.enabled)")
    else:
        firing = [r for r in alerts.get("rules", ())
                  if r.get("state") == "firing"]
        if not firing:
            lines.append("  alerts: %d rule(s), none firing"
                         % len(alerts.get("rules", ())))
        for r in firing:
            lines.append("  alerts: FIRING %s (%s) value=%s since=%s"
                         % (r.get("rule"), r.get("severity"),
                            r.get("value"), r.get("since")))
    return "\n".join(lines)


def _watch_cli(argv) -> int:
    """``veles-tpu watch URL [URL ...]`` — live terminal dashboard
    over a serving fleet: scrape every endpoint's ``/metrics`` each
    period into a client-side watchtower SeriesStore
    (telemetry/timeseries.py, ``count_samples=False``), display
    WINDOWED rates and latency quantiles (bucket deltas between
    samples — not the cumulative-since-start ``_p99`` gauges), the
    roster's up/down state, and the firing alerts from the fleet's
    ``GET /alerts``."""
    import argparse
    import json as _json
    import time as _time
    parser = argparse.ArgumentParser(
        prog="veles_tpu watch",
        description="live fleet watch dashboard "
                    "(docs/observability.md 'Watchtower')")
    parser.add_argument("urls", nargs="*", metavar="URL",
                        help="endpoint serving /metrics (router "
                             "and/or replicas; bare host:port "
                             "accepted)")
    parser.add_argument("--endpoints-file", default=None,
                        metavar="FILE",
                        help="replica roster file — same format as "
                             "`route`/`metrics aggregate` (plain "
                             "lines, or a saved GET /roster page)")
    parser.add_argument("--period", type=float, default=1.0,
                        metavar="SEC",
                        help="seconds between scrapes (default 1)")
    parser.add_argument("--window", type=float, default=30.0,
                        metavar="SEC",
                        help="trailing window for rates/quantiles "
                             "(default 30)")
    parser.add_argument("--iterations", type=int, default=0,
                        metavar="N",
                        help="stop after N frames (0 = run until "
                             "interrupted)")
    parser.add_argument("--once", action="store_true",
                        help="two samples one period apart, one "
                             "frame, exit (scriptable snapshot)")
    parser.add_argument("--no-clear", action="store_true",
                        help="append frames instead of redrawing "
                             "(logs, tests)")
    parser.add_argument("--json", action="store_true",
                        help="print one JSON line per frame instead "
                             "of the dashboard (implies --no-clear)")
    parser.add_argument("--timeout", type=float, default=5.0,
                        help="per-endpoint scrape timeout, seconds")
    args = parser.parse_args(argv)
    from .telemetry import fleet as _fleet
    from .telemetry.timeseries import SeriesStore
    urls = list(args.urls)
    if args.endpoints_file:
        try:
            urls += _fleet.read_endpoints(args.endpoints_file)
        except (OSError, ValueError) as e:
            parser.error("bad --endpoints-file: %s" % e)
    if not urls:
        parser.error("no endpoints (positional URLs and/or "
                     "--endpoints-file)")
    if args.period <= 0:
        parser.error("--period must be > 0")
    store = SeriesStore(period=args.period,
                        retention=max(600.0, args.period * 600),
                        count_samples=False)
    iterations = 2 if args.once else args.iterations
    n = 0
    last_up = 0
    try:
        while True:
            agg = _fleet.aggregate(urls, timeout=args.timeout)
            _fleet.ingest_aggregate(store, agg)
            last_up = sum(1 for ep in agg["endpoints"] if ep["up"])
            n += 1
            final = iterations and n >= iterations
            # --once stays quiet until its second sample: the first
            # frame of a fresh store has no deltas to show
            if not args.once or final:
                rep = _fleet.interval_report(store, window=args.window)
                alerts, _ = _fetch_alerts(urls, timeout=args.timeout)
                if args.json:
                    rep["alerts"] = alerts
                    print(_json.dumps(rep, sort_keys=True))
                else:
                    if not args.no_clear:
                        print("\x1b[2J\x1b[H", end="")
                    print(_watch_frame(rep, agg, alerts), flush=True)
            if final:
                break
            _time.sleep(args.period)
    except KeyboardInterrupt:
        pass
    return 0 if last_up else 2


def _alerts_cli(argv) -> int:
    """``veles-tpu alerts URL`` — list the fleet watchtower's alert
    rule states (``GET /alerts``). Exit 0 with nothing firing, 1
    with at least one firing rule (scriptable: a deploy gate can
    refuse to proceed into a burning fleet), 2 when no endpoint
    answered."""
    import argparse
    import json as _json
    parser = argparse.ArgumentParser(
        prog="veles_tpu alerts",
        description="watchtower alert rule states "
                    "(docs/observability.md 'Watchtower')")
    parser.add_argument("urls", nargs="+", metavar="URL",
                        help="endpoint serving /alerts (first "
                             "answering one is reported)")
    parser.add_argument("--timeout", type=float, default=5.0)
    parser.add_argument("--json", action="store_true",
                        help="print the raw /alerts payload")
    args = parser.parse_args(argv)
    payload, url = _fetch_alerts(args.urls, timeout=args.timeout)
    if payload is None:
        print("alerts: no endpoint answered /alerts", file=sys.stderr)
        return 2
    if args.json:
        print(_json.dumps(payload, indent=2, sort_keys=True))
        return 1 if payload.get("firing") else 0
    if not payload.get("enabled"):
        print("%s: watchtower off "
              "(set root.common.telemetry.watch.enabled)" % url)
        return 0
    rules = payload.get("rules", [])
    print("%s: %d rule(s), %d firing"
          % (url, len(rules), len(payload.get("firing", []))))
    for r in rules:
        print("  %-8s %-24s %-9s value=%-12s since=%s"
              % (r.get("severity"), r.get("rule"),
                 r.get("state") or "pending",
                 r.get("value"), r.get("since")))
    return 1 if payload.get("firing") else 0


def _loadgen_cli(argv) -> int:
    """``veles-tpu loadgen URL`` — drive a serving endpoint (replica
    or router front) open-loop with a seeded synthetic workload
    (veles_tpu/loadgen/), optionally under timed chaos storms, and
    print the per-class latency aggregates plus the SLO verdict.
    Storms arm the PROCESS-LOCAL fault plane, so they reach
    in-process fleets only — arm a remote replica through its own
    VELES_FAULTS."""
    import argparse
    import json as _json
    parser = argparse.ArgumentParser(
        prog="veles_tpu loadgen",
        description="open-loop fleet load/chaos harness "
                    "(docs/services.md 'Overload & QoS')")
    parser.add_argument("url", metavar="URL",
                        help="endpoint to drive (http://host:port)")
    parser.add_argument("--path", default="/generate",
                        help="POST path (default /generate)")
    parser.add_argument("--requests", type=int, default=100,
                        metavar="N", help="requests to offer")
    parser.add_argument("--rate", type=float, default=20.0,
                        metavar="R", help="offered req/s (base rate)")
    parser.add_argument("--shape", default="steady",
                        choices=("steady", "burst", "diurnal"),
                        help="arrival shape (default steady)")
    parser.add_argument("--n-new", type=int, default=8, metavar="T",
                        help="tokens to decode per request")
    parser.add_argument("--min-prompt", type=int, default=4)
    parser.add_argument("--max-prompt", type=int, default=64)
    parser.add_argument("--vocab", type=int, default=128,
                        help="prompt token id upper bound (match the "
                             "served model's vocabulary)")
    parser.add_argument("--batch-fraction", type=float, default=0.5,
                        metavar="F",
                        help="fraction labeled priority=batch")
    parser.add_argument("--stream-fraction", type=float, default=0.0,
                        metavar="F", help="fraction streaming (SSE)")
    parser.add_argument("--sample-fraction", type=float, default=0.25,
                        metavar="F", help="fraction mode=sample")
    parser.add_argument("--shared-fraction", type=float, default=0.5,
                        metavar="F",
                        help="fraction opening with a shared prefix")
    parser.add_argument("--deadline-ms", type=float, default=None,
                        metavar="MS",
                        help="per-request deadline for interactive "
                             "requests (propagated to the fleet)")
    parser.add_argument("--storm", action="append", default=[],
                        metavar="SPEC",
                        help="timed chaos storm, a fault clause with "
                             "a window= field (repeatable), e.g. "
                             "serve.replica_death:raise:window=50:51")
    parser.add_argument("--timeout", type=float, default=60.0,
                        metavar="SEC", help="per-request client "
                        "patience (default 60)")
    parser.add_argument("--slo-ttft-ms", type=float, default=2000.0,
                        metavar="MS", help="interactive TTFT p99 "
                        "bound for the verdict (default 2000)")
    parser.add_argument("--max-interactive-loss", type=float,
                        default=0.05, metavar="F",
                        help="interactive shed+error fraction bound")
    parser.add_argument("--min-goodput", type=float, default=0.0,
                        metavar="TPS",
                        help="goodput floor (tokens/s) for the "
                             "verdict (default 0 = no floor)")
    parser.add_argument("--abort-on-alert", action="store_true",
                        help="poll the fleet's GET /alerts while "
                             "driving and stop dispatching the "
                             "moment any watchtower rule fires — "
                             "the run FAILS at fire time instead of "
                             "at the end-of-run verdict")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--json", default=None, metavar="PATH",
                        help="write the full report (records "
                             "included) as JSON")
    args = parser.parse_args(argv)
    from .loadgen import LoadGen, Workload, parse_storm, verdict
    workload = Workload(
        n_requests=args.requests, rate=args.rate, shape=args.shape,
        min_prompt=args.min_prompt, max_prompt=args.max_prompt,
        n_new=args.n_new, vocab=args.vocab,
        shared_fraction=args.shared_fraction,
        batch_fraction=args.batch_fraction,
        stream_fraction=args.stream_fraction,
        sample_fraction=args.sample_fraction,
        deadline_ms=args.deadline_ms, seed=args.seed)
    storms = [parse_storm(s) for s in args.storm]
    url = args.url if "://" in args.url else "http://" + args.url
    report = LoadGen(url, workload, storms=storms, path=args.path,
                     timeout=args.timeout,
                     abort_on_alert=args.abort_on_alert).run()
    slo = verdict(report, slo_ttft_ms=args.slo_ttft_ms,
                  max_interactive_loss=args.max_interactive_loss,
                  min_goodput_tokens_per_s=args.min_goodput)
    report["verdict"] = slo
    agg = report["aggregates"]
    print("offered %d, answered %d in %.1fs (goodput %.1f tok/s)"
          % (report["offered"], report["answered"],
             report["wall_seconds"], agg["goodput_tokens_per_s"]))
    aborted = report.get("aborted_on_alert")
    if aborted is not None:
        print("  ABORTED on firing alert(s) %s after %d dispatched"
              % (",".join(aborted["rules"]) or "(unknown)",
                 aborted["after_requests"]))
    for cls in ("interactive", "batch"):
        row = agg[cls]
        print("  %-11s ok=%d shed=%d err=%d ttft_p99=%sms "
              "e2e_p99=%sms" % (cls, row["ok"], row["shed"],
                                row["errors"], row["ttft_p99_ms"],
                                row["e2e_p99_ms"]))
    if agg["server_ttft_p99_ms"] is not None:
        print("  server ttft_p99=%sms queue_wait_p99=%sms"
              % (agg["server_ttft_p99_ms"],
                 agg["server_queue_wait_p99_ms"]))
    for check in slo["checks"]:
        print("  [%s] %s: %s vs %s"
              % ("ok" if check["ok"] else "FAIL", check["name"],
                 check["observed"], check["bound"]))
    if args.json:
        with open(args.json, "w") as fh:
            _json.dump(report, fh, indent=2, sort_keys=True)
        print("report written: %s" % args.json)
    print("verdict: %s" % ("PASS" if slo["pass"] else "FAIL"))
    return 0 if slo["pass"] else 1


def _route_cli(argv) -> int:
    """``veles-tpu route URL [URL ...]`` — run the serving-fleet
    router (serving/router.py): health-gated admission over the
    replica roster, per-replica circuit breakers, idempotent failover
    keyed on request_id, graceful drain on SIGTERM / POST /drain.
    The roster comes from positional URLs and/or ``--endpoints-file``
    (plain lines, or the JSON a saved ``GET /roster`` page is — the
    same file ``veles-tpu metrics aggregate --endpoints-file``
    consumes, so fleet scraping and routing share one roster)."""
    import argparse
    import signal
    import threading
    parser = argparse.ArgumentParser(
        prog="veles_tpu route",
        description="serving fleet router "
                    "(docs/services.md 'Serving fleet')")
    parser.add_argument("endpoints", nargs="*", metavar="URL",
                        help="replica endpoint (http://host:port; "
                             "bare host:port accepted)")
    parser.add_argument("--endpoints-file", default=None,
                        metavar="FILE",
                        help="replica roster file: one endpoint per "
                             "line (# comments), or JSON "
                             "({\"endpoints\": [...]} / a bare list)")
    parser.add_argument("--port", type=int, default=0,
                        help="router port (0 = ephemeral, printed)")
    parser.add_argument("--path", default="/generate",
                        help="proxied POST path (default /generate)")
    parser.add_argument("--probe-interval", type=float, default=None,
                        metavar="SEC",
                        help="replica /readyz + /metrics probe period "
                             "(root.common.router.probe_interval, "
                             "default 1)")
    parser.add_argument("--failure-threshold", type=int, default=None,
                        metavar="N",
                        help="consecutive attempt failures that open "
                             "a replica's circuit breaker (default 3)")
    parser.add_argument("--retry-budget", type=int, default=None,
                        metavar="N",
                        help="failover retries per request beyond the "
                             "first attempt (default 2)")
    parser.add_argument("--attempt-timeout", type=float, default=None,
                        metavar="SEC",
                        help="patience per replica attempt before "
                             "failing over (default 10)")
    parser.add_argument("--request-timeout", type=float, default=None,
                        metavar="SEC",
                        help="total routing budget per request "
                             "(default 120)")
    parser.add_argument("--drain-grace", type=float, default=None,
                        metavar="SEC",
                        help="graceful-drain budget on SIGTERM / "
                             "POST /drain (default 30)")
    parser.add_argument("--journal", default=None, metavar="DIR",
                        help="durable request journal directory "
                             "(docs/services.md 'Lossless request "
                             "plane'): every accepted request is "
                             "fsync'd to DIR before dispatch and "
                             "marked terminal on answer; a restart "
                             "replays the unanswered tail, so a "
                             "router SIGKILL loses zero accepted "
                             "requests")
    args = parser.parse_args(argv)
    endpoints = list(args.endpoints)
    if args.endpoints_file:
        from .telemetry.fleet import read_endpoints
        try:
            endpoints += read_endpoints(args.endpoints_file)
        except (OSError, ValueError) as e:
            print("route: bad --endpoints-file: %s" % e,
                  file=sys.stderr)
            return 1
    if not endpoints:
        parser.error("no replica endpoints (positional URLs and/or "
                     "--endpoints-file)")
    from .serving.router import FleetRouter
    router = FleetRouter(
        endpoints, port=args.port, path=args.path,
        probe_interval=args.probe_interval,
        failure_threshold=args.failure_threshold,
        retry_budget=args.retry_budget,
        attempt_timeout=args.attempt_timeout,
        request_timeout=args.request_timeout,
        journal_dir=args.journal).start()
    print("ROUTING port=%d replicas=%d" % (router.port,
                                           len(router.replicas)),
          flush=True)                                   # scriptable
    term = threading.Event()
    prev_term = signal.signal(signal.SIGTERM,
                              lambda _s, _f: term.set())
    try:
        while not term.wait(0.2):
            pass
        # SIGTERM: stop admission (/readyz flips to draining), finish
        # in-flight requests, exit 0 — the rolling-restart contract
        router.drain(grace=args.drain_grace)
    except KeyboardInterrupt:
        pass
    finally:
        router.stop()
        signal.signal(signal.SIGTERM, prev_term)
    return 0


def _blackbox_cli(argv) -> int:
    """``veles-tpu blackbox dump|inspect`` — write the current
    process's flight-recorder ring to a black-box file, or summarize
    one written by a crash/watchdog/SIGTERM/NaN-sentinel dump
    (veles_tpu/telemetry/recorder.py)."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="veles_tpu blackbox",
        description="flight-recorder black box "
                    "(docs/observability.md)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    dmp = sub.add_parser("dump", help="dump this process's ring")
    dmp.add_argument("--out", default=None,
                     help="output path (default: blackbox-<ts>.jsonl "
                          "in the snapshot directory)")
    dmp.add_argument("--reason", default="cli dump")
    ins = sub.add_parser(
        "inspect", help="summarize a blackbox-*.jsonl dump")
    ins.add_argument("path")
    ins.add_argument("--tail", type=int, default=10, metavar="N",
                     help="also print the last N events")
    ins.add_argument("--request", default=None, metavar="ID",
                     help="only events tagged with this request_id "
                          "or trace_id — cross-reference a crashed "
                          "replica's black box against a fleet "
                          "trace (`trace fleet --request`)")
    args = parser.parse_args(argv)
    from .telemetry.recorder import (flight, inspect, matches_request,
                                     read_blackbox)
    if args.cmd == "dump":
        try:
            path = flight.dump(args.reason, path=args.out)
        except OSError as e:
            print("blackbox dump failed: %s" % e, file=sys.stderr)
            return 1
        print("black box -> %s (%d events)"
              % (path, flight.stats()["buffered"]))
        return 0
    try:
        summary = inspect(args.path, request=args.request)
    except OSError as e:
        print("blackbox inspect failed: %s" % e, file=sys.stderr)
        return 1
    print("black box %s" % summary["path"])
    print("  reason:  %s" % summary["reason"])
    print("  pid:     %s" % summary["pid"])
    if args.request:
        print("  request: %s (%d of %d events)"
              % (args.request, summary["events"],
                 summary["events_total"]))
    print("  events:  %d over %.3fs"
          % (summary["events"], summary["span_seconds"]))
    for kind, count in sorted(summary["by_kind"].items(),
                              key=lambda kv: -kv[1]):
        print("  %-12s %d" % (kind, count))
    if args.tail > 0:
        _, events = read_blackbox(args.path)
        if args.request:
            events = [e for e in events
                      if matches_request(e, args.request)]
        for rec in events[-args.tail:]:
            label = rec.get("name") or rec.get("counter") or ""
            extra = ""
            if rec.get("request_id"):
                extra = " %s attempt=%s %s" % (
                    rec.get("request_id"), rec.get("attempt", "?"),
                    rec.get("phase") or rec.get("outcome") or "")
            print("  tail: %-10s %s%s" % (rec.get("kind", "?"),
                                          label, extra))
    return 0


def _quantize_cli(argv) -> int:
    """``veles-tpu quantize SNAPSHOT`` — offline int8 weight
    quantization of a snapshot (veles_tpu/quant/): eligible 2-D matmul
    weights become per-channel symmetric int8 with scale sidecars,
    shrinking their bytes ~4x (whole-file ratio depends on the
    float-kept share: embeddings, optimizer state). The output is an
    ordinary snapshot —
    ``load_snapshot`` dequantizes on read, so --snapshot/resume and
    serving work unchanged anywhere."""
    import argparse
    import os
    import pickle
    import time
    parser = argparse.ArgumentParser(
        prog="veles_tpu quantize",
        description="int8 snapshot quantization "
                    "(docs/services.md 'Quantized serving')")
    parser.add_argument("snapshot", help="snapshot file to quantize")
    parser.add_argument("--out", default=None,
                        help="output path (default: insert .int8 "
                             "before the .pickle extension)")
    parser.add_argument("--granularity", default=None,
                        choices=("per_channel", "per_tensor"),
                        help="scale granularity (default: "
                             "root.common.quant.granularity)")
    args = parser.parse_args(argv)
    from .error import VelesError
    from .quant import quantize_state
    from .resilience import checkpoint_chain as chain_mod
    from .snapshotter import CODECS, load_snapshot
    out = args.out
    if out is None:
        base = args.snapshot
        marker = ".pickle"
        if marker not in base:
            parser.error("cannot derive --out from %r (no .pickle "
                         "extension); pass --out" % base)
        idx = base.rindex(marker)
        out = base[:idx] + ".int8" + base[idx:]
    try:
        state = load_snapshot(args.snapshot)
        qstate, report = quantize_state(state,
                                        granularity=args.granularity)
    except (OSError, VelesError) as e:
        print("quantize failed: %s" % e, file=sys.stderr)
        return 1
    opener = open
    for _codec, (op, ext) in CODECS.items():
        if ext and out.endswith(ext):
            opener = op
            break
    tmp = out + ".tmp"
    with opener(tmp, "wb") as fout:
        pickle.dump(qstate, fout, protocol=pickle.HIGHEST_PROTOCOL)
    digest = chain_mod.file_sha256(tmp)
    chain_mod.commit_file(tmp, out)
    chain_mod.write_manifest(
        out, sha256=digest, prefix="quantize", runs=0,
        created=time.time(),
        checksum=qstate.get("__meta__", {}).get("checksum", ""))
    out_size = os.path.getsize(out)
    try:
        in_size = os.path.getsize(args.snapshot)
    except OSError:
        # non-file sources load_snapshot accepts (sqlite://...) have
        # no size to compare; the quantized output is still reported
        in_size = None
    if in_size is None:
        print("quantized %d tensor(s) (%s): %s -> %s (%.1f KiB)"
              % (report["params"],
                 qstate["__meta__"]["quant"]["granularity"],
                 args.snapshot, out, out_size / 1024))
    else:
        print("quantized %d tensor(s) (%s): %s (%.1f KiB) -> %s (%.1f "
              "KiB, %.2fx)"
              % (report["params"],
                 qstate["__meta__"]["quant"]["granularity"],
                 args.snapshot, in_size / 1024, out, out_size / 1024,
                 in_size / max(1, out_size)))
    return 0


def _export_cli(argv) -> int:
    """``veles-tpu export serve-artifact MODEL.py --out DIR`` — build
    the model (optionally restore a snapshot) and serialize the
    continuous engine's per-bucket prefill programs plus its one
    fixed-shape decode step via ``jax.export`` into a package
    directory (export/serve_artifact.py). Serve it with
    ``--serve-artifact DIR``: startup then performs zero jit
    traces/compiles."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="veles_tpu export",
        description="AOT inference-artifact export "
                    "(docs/services.md 'AOT serving artifacts')")
    sub = parser.add_subparsers(dest="cmd", required=True)
    exp = sub.add_parser(
        "serve-artifact",
        help="pre-export the serving engine's decode programs")
    exp.add_argument("model", help="workflow .py (build_workflow())")
    exp.add_argument("--out", required=True,
                     help="artifact package directory to write")
    exp.add_argument("--snapshot", default=None,
                     help="restore this snapshot before exporting")
    exp.add_argument("-b", "--backend", default=None,
                     help="auto | tpu | cpu (the artifact is lowered "
                          "for this platform)")
    exp.add_argument("--serve-slots", type=int, default=None)
    exp.add_argument("--serve-buckets", default=None,
                     metavar="L1,L2,...")
    exp.add_argument("--serve-max-context", type=int, default=None)
    exp.add_argument("--serve-decode-block", type=int, default=None)
    exp.add_argument("--serve-page-size", type=int, default=None)
    exp.add_argument("--serve-pages", type=int, default=None)
    exp.add_argument("--quant-weights", action="store_true")
    exp.add_argument("--quant-kv", action="store_true")
    args = parser.parse_args(argv)
    if args.backend in ("cpu", "numpy"):
        import jax
        jax.config.update("jax_platforms", "cpu")
    if args.quant_weights:
        root.common.quant.weights = True
    if args.quant_kv:
        root.common.quant.kv = True
    from . import Device_for
    from .export.serve_artifact import export_serve_artifact
    module = import_file_as_module(args.model)
    if not hasattr(module, "build_workflow"):
        raise VelesError("%s defines no build_workflow()" % args.model)
    workflow = module.build_workflow()
    workflow.initialize(device=Device_for(args.backend or "auto"))
    if args.snapshot:
        from .snapshotter import resume as snap_resume
        snap_resume(workflow, args.snapshot)
    path = export_serve_artifact(
        workflow, args.out, max_slots=args.serve_slots,
        buckets=args.serve_buckets,
        max_context=args.serve_max_context,
        decode_block=args.serve_decode_block,
        page_size=args.serve_page_size, pages=args.serve_pages)
    import json as _json
    import os as _os
    with open(_os.path.join(path, "contents.json")) as fin:
        serving = _json.load(fin)["serving"]
    print("serve-artifact -> %s (%d programs: %s; serve with "
          "--serve-artifact %s)"
          % (path, len(serving["programs"]),
             ", ".join(sorted(serving["programs"])), path))
    return 0


def _materialize(args) -> None:
    """Collapse Range/Tuneable markers to defaults — any run that is not
    itself the optimizer must still work with an optimize-ready config."""
    from .genetics.config import materialize_defaults
    n = materialize_defaults(root)
    if n:
        logging.getLogger("veles_tpu").info(
            "collapsed %d Range marker(s) to defaults (no --optimize)", n)


def _run_meta(launcher: Launcher, module, args) -> int:
    """--optimize / --ensemble-train / --ensemble-test modes
    (reference: veles/__main__.py:334-361,724-732)."""
    if not hasattr(module, "build_workflow"):
        raise VelesError("meta-learning modes need build_workflow() in %s"
                         % args.model)
    # subprocess candidates need the (exclusive) TPU for themselves —
    # the parent must not initialize a device it will never use
    subprocess_candidates = (
        (args.optimize and (args.optimize_subprocess
                            or args.optimize_workers > 1))
        or (args.ensemble_train and args.ensemble_workers > 1
            and args.ensemble_member is None))
    device = None if subprocess_candidates else launcher.make_device()
    placement = None
    if args.trial_devices:
        # each worker slot trains on its own disjoint chip slice; on a
        # CPU host the package init materializes the slice width as
        # virtual devices, so the same flag is CI-testable
        placement_used = (
            (args.optimize and args.optimize_workers > 1)
            or (args.ensemble_train and args.ensemble_workers > 1
                and args.ensemble_member is None))
        if not placement_used:
            raise VelesError(
                "--trial-devices places WORKER-POOL trials on chip "
                "slices; it needs --optimize-workers or "
                "--ensemble-workers > 1 (serial/inline candidates run "
                "on the parent's device set)")
        from .parallel.trials import mesh_slice_placement
        placement = mesh_slice_placement(
            devices_per_trial=args.trial_devices)
    if args.optimize:
        from .genetics import GeneticsOptimizer
        size, _, gens = args.optimize.partition(":")
        extra = []               # forwarded to subprocess candidates
        if args.config:
            extra.append(args.config)
        extra += args.config_list     # user's inline overrides still apply
        if args.backend:
            extra += ["--backend", args.backend]
        if args.random_seed is not None:
            extra += ["--random-seed", str(args.random_seed)]
        result = GeneticsOptimizer(
            build_workflow=module.build_workflow, model_path=args.model,
            size=int(size), generations=int(gens or 3),
            device=device, subprocess_mode=args.optimize_subprocess,
            n_workers=args.optimize_workers,
            placement=placement,
            crossover=args.optimize_crossover,
            selection=args.optimize_selection,
            extra_argv=extra).run()
    elif args.ensemble_train:
        _materialize(args)
        from .ensemble import EnsembleTrainer
        n, _, ratio = args.ensemble_train.partition(":")
        if args.ensemble_member is not None:
            # parallel-worker child: train exactly one member; the
            # parent assembles the manifest from the entry we emit
            result = EnsembleTrainer(
                module.build_workflow, n_models=int(n),
                train_ratio=float(ratio or 1.0), device=device,
                base_seed=args.random_seed,
                out_file=args.ensemble_file).train_member(
                    args.ensemble_member)
        else:
            extra = []
            if args.config:
                extra.append(args.config)
            extra += args.config_list
            if args.backend:
                extra += ["--backend", args.backend]
            result = EnsembleTrainer(
                module.build_workflow, n_models=int(n),
                train_ratio=float(ratio or 1.0), device=device,
                base_seed=args.random_seed,
                out_file=args.ensemble_file,
                n_workers=args.ensemble_workers, placement=placement,
                model_path=args.model, extra_argv=extra).run()
    else:
        from .ensemble import EnsembleTester
        _materialize(args)
        result = EnsembleTester(module.build_workflow, args.ensemble_test,
                                device=device).run()
    if args.result_file:
        launcher.write_results(result, args.result_file)
    return 0


def _drive(launcher: Launcher, workflow, args):
    launcher.initialize(workflow)
    if args.snapshot:
        launcher.resume(args.snapshot)
    elif args.snapshot_dir:
        # elastic restart: rerunning the same command after a crash or
        # preemption resumes from the newest snapshot automatically
        # (reference disaster-recovery story, SURVEY.md §5.3)
        launcher.try_restore_latest()   # warns if nothing can WRITE
        # snapshots either (no Snapshotter unit linked)
    if args.workflow_graph:
        with open(args.workflow_graph, "w") as fout:
            fout.write(workflow.generate_graph())
        launcher.info("workflow graph → %s", args.workflow_graph)
        return None
    if args.dry_run:
        launcher.info("dry run: initialize OK (%d units)", len(workflow))
        return None
    if args.serve_generate is not None:
        # serve the (optionally snapshot-restored) model instead of
        # training: the CLI face of GenerationAPI. Validate the stack
        # NOW so a non-LM workflow fails with the split_stack reason,
        # not a 500 on the first request.
        from .nn.sampling import split_stack
        from .restful_api import GenerationAPI
        # (the continuous engine serves a hybrid_block through the
        # block's own programs; the draft's consumer does not)
        split_stack(list(workflow.forwards), hybrid=True)
        draft = None
        if args.serve_draft:
            draft_mod = import_file_as_module(args.serve_draft)
            draft = draft_mod.build_workflow()
            draft.initialize(device=launcher.device)
            if args.serve_draft_snapshot:
                from .snapshotter import resume as snap_resume
                snap_resume(draft, args.serve_draft_snapshot)
            split_stack(list(draft.forwards))
        api = GenerationAPI(workflow, draft=draft,
                            port=args.serve_generate,
                            name="serve_generate")
        api.initialize()
        launcher.info("generation serving on "
                      "http://127.0.0.1:%d/generate — Ctrl-C stops, "
                      "SIGTERM drains gracefully", api.port)
        print("SERVING port=%d" % api.port, flush=True)  # scriptable
        # SIGTERM = the scheduler's eviction notice: stop admission
        # (/readyz flips to draining), finish in-flight tickets within
        # the drain grace, exit 0 — a rolling restart never turns
        # half-served requests into client errors
        import signal
        import threading as _threading
        term = _threading.Event()
        prev_term = signal.signal(signal.SIGTERM,
                                  lambda _s, _f: term.set())
        try:
            while not term.wait(1.0):
                pass
            launcher.info("SIGTERM — draining the serving front")
            api.drain(grace=args.serve_drain_grace)
        except KeyboardInterrupt:
            launcher.info("serving stopped")
        finally:
            api.stop()
            signal.signal(signal.SIGTERM, prev_term)
        return None
    from .resilience import elastic
    results = (launcher.run_elastic() if elastic.enabled()
               else launcher.run())
    if args.timings:
        launcher.print_stats()
    if args.result_file:
        launcher.write_results(results, args.result_file)
    for key, value in sorted(results.items()):
        if not isinstance(value, dict):
            launcher.info("result %s = %s", key, value)
    try:        # peak memory at exit (reference: veles/__main__.py:791-797)
        import resource
        # ru_maxrss units are platform-defined: KiB on Linux, bytes on
        # Darwin
        div = 1024.0 * 1024.0 if sys.platform == "darwin" else 1024.0
        launcher.info("max RSS: %.1f MiB", resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / div)
    except Exception:
        pass
    if launcher.interrupted:
        sys.exit(130)   # Ctrl-C must not look like a completed run
    return results


if __name__ == "__main__":
    sys.exit(main())
