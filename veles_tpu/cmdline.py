"""Command-line surface.

Equivalent of the reference's veles/cmdline.py:61-278 (the veles(1) arg
set) collapsed to one explicit parser — the reference's metaclass-
distributed `init_parser` registry existed to merge flags from dozens of
optional units; here the surface is small enough to state in one place,
and unit-specific knobs ride the config tree (root.x.y=z overrides).
"""

from __future__ import annotations

import argparse


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="veles_tpu",
        description="TPU-native dataflow deep-learning framework "
                    "(rebuild of Samsung VELES capabilities)")
    p.add_argument("model", help="workflow .py file (defines "
                   "build_workflow() or run(load, main))")
    p.add_argument("config", nargs="?", default=None,
                   help="optional config .py/.json applied to root")
    p.add_argument("config_list", nargs="*", default=[],
                   help="inline overrides root.x.y=value")
    p.add_argument("-b", "--backend", default=None,
                   help="auto | tpu | cpu | xla | numpy")
    p.add_argument("--mesh", default=None,
                   help="mesh spec, e.g. data=8 or data=4,tensor=2")
    p.add_argument("-s", "--snapshot", default=None,
                   help="resume from snapshot file")
    p.add_argument("--snapshot-dir", default=None)
    p.add_argument("--random-seed", type=int, default=None)
    p.add_argument("--test", action="store_true",
                   help="run in test (inference) mode")
    p.add_argument("--result-file", default=None,
                   help="write gathered metrics JSON here")
    p.add_argument("--workflow-graph", default=None,
                   help="write the control graph DOT file and exit "
                        "after initialize")
    p.add_argument("--dump-config", action="store_true")
    p.add_argument("--dry-run", action="store_true",
                   help="build + initialize only")
    p.add_argument("--timings", action="store_true",
                   help="print per-unit timing table at exit")
    p.add_argument("--trace-file", default=None,
                   help="append event spans as JSON lines here")
    # model-health observability (veles_tpu/telemetry/tensormon.py +
    # recorder.py, docs/observability.md "Model health")
    p.add_argument("--tensormon", action="store_true",
                   help="in-graph tensor taps on the fused train step "
                        "(grad norms, update ratios, NaN/Inf counts, "
                        "activation saturation) — accumulated on "
                        "device, drained with the epoch metrics, "
                        "served as veles_model_* gauges on /metrics")
    p.add_argument("--nan-policy", default=None,
                   choices=("warn", "halt", "snapshot_and_halt"),
                   help="NaN sentinel policy (implies --tensormon): "
                        "warn logs and counts; halt marks health "
                        "unready and raises ModelHealthError; "
                        "snapshot_and_halt first commits a forensic "
                        "snapshot through the checkpoint chain")
    p.add_argument("--blackbox", action="store_true",
                   help="arm flight-recorder autodump: unhandled "
                        "workflow crashes, watchdog trips and SIGTERM "
                        "write blackbox-<ts>.jsonl next to the "
                        "snapshots (read with `veles-tpu blackbox "
                        "inspect`)")
    p.add_argument("--force-numpy", action="store_true")
    p.add_argument("--mixed-precision", action="store_true",
                   help="bf16 activation/param storage in the fused "
                        "step (f32 masters + accumulation); the HBM "
                        "lever for image-scale nets")
    p.add_argument("-v", "--verbose", action="count", default=0)
    p.add_argument("--debug", default="", metavar="ClassA,ClassB",
                   help="enable DEBUG for specific unit/class loggers "
                        "('all' raises the root logger)")
    # observability services (reference graphics/web-status,
    # veles/graphics_server.py:73, veles/launcher.py:852-885)
    p.add_argument("--graphics", action="store_true",
                   help="live plots: spawn the renderer subprocess")
    p.add_argument("--plots-dir", default=None,
                   help="where the renderer writes plot PNGs")
    p.add_argument("--status-url", default=None,
                   help="web-status server to POST beacons to "
                        "(see python -m veles_tpu.web_status)")
    p.add_argument("--status-interval", type=float, default=10.0,
                   help="beacon period in seconds")
    p.add_argument("--serve-generate", type=int, default=None,
                   metavar="PORT",
                   help="after initialize (and optional --snapshot "
                        "resume), serve the workflow's generation stack "
                        "over HTTP instead of training (GenerationAPI: "
                        "greedy/sample/beam, micro-batched; + "
                        "speculative when --serve-draft is given); "
                        "0 picks an ephemeral port; Ctrl-C stops, "
                        "SIGTERM drains gracefully (/readyz flips to "
                        "draining, in-flight tickets finish, exit 0)")
    p.add_argument("--serve-drain-grace", type=float, default=None,
                   metavar="SEC",
                   help="graceful-drain budget for SIGTERM / POST "
                        "/generate/drain: seconds to wait for "
                        "in-flight requests before aborting the "
                        "stragglers 503 "
                        "(root.common.serving.drain_grace, default "
                        "30)")
    p.add_argument("--serve-drain-handoff", default=None,
                   choices=("on", "off"),
                   help="drain-by-handoff (default on): a draining "
                        "replica settles each in-flight ticket 503 + "
                        "its emitted-token resume progress at the "
                        "next step boundary — drain latency is one "
                        "handoff, not the longest generation; 'off' "
                        "restores the wait-out-the-grace drain "
                        "(root.common.serving.drain_handoff)")
    p.add_argument("--serve-engine", default=None,
                   choices=("continuous", "recurrent", "window"),
                   help="decode plane under --serve-generate: "
                        "'continuous' (default) runs the slot-pool "
                        "continuous-batching engine (greedy/sample "
                        "requests share one fixed-shape decode step, "
                        "admitted/retired per iteration; recurrent "
                        "LM stacks auto-route to the O(1)-state "
                        "pool); 'recurrent' pins the O(1)-state pool "
                        "(fixed per-slot state, pageless admission); "
                        "'window' keeps the legacy shape-keyed "
                        "micro-batcher")
    p.add_argument("--serve-slots", type=int, default=None, metavar="N",
                   help="KV-cache slot rows of the continuous-batching "
                        "pool (root.common.serving.max_slots)")
    p.add_argument("--serve-buckets", default=None, metavar="L1,L2,...",
                   help="prefill pad-to lengths; the serving jit cache "
                        "is bounded by len(buckets) programs and "
                        "the decode step's 1 or 2 view lengths "
                        "(root.common.serving.buckets)")
    p.add_argument("--serve-max-context", type=int, default=None,
                   metavar="T",
                   help="per-slot KV capacity; requests need "
                        "len(prompt)+n_new <= T to ride the slot pool "
                        "(root.common.serving.max_context)")
    p.add_argument("--serve-page-size", type=int, default=None,
                   metavar="P",
                   help="positions per KV-cache page (a multiple of "
                        "the decode block); pool HBM is pages x P, "
                        "not slots x max-context "
                        "(root.common.serving.page_size)")
    p.add_argument("--serve-pages", type=int, default=None, metavar="N",
                   help="usable pages of the paged KV pool; default "
                        "is dense-equivalent capacity (every slot can "
                        "hold max-context) — SHRINK it to trade worst-"
                        "case context reservation for more concurrent "
                        "slots at the same HBM "
                        "(root.common.serving.pages)")
    p.add_argument("--serve-spec-gamma", type=int, default=None,
                   metavar="G",
                   help="draft tokens per on-device speculation round; "
                        "the pool serves mode=speculative requests "
                        "whose gamma matches this fixed shape "
                        "(root.common.serving.spec_gamma)")
    p.add_argument("--serve-beam-width", type=int, default=None,
                   metavar="W",
                   help="hypothesis rows per pooled beam request; the "
                        "pool serves mode=beam requests whose width "
                        "matches this fixed shape "
                        "(root.common.serving.beam_width)")
    p.add_argument("--serve-prefix-cache", default=None,
                   choices=("on", "off"),
                   help="prefix-sharing paged KV cache: a radix index "
                        "over page-size token blocks lets admissions "
                        "adopt a shared prompt prefix's pages "
                        "read-only and prefill only the suffix "
                        "(root.common.serving.prefix_cache; "
                        "greedy/sample on the float pool; answers "
                        "bit-identical on or off)")
    p.add_argument("--serve-prefill-chunk", type=int, default=None,
                   metavar="C",
                   help="prefill admissions in C-token chunks "
                        "co-scheduled with the decode tick instead of "
                        "one monolithic bucketed pass — bounds the "
                        "per-tick decode stall a long admission "
                        "causes (root.common.serving.prefill_chunk; "
                        "0 = monolithic)")
    p.add_argument("--serve-tp", type=int, default=None, metavar="N",
                   help="tensor-parallel serving over a 1D (\"model\",)"
                        " mesh slice: N chips serve as ONE logical "
                        "replica — attention heads and K/V pages shard "
                        "over the head axis, FC/embedding weights "
                        "column/row-parallel, while page tables and "
                        "the prefix cache stay replicated host data "
                        "(root.common.serving.tp; 1 = solo; answers "
                        "id-exact vs the unsharded engine; float "
                        "plane only)")
    p.add_argument("--serve-state-cache", default=None,
                   choices=("on", "off"),
                   help="state-checkpoint prefix cache of the O(1)-"
                        "state lane: prefill snapshots the recurrent "
                        "state every page-size tokens into a radix "
                        "index; a same-prefix admission adopts the "
                        "deepest snapshot copy-on-write and scans "
                        "only the suffix "
                        "(root.common.serving.state_cache; answers "
                        "bit-identical on or off)")
    p.add_argument("--serve-stream", default=None,
                   choices=("on", "off"),
                   help="honor stream=true requests with SSE "
                        "token-streaming responses (default on; "
                        "root.common.serving.stream — off answers "
                        "them buffered)")
    p.add_argument("--serve-qos", default=None,
                   choices=("on", "off"),
                   help="QoS classes on the serving plane (default "
                        "off; root.common.serving.qos): requests "
                        "carry priority=interactive|batch, admission "
                        "promotes interactive past queued batch, and "
                        "under slot pressure the engine preempts "
                        "batch rows at a step boundary — they requeue "
                        "with resume progress and finish bit-"
                        "identical (docs/services.md 'Overload & "
                        "QoS')")
    p.add_argument("--router-qos", default=None,
                   choices=("on", "off"),
                   help="adaptive admission at the fleet router "
                        "(default off; root.common.router.qos): AIMD "
                        "controller keyed on the TTFT p99 vs "
                        "--router-slo-ttft-ms throttles batch first, "
                        "a retry token bucket caps failover "
                        "amplification, and a hysteresis-guarded "
                        "brownout ladder degrades before shedding")
    p.add_argument("--router-slo-ttft-ms", type=float, default=None,
                   metavar="MS",
                   help="TTFT p99 SLO the router's AIMD controller "
                        "defends (root.common.router.slo_ttft_ms, "
                        "default 500)")
    p.add_argument("--serve-artifact", default=None, metavar="DIR",
                   help="AOT serve-artifact package (from `veles-tpu "
                        "export serve-artifact`): the continuous "
                        "engine loads its pre-exported prefill/decode "
                        "programs at initialize — zero jit compiles "
                        "on the serving path "
                        "(root.common.serving.artifact); a corrupt or "
                        "mismatched artifact falls back to live jit "
                        "with a counted warning")
    # quantization subsystem (veles_tpu/quant/, docs/services.md
    # "Quantized serving")
    p.add_argument("--quant-weights", action="store_true",
                   help="serve with per-channel symmetric int8 decode "
                        "matmul weights, dequantized on read inside "
                        "the serving programs "
                        "(root.common.quant.weights)")
    p.add_argument("--quant-kv", action="store_true",
                   help="store the serving KV-cache slot pool int8 "
                        "with per-slot scales — half the pool HBM at "
                        "the same --serve-slots "
                        "(root.common.quant.kv)")
    p.add_argument("--serve-draft", default=None, metavar="MODEL_PY",
                   help="draft model .py for mode=speculative under "
                        "--serve-generate (its build_workflow() is "
                        "initialized on the same backend)")
    p.add_argument("--serve-draft-snapshot", default=None,
                   help="snapshot to restore the --serve-draft model "
                        "from before serving")
    # multi-host (replaces master/slave -l/-m, veles/launcher.py:193-267)
    p.add_argument("--coordinator", default=None,
                   help="host:port of the jax distributed coordinator")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--slave-death-probability", type=float, default=0.0,
                   help="fault injection for recovery testing")
    p.add_argument("--elastic", action="store_true",
                   help="preemption-tolerant training: on detected "
                        "host loss (heartbeat lapse, join failure, or "
                        "an injected distributed.host_loss fault) the "
                        "run declares a new generation and resumes "
                        "from the newest valid checkpoint instead of "
                        "dying; multi-process survivors exit 43 for "
                        "the respawn plane "
                        "(root.common.resilience.elastic.{enabled,"
                        "min_hosts,generation_timeout,"
                        "max_generations}; docs/resilience.md "
                        "'Elastic training')")
    # overlap engine (veles_tpu/overlap/, docs/overlap.md)
    p.add_argument("--overlap", action="store_true",
                   help="overlap host I/O with device compute: "
                        "side-effect units (plotters/publishers/image "
                        "savers) run on an async side-plane, "
                        "snapshots commit+fsync on a checkpoint lane, "
                        "loaders prefetch the next batch. Results are "
                        "bit-identical with or without it")
    p.add_argument("--prefetch-depth", type=int, default=None,
                   metavar="N",
                   help="stage up to N minibatches ahead on a "
                        "background thread (loader data plane; "
                        "implies nothing about --overlap — the two "
                        "compose)")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax/XPlane profiler trace of the run "
                        "into this directory (read it with `veles_tpu "
                        "trace self-time DIR`, tensorboard or xprof); "
                        "under --serve-generate it arms POST "
                        "/generate/profile {\"seconds\": N} instead, "
                        "which captures a running server into a fresh "
                        "subdirectory")
    p.add_argument("--job-timeout", type=float, default=0.0,
                   help="floor (seconds) for the per-dispatch hang "
                        "watchdog; 0 keeps only the mean+3σ adaptive "
                        "threshold (reference: veles/server.py:619-635)")
    # meta-learning (reference --optimize / --ensemble-train/-test,
    # veles/__main__.py:334-361,724-732)
    p.add_argument("--optimize", default=None, metavar="SIZE[:GENS]",
                   help="GA hyper-parameter search over Range() markers "
                        "in the config tree")
    p.add_argument("--optimize-subprocess", action="store_true",
                   help="evaluate each candidate in an isolated "
                        "subprocess instead of inline")
    p.add_argument("--optimize-workers", type=int, default=1, metavar="W",
                   help="evaluate up to W candidates concurrently via "
                        "the trial scheduler (implies subprocess "
                        "isolation; each worker slot gets its own "
                        "device placement)")
    p.add_argument("--trial-devices", type=int, default=0, metavar="D",
                   help="place each --optimize/--ensemble worker trial "
                        "on its own disjoint D-chip slice "
                        "(mesh_slice_placement via TPU_VISIBLE_CHIPS); "
                        "0 = private single CPU device per slot")
    p.add_argument("--optimize-crossover", default="uniform",
                   choices=("uniform", "arithmetic", "geometric",
                            "pointed"),
                   help="GA crossover operator")
    p.add_argument("--optimize-selection", default="roulette",
                   choices=("roulette", "random", "tournament"),
                   help="GA parent-selection procedure")
    p.add_argument("--ensemble-train", default=None, metavar="N[:RATIO]",
                   help="train N ensemble members, each on RATIO of the "
                        "train set (default 1.0)")
    p.add_argument("--ensemble-test", default=None, metavar="MANIFEST",
                   help="soft-vote evaluate a trained ensemble manifest")
    p.add_argument("--ensemble-file", default="ensemble.json",
                   help="where --ensemble-train writes its manifest")
    p.add_argument("--ensemble-workers", type=int, default=1, metavar="W",
                   help="train up to W ensemble members concurrently via "
                        "the trial scheduler (members become CLI "
                        "subprocesses)")
    p.add_argument("--ensemble-member", type=int, default=None,
                   metavar="I",
                   help="(internal) train only member I of the "
                        "--ensemble-train set and write its manifest "
                        "entry to --result-file — the unit a parallel "
                        "ensemble worker executes")
    return p


def parse_args(parser: argparse.ArgumentParser, argv):
    """Parse accepting SPLIT positional groups: real invocations (and
    the child commands the trial scheduler builds) routinely interleave
    ``root.x.y=value`` overrides with optionals —
    ``model.py --optimize 3:1 root.lr=0.1 --backend cpu`` — which
    plain ``parse_args`` rejects ("unrecognized arguments"): argparse
    commits the whole positional pattern to the FIRST positional run
    it meets. ``parse_intermixed_args`` (two-pass: optionals first,
    then the collected positionals as one run) accepts them; the
    fallback covers parser shapes intermixed parsing refuses (it
    forbids some nargs forms), where the classic behavior is kept."""
    try:
        return parser.parse_intermixed_args(argv)
    except TypeError:
        return parser.parse_args(argv)


def split_child_argv(extra):
    """Partition forwarded argv into (positional config overrides,
    flag arguments). Child commands built for the trial scheduler must
    group ALL positionals (``root.x=y`` overrides, config files)
    directly after the model path — argparse cannot consume a second
    positional group appearing after optionals like ``--backend cpu``.
    """
    positionals, flags = [], []
    it = iter(extra)
    for item in it:
        if item.startswith("-"):
            flags.append(item)
            # flags used by forwarded child argv are all value-taking
            # (--backend X, --random-seed N); keep the pair together
            if "=" not in item:
                try:
                    flags.append(next(it))
                except StopIteration:
                    pass
        else:
            positionals.append(item)
    return positionals, flags


def parse_mesh(spec: str):
    """'data=4,tensor=2' → {'data': 4, 'tensor': 2}."""
    out = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        out[name.strip()] = int(size)
    return out


def apply_config_overrides(root, items):
    """Inline ``root.x.y=value`` overrides (reference --config-list,
    veles/__main__.py:474-481)."""
    import json
    for item in items:
        path, _, value = item.partition("=")
        if not _:
            raise ValueError("override %r is not of form root.x.y=value"
                             % item)
        parts = path.split(".")
        if parts[0] == "root":
            parts = parts[1:]
        node = root
        for part in parts[:-1]:
            node = getattr(node, part)
        try:
            value = json.loads(value)
        except ValueError:
            pass
        setattr(node, parts[-1], value)
