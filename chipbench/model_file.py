"""The model file the CLI loads inside the process that holds the chip.

``chipbench/run.py`` (no jax) starts ``python -m veles_tpu
chipbench/model_file.py --backend tpu ...`` and this file's ``run(load,
main)`` is the CLI's model protocol: it builds the cell's workflow from
its configuration file, lets ``main()`` drive it as for any model, and
around that does what only this process can: make the weights on the
device from ``--seed``, open and close the measured window, trace it,
read the device's memory, and, once the window has closed and the
program's state is freed, run the plain reference and compare.

Everything it reports goes to the parent as one ``CHIPBENCH_CHILD {json}``
line; earlier lines are for a reader.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, modules, reduce  # noqa: E402

SPEC_ENV = "CHIPBENCH_SPEC"
CHILD_TAG = "CHIPBENCH_CHILD "
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_STEPS = 3


def say(msg):
    print("chipbench: " + msg, flush=True)


class Probe:
    """What only this process sees: compilations and device memory."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.window_open = False
        self.compiles_in_window = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.compile_s += secs
            self.compiles += 1
            if self.window_open:
                self.compiles_in_window += 1

    @staticmethod
    def device():
        import jax
        d = jax.devices()
        return {"platform": d[0].platform, "kind": d[0].device_kind,
                "count": len(d)}

    @staticmethod
    def memory():
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return {"peak_bytes_in_use": max(
                    s.get("peak_bytes_in_use", 0) for s in stats),
                "bytes_in_use": max(s.get("bytes_in_use", 0) for s in stats),
                "bytes_limit": max(s.get("bytes_limit", 0) for s in stats)}


def skip_host_draw():
    """The program draws every weight on one core in float64
    (prng.RandomGenerator.fill_normal): minutes at these sizes, in every
    run. The benchmark hands over weights made on the device, so in this
    process the host draw is skipped and the host mirrors stay zero."""
    from veles_tpu import prng
    prng.RandomGenerator.fill_normal = lambda self, arr, scale: None


def build_workflow(cfg, wl, seed):
    """StandardWorkflow over the cell's configuration; the loader holds
    ``rows_per_epoch`` sequences drawn from the seed."""
    import numpy
    from veles_tpu import nn
    from veles_tpu.loader import FullBatchLoaderMSE
    reference = modules.reference_of(cfg, training=True)

    class TokenLoader(FullBatchLoaderMSE):
        hide_from_registry = True

        def load_data(self):
            rows = reference.make_tokens(seed, wl["rows_per_epoch"],
                                         wl["seq_len"], cfg["vocab_size"])
            self.create_originals(
                numpy.ascontiguousarray(rows[:, :-1]), None,
                targets=numpy.ascontiguousarray(rows[:, 1:]))
            self.class_lengths = [0, 0, len(rows)]

    loader = TokenLoader(None, minibatch_size=wl["minibatch"], name="tokens")
    return nn.StandardWorkflow(
        name="chipbench",
        layers=reference.layer_list(cfg, wl.get("learning_rate")),
        loader_unit=loader, loss_function="softmax_seq",
        decision_config=dict(max_epochs=10 ** 9, fail_iterations=10 ** 9),
        steps_per_dispatch=wl.get("steps_per_dispatch", 1))


def place_like(new, old):
    """``new`` placed as the program placed ``old``: same tree, shapes,
    dtypes and shardings, or an error."""
    import jax
    if (jax.tree_util.tree_structure(new)
            != jax.tree_util.tree_structure(old)):
        raise ValueError("the benchmark's weights and the program's "
                         "parameters are different trees")

    def put(n, o):
        if n.shape != o.shape or n.dtype != o.dtype:
            raise ValueError("weight %s %s against the program's %s %s"
                             % (n.shape, n.dtype, o.shape, o.dtype))
        return jax.device_put(n, o.sharding)
    return jax.tree_util.tree_map(put, new, old)


def metrics_series():
    """{series: value} of what the program's ``/metrics`` would print now:
    every counter, and a histogram as its ``_sum`` and ``_count``. A serving
    cell's parent scrapes the page; a training cell has no server, so this
    process reads the two registries the page is rendered from. Two
    dictionary copies on the host, no device sync."""
    from veles_tpu.telemetry.counters import counters, histograms
    out = {k: float(v) for k, v in counters.snapshot().items()}
    for name, h in histograms.snapshot().items():
        out[name + "_sum"] = float(h["sum"])
        out[name + "_count"] = float(h["count"])
    return out


class TrainWindow:
    """Drives nothing itself: it sits around ``TrainStep.xla_run`` while
    the workflow's own loop (loader, step, decision) runs. The first
    ``CHECK_STEPS`` dispatches are set-up and give the numbers that are
    compared; then the window opens, and closes at the first step boundary
    past ``seconds``. The compiled step and its state are one object from
    the first dispatch to the last. With ``--trace 1`` the profiler is on
    for a slice of ``trace_seconds`` in the window's middle. The window's
    and the slice's ``counters`` are the rise of the program's ``/metrics``
    series between their two ends, as a serving slice's are."""

    def __init__(self, spec, wf, probe):
        self.spec, self.wf, self.probe = spec, wf, probe
        self.cfg, self.wl = spec["config"], spec["workload"]
        self.reference = modules.reference_of(self.cfg, training=True)
        self.n = 0
        self.closed = self.opened = self.warm = self.epoch_ended = False
        self.fed, self.losses = [], []
        self.grad1 = self.delta = None
        self.steps = 0
        self.result = {}
        self.slice = None
        self.stamps = []

    def attach(self):
        step = self.wf.train_step
        if self.wl.get("steps_per_dispatch", 1) != 1:
            raise ValueError("the check reads each of the first steps: "
                             "steps_per_dispatch must be 1")
        say("workflow initialized %.1f s after the start"
            % (time.time() - self.spec["t_start"]))
        t = time.perf_counter()
        step.params = place_like(
            self.reference.make_weights(self.cfg, self.spec["seed"]),
            step.params)
        say("weights made on the device in %.2f s (dispatched, not waited "
            "for)" % (time.perf_counter() - t))
        self._run = step.xla_run
        step.xla_run = self.hooked

    def hooked(self):
        import jax
        if self.closed:
            return
        step, loader = self.wf.train_step, self.wf.loader
        self.n += 1
        if self.warm and not self.opened:
            self.open()
        if self.opened:
            now = time.perf_counter()
            if self.slice_at is not None and now >= self.slice_at:
                self.slice_open()
            self._run()
            self.steps += 1
            now = time.perf_counter()
            self.stamps.append(now - self.t0)
            if self.slice and "window_s" not in self.slice \
                    and now >= self.slice["t0"] + self.slice_s:
                self.slice_close()
            if now >= self.deadline:
                self.close()
            return
        # set-up: the first steps are the ones compared; then the first
        # epoch runs out and one step of the second is taken, so that every
        # variant of the step that the loop compiles (the first call, a
        # call on its own outputs, the first call after an epoch's drain)
        # has compiled before the window opens
        checked = self.n <= CHECK_STEPS
        if checked:
            self.fed.append(loader.minibatch_indices.map_read()
                            [:loader.minibatch_size].copy().tolist())
        self._run()
        if checked:
            self.losses.append(float(step.last_loss))
            say("step %d done %.1f s after the start"
                % (self.n, time.time() - self.spec["t_start"]))
        if self.n == 1:
            self.grad1 = self._grad_norms()
        if self.n == CHECK_STEPS:
            self.delta = check.floats(jax.device_get(
                self.reference.delta_norms(step.params, self.cfg,
                                           self.spec["seed"])))
        if bool(loader.epoch_ended):
            self.epoch_ended = True
        elif self.epoch_ended:
            self.warm = True

    def _grad_norms(self):
        """The first gradient as the optimizer got it, from Adam's first
        moment after one step: m1 = (1 - beta1) * g1."""
        import jax
        step = self.wf.train_step
        norms = jax.device_get(self.reference.leaf_norms(
            {u: s["m"] for u, s in step.opt_state.items()}))
        return {u: {k: float(x) / (1.0 - step._gd_for[u].beta1)
                    for k, x in leaves.items()}
                for u, leaves in norms.items()}

    def open(self):
        import jax
        jax.block_until_ready(self.wf.train_step.params)
        seconds = self.spec["seconds"]
        # with --trace 1 the window is as long as without; the profiler is
        # on for a slice of ``trace_seconds`` in its middle, and the
        # per-layer metrics are of that slice
        self.slice_s = min(seconds, self.wl.get("trace_seconds", seconds))
        self.probe.window_open = self.opened = True
        self.result["setup_s"] = time.time() - self.spec["t_start"]
        self.series0 = metrics_series()
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds
        self.slice_at = (self.t0 + (seconds - self.slice_s) / 2.0
                         if self.spec["trace"] else None)

    def slice_open(self):
        import jax
        jax.block_until_ready(self.wf.train_step.params)
        jax.profiler.start_trace(self.spec["trace_dir"])
        self.slice_at = None
        self.slice = {"series0": metrics_series(),
                      "t0": time.perf_counter(), "steps": -self.steps,
                      "from_s": time.perf_counter() - self.t0}

    def slice_close(self):
        import jax
        jax.block_until_ready(self.wf.train_step.params)
        t1 = time.perf_counter()
        series = metrics_series()
        jax.profiler.stop_trace()
        self.slice["counters"] = reduce.counters_rise(
            self.slice.pop("series0"), series)
        self.slice["window_s"] = t1 - self.slice["t0"]
        self.slice["steps"] += self.steps
        self.slice["tokens"] = (self.slice["steps"] * self.wl["minibatch"]
                                * self.wl["seq_len"])

    def close(self):
        import jax
        step = self.wf.train_step
        jax.block_until_ready(step.params)
        t1 = time.perf_counter()
        series = metrics_series()
        self.probe.window_open = False
        if self.slice and "window_s" not in self.slice:
            self.slice_close()
        self.closed = True
        self.result.update(
            counters=reduce.counters_rise(self.series0, series),
            window_s=t1 - self.t0, steps=self.steps,
            tokens=self.steps * self.wl["minibatch"] * self.wl["seq_len"],
            memory=self.probe.memory())
        # where a stall was, if there was one: the host returns from a
        # dispatch at once and waits only when an epoch drains
        gaps = sorted(((b - a, i + 1) for i, (a, b) in enumerate(
            zip([0.0] + self.stamps, self.stamps))), reverse=True)[:3]
        say("the longest waits of the host in the window: %s" % ", ".join(
            "%.3f s in step %d" % g for g in gaps))
        # the program's state goes before the reference comes; the
        # workflow's own loop then runs out its epoch on empty steps
        step.params, step.opt_state = {}, {}
        self.wf.decision.max_epochs = 0

    def finish(self):
        """After ``main()`` has returned: the reference, then the report."""
        reference = self.reference
        if not self.closed:
            raise RuntimeError("the workflow ended before the window closed")
        out = dict(self.result)
        if self.slice:
            planes = reduce.load_xplane(self.spec["trace_dir"])
            out["trace"] = reduce.reduce_trace(planes, self.slice["window_s"])
            out["slice"] = {k: self.slice[k] for k in (
                "from_s", "window_s", "steps", "tokens", "counters")}
            keep_sample(self.spec, planes)
        rows = reference.make_tokens(
            self.spec["seed"], self.wl["rows_per_epoch"], self.wl["seq_len"],
            self.cfg["vocab_size"])
        batches = [rows[idx] for idx in self.fed]
        say("the program's state is freed: %d bytes in use before the "
            "reference" % self.probe.memory()["bytes_in_use"])
        program = {"loss": self.losses, "grad1": self.grad1,
                   "delta": self.delta}
        t = time.perf_counter()
        ref = reference.train_reference(
            self.cfg, self.spec["seed"], batches, self.wl["learning_rate"])
        out["reference_s"] = time.perf_counter() - t
        out["correct"], out["checks"] = check.compare_train(
            program, ref, self.wl["limits"])
        out["attempted"], out["failed"] = self.steps, 0
        out["program"] = {"loss": self.losses}
        out["reference"] = {"loss": ref["loss"]}
        if self.spec.get("study"):
            out["study"] = self.study(batches, ref)
        return out

    def study(self, batches, ref):
        """Not part of a benchmark run: the control (the reference in the
        precision below) and the planted faults, read against the same
        reference, for setting the limits."""
        reference = self.reference
        lr, seed = self.wl["learning_rate"], self.spec["seed"]
        readings = {}
        ctl = reference.train_reference(
            self.cfg, seed, batches, lr, quant=self.wl["control_precision"])
        readings["control"] = check.compare_train(
            ctl, ref, self.wl["limits"])[1]
        fault = reference.train_reference(self.cfg, seed, batches, lr,
                                          keep_share=0.5)
        readings["half_batch"] = check.compare_train(
            fault, ref, self.wl["limits"])[1]
        return readings


def keep_sample(spec, planes, span_ns=1_700_000_000):
    """With ``--keep``: the start of the trace in the plain form, for the
    recorded sample under ``chipbench/testdata``."""
    if not spec.get("keep"):
        return
    starts = [e[1] for p in planes for ln in p["lines"] for e in ln["events"]]
    if not starts:
        return
    end = min(starts) + span_ns
    sample = [{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [e for e in ln["events"] if e[1] + e[2] <= end]}
        for ln in p["lines"]]} for p in planes]
    os.makedirs(spec["keep"], exist_ok=True)
    path = os.path.join(spec["keep"], "trace_sample.%s.json"
                        % spec["workload"]["name"])
    with open(path, "w") as f:
        json.dump(sample, f)


def train_cell(spec, load, main, probe):
    skip_host_draw()
    wf, _ = load(build_workflow, cfg=spec["config"], wl=spec["workload"],
                 seed=spec["seed"])
    window = TrainWindow(spec, wf, probe)
    initialize = wf.initialize

    def initialize_then_attach(**kwargs):
        out = initialize(**kwargs)
        window.attach()
        return out
    wf.initialize = initialize_then_attach
    main()
    return window.finish()


def run(load, main):
    """The CLI's model protocol (veles_tpu/__main__.py)."""
    spec = json.loads(os.environ[SPEC_ENV])
    say("the CLI reached the model file %.1f s after the start"
        % (time.time() - spec["t_start"]))
    probe = Probe()
    device = probe.device()
    say("device %s, up %.1f s after the start"
        % (json.dumps(device), time.time() - spec["t_start"]))
    if device["platform"] != spec["platform"]:
        raise SystemExit("chipbench: jax reports platform %r, the cell runs "
                         "on %r only" % (device["platform"],
                                         spec["platform"]))
    if device["count"] < spec["workload"]["chips"]:
        raise SystemExit("chipbench: %d device(s), the cell needs %d"
                         % (device["count"], spec["workload"]["chips"]))
    kind = spec["workload"]["kind"]
    try:
        modules.reference_of(spec["config"], training=kind == "train",
                             serving=kind == "serve")
    except modules.ContractError as e:
        raise SystemExit("chipbench: %s" % e)
    if kind == "train":
        report = train_cell(spec, load, main, probe)
    else:
        from chipbench import serve_side
        report = serve_side.serve_cell(spec, load, main, probe)
    report["device"] = device
    report["compile_s"] = probe.compile_s
    report["compiles"] = probe.compiles
    report["compiles_in_window"] = probe.compiles_in_window
    print(CHILD_TAG + json.dumps(report), flush=True)
