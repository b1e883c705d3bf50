"""The child's side of a serving cell (see model_file.py and run.py).

``main()`` is the CLI's own serving loop: ``GenerationAPI`` in front of
``ContinuousEngine``, answering ``POST /generate`` until SIGTERM drains
it. Around it this file makes the weights on the device from ``--seed``,
opens and closes the profiler when the parent says so (SIGUSR1), reads
the device's memory at the close, and, once the server has stopped and
its state is freed, runs the plain reference over a sample of what was
served and compares.
"""

import gc
import json
import signal
import time

from chipbench import check, modules, reduce
from chipbench.model_file import say, skip_host_draw, keep_sample


def build_workflow(cfg, wl):
    """A forward-only workflow over the configuration: the stack that
    ``nn/sampling.split_stack`` takes (embedding, blocks, head), with no
    train step, since a train step would hold a second copy of the
    weights and an optimizer state that serving never uses. It is
    initialized on one row of the cell file's ``init_positions`` tokens (8
    where it gives none): a table of learned positions takes its length
    from that row, so such a model's cell gives its longest context."""
    import numpy
    from veles_tpu import nn
    from veles_tpu.loader import FullBatchLoaderMSE
    from veles_tpu.memory import Array
    positions = wl.get("init_positions", 8)

    class NoData(FullBatchLoaderMSE):
        hide_from_registry = True

        def load_data(self):
            z = numpy.zeros((1, positions), numpy.int32)
            self.create_originals(z, None, targets=z)
            self.class_lengths = [0, 0, 1]

    std = nn.StandardWorkflow(
        name="chipbench-serve",
        layers=modules.reference_of(cfg, serving=True).layer_list(cfg),
        loader_unit=NoData(None, minibatch_size=1, name="nodata"),
        loss_function="softmax_seq")
    wf = std.extract_forward_workflow()
    wf.forwards = list(std.forwards)
    wf.forwards[0].input = Array(numpy.zeros((1, positions), numpy.int32),
                                 name="tokens")
    return wf


class ServeSide:
    def __init__(self, spec, wf, probe):
        self.spec, self.wf, self.probe = spec, wf, probe
        self.cfg, self.wl = spec["config"], spec["workload"]
        self.reference = modules.reference_of(self.cfg, serving=True)
        self.todo = (["window open"]
                     + (["trace on", "trace off"] if spec["trace"] else [])
                     + ["window closed"])
        self.result = {}

    def attach(self):
        """Hand the program the seed's weights: every forward's host
        mirror stays zero and its device side is the benchmark's array."""
        import jax.numpy as jnp
        say("workflow initialized %.1f s after the start"
            % (time.time() - self.spec["t_start"]))
        t = time.perf_counter()
        weights = self.reference.make_weights(self.cfg, self.spec["seed"])
        for f in self.wf.forwards:
            arrays = f.param_arrays()
            if set(arrays) != set(weights[f.name]):
                raise ValueError("%s: leaves %s against the benchmark's %s"
                                 % (f.name, sorted(arrays),
                                    sorted(weights[f.name])))
            for k, arr in arrays.items():
                w = weights[f.name][k]
                if tuple(arr.shape) != w.shape or arr.dtype != w.dtype:
                    raise ValueError("%s.%s: %s %s against %s %s" % (
                        f.name, k, arr.shape, arr.dtype, w.shape, w.dtype))
                arr.assign_devmem(w)
        jnp.zeros(()).block_until_ready()
        say("weights made on the device in %.2f s, %.1f s after the start"
            % (time.perf_counter() - t,
               time.time() - self.spec["t_start"]))

    def on_signal(self, *_):
        """SIGUSR1 from the parent, once for each step of ``self.todo``:
        the window opens; with ``--trace 1`` the profiler goes on and off
        for a slice in the window's middle; the window closes."""
        import jax
        step = self.todo.pop(0)
        if step == "window open":
            self.probe.window_open = True
        elif step == "trace on":
            jax.profiler.start_trace(self.spec["trace_dir"])
            self.trace_t0 = time.perf_counter()
        elif step == "trace off":
            self.result["trace_window_s"] = (time.perf_counter()
                                             - self.trace_t0)
            jax.profiler.stop_trace()
        else:
            self.probe.window_open = False
            self.result["memory"] = self.probe.memory()
        say(step)

    def finish(self):
        if self.todo:
            raise RuntimeError("the server stopped before the window closed")
        out = dict(self.result)
        if self.spec["trace"]:
            planes = reduce.load_xplane(self.spec["trace_dir"])
            out["trace"] = reduce.reduce_trace(planes,
                                               out.pop("trace_window_s"))
            keep_sample(self.spec, planes)
        # the program's state goes before the reference comes
        for f in self.wf.forwards:
            for arr in f.param_arrays().values():
                arr.reset(arr.mem)
        gc.collect()
        say("the program's state is freed: %d bytes in use before the "
            "reference" % self.probe.memory()["bytes_in_use"])
        with open(self.spec["sample_path"]) as f:
            sample = json.load(f)
        t = time.perf_counter()
        seed, pad = self.spec["seed"], self.wl["check_pad"]
        gaps, study = [], []
        for s in sample:
            g, _ = self.reference.served_gaps(
                self.cfg, seed, s["prompt"], s["served"], pad=pad)
            gaps.extend(float(x) for x in g)
            if self.spec.get("study"):
                c, _ = self.reference.served_gaps(
                    self.cfg, seed, s["prompt"], s["served"], pad=pad,
                    pick=self.wl["control_precision"])
                study.append(float(max(c)))
        out["reference_s"] = time.perf_counter() - t
        out["correct"], out["checks"] = check.compare_served(
            gaps, self.wl["limits"])
        out["program"] = {"sampled_requests": len(sample),
                          "served_tokens": len(gaps)}
        if study:
            out["study"] = {"control_gap_by_request": study,
                            "control": max(study)}
        return out


def serve_cell(spec, load, main, probe):
    skip_host_draw()
    wf, _ = load(build_workflow, cfg=spec["config"], wl=spec["workload"])
    side = ServeSide(spec, wf, probe)
    initialize = wf.initialize

    def initialize_then_attach(**kwargs):
        out = initialize(**kwargs)
        side.attach()
        return out
    wf.initialize = initialize_then_attach
    signal.signal(signal.SIGUSR1, side.on_signal)
    main()
    return side.finish()
