"""What decides ``correct``, shown to fail: at a size a test run holds.

- the plain reference against the program's own host oracle
  (``TransformerBlock.numpy_apply``) at a tiny size;
- the control (the reference with float8 products) put in the program's
  place: not correct under the tiny cell's limits;
- the rest of a run with the timed path broken underneath (a step that
  returns its state unchanged; half of the batch left out, the mean taken
  over the rest; a served token altered where it is produced): ``correct``
  comes out false, and true with nothing broken;
- a rehearsal run's last line: the keys the driver reads, and no metric;
  the child's report behind it: a training cell's carries the window's
  counters, a serving cell's is key for key what it was.
"""
import json
import os
import subprocess
import sys
import time

import numpy
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


TINY = load("configs", "tiny.json")
TRAIN = dict(load("workloads", "tiny_train.json"), chips=1)
SERVE = dict(load("workloads", "tiny_serve.json"), chips=1)


def rehearsal_serving_cells():
    """{cell: (its file, its configuration)} of every serving cell under
    ``workloads/`` that ``BENCHMARK.json`` does not name: the sizes a test
    run holds. A rehearsal cell that a later PR adds is driven by the
    tests below as ``tiny_serve`` is."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        named = {w["name"] for w in json.load(f)["workloads"]}
    out = {}
    for name in sorted(os.listdir(os.path.join(ROOT, "chipbench",
                                               "workloads"))):
        wl = load("workloads", name)
        if wl["kind"] == "serve" and wl["name"] not in named:
            out[wl["name"]] = (dict(wl, chips=1),
                               load("configs", wl["config"] + ".json"))
    return out


SERVING = rehearsal_serving_cells()


@pytest.fixture(autouse=True)
def host_draw_restored():
    """``model_file.skip_host_draw`` patches the program's generator for
    the whole process, and a test worker runs other files after this one:
    whatever a test here patched is put back."""
    from veles_tpu import prng
    keep = prng.RandomGenerator.fill_normal
    yield
    prng.RandomGenerator.fill_normal = keep


def host(tree):
    return {u: {k: numpy.asarray(v) for k, v in leaves.items()}
            for u, leaves in tree.items()}


def test_reference_against_numpy_apply():
    """One block, the embedding and the head of the reference against the
    program's host oracle on the same seeded weights."""
    import jax.numpy as jnp
    from chipbench import reference
    params = reference.make_weights(TINY, 7)
    np_params = host(params)
    tokens = reference.make_tokens(7, 2, 12, TINY["vocab_size"])[:, :-1]
    x = np_params["embed"]["table"][tokens]
    from veles_tpu.nn.transformer import TransformerBlock
    layer = dict(reference.layer_list(TINY)[1])
    layer.pop("type")
    blk = TransformerBlock(None, **layer)
    for i in range(TINY["num_hidden_layers"]):
        x = blk.numpy_apply(np_params["blk%d" % i], x)
    want = x @ np_params["head"]["weights"] + np_params["head"]["bias"]
    got = numpy.asarray(reference.logits_fn(params, jnp.asarray(tokens),
                                            TINY))
    assert numpy.abs(got - want).max() <= 1e-5 * numpy.abs(want).max() + 1e-6


def test_weights_are_the_seed_s():
    from chipbench import reference
    a = host(reference.make_weights(TINY, 2 ** 31 + 11))
    b = host(reference.make_weights(TINY, 2 ** 31 + 11))
    c = host(reference.make_weights(TINY, 2 ** 31 + 12))
    assert all((a[u][k] == b[u][k]).all() for u in a for k in a[u])
    assert not (a["blk0"]["wq"] == c["blk0"]["wq"]).all()
    assert abs(a["blk0"]["w1"].std() - TINY["initializer_range"]) < 2e-3
    assert (a["blk0"]["ln1_g"] == 1).all() and (a["head"]["bias"] == 0).all()


@pytest.fixture(scope="module")
def tiny_reference():
    from chipbench import reference
    rows = reference.make_tokens(5, TRAIN["rows_per_epoch"],
                                 TRAIN["seq_len"], TINY["vocab_size"])
    batches = [rows[i:i + 2] for i in (0, 2, 4)]
    return batches, reference.train_reference(
        TINY, 5, batches, TRAIN["learning_rate"])


@pytest.mark.parametrize("quant,correct", [
    (None, True), ("float8_e4m3fn", False)], ids=["reference", "control"])
def test_training_control_is_not_correct(tiny_reference, quant, correct):
    from chipbench import check, reference
    batches, ref = tiny_reference
    side = reference.train_reference(TINY, 5, batches,
                                     TRAIN["learning_rate"], quant=quant)
    ok, numbers = check.compare_train(side, ref, TRAIN["limits"])
    assert ok == correct, numbers


def test_layer_by_layer_step_is_the_whole_gradient(tiny_reference):
    """The reference takes a step layer by layer; its first loss and
    gradient are those of the whole model's loss in one expression."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference
    batches, ref = tiny_reference
    rows = jnp.asarray(batches[0])
    loss, grad = jax.value_and_grad(reference.loss_fn)(
        reference.make_weights(TINY, 5), rows[:, :-1], rows[:, 1:], TINY)
    assert ref["loss"][0] == pytest.approx(float(loss), rel=1e-6)
    whole = reference.floats(jax.device_get(reference.leaf_norms(grad)))
    for unit, leaves in whole.items():
        for leaf, norm in leaves.items():
            assert ref["grad1"][unit][leaf] == pytest.approx(norm, rel=1e-5)


def test_half_of_the_batch_left_out_is_not_correct(tiny_reference):
    """The fault as it is planted in the reference for the chip's
    readings: the loss over the leading half of the positions only."""
    from chipbench import check, reference
    batches, ref = tiny_reference
    fault = reference.train_reference(TINY, 5, batches,
                                      TRAIN["learning_rate"], keep_share=0.5)
    ok, numbers = check.compare_train(fault, ref, TRAIN["limits"])
    assert not ok
    assert numbers["grad_norm_gap"]["value"] > 10 * TRAIN["limits"][
        "grad_norm_gap"]


def test_served_control_is_not_correct():
    from chipbench import check, reference
    rng = numpy.random.default_rng(9)
    worst = 0.0
    for _ in range(6):
        prompt = rng.integers(0, TINY["vocab_size"], 20).tolist()
        served = rng.integers(0, TINY["vocab_size"], 30).tolist()
        _, greedy = reference.served_gaps(TINY, 9, prompt, served, pad=64)
        again, _ = reference.served_gaps(TINY, 9, prompt, greedy.tolist(),
                                         pad=64)
        # the reference's own first choice lies 0 below its best at the
        # first position; later ones were chosen in another context
        assert again[0] == 0.0
        gaps, _ = reference.served_gaps(TINY, 9, prompt, served, pad=64,
                                        pick="float8_e4m3fn")
        worst = max(worst, float(gaps.max()))
    assert not check.compare_served([worst], SERVE["limits"])[0], worst


# -- the rest of a run, with the timed path broken underneath ----------------

def run_train_cell(break_it=None, seconds=0.5, trace=0):
    """``model_file.train_cell`` as the child runs it, minus the look for a
    chip: the CLI's load and main are stood in for on the CPU."""
    from veles_tpu.backends import XLADevice
    from veles_tpu.config import root
    from chipbench import model_file
    spec = {"workload": TRAIN, "config": TINY, "seed": 5, "seconds": seconds,
            "trace": trace, "trace_dir": None, "t_start": time.time(),
            "platform": "cpu", "study": False}
    state = {}

    def load_(builder, **kwargs):
        state["wf"] = builder(**kwargs)
        return state["wf"], False

    def main():
        wf = state["wf"]
        wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
        if break_it:
            break_it(wf)
        wf.run()
    before = root.common.engine.get("mixed_precision", False)
    root.common.engine.mixed_precision = True
    try:
        return model_file.train_cell(spec, load_, main, model_file.Probe())
    finally:
        root.common.engine.mixed_precision = before


def state_unchanged(wf):
    """A step that computes everything and returns its state as it was."""
    step = wf.train_step
    inner = step.xla_run.__self__._run

    def run():
        import jax
        keep = jax.tree_util.tree_map(lambda a: a + 0,
                                      (step.params, step.opt_state))
        inner()
        step.params, step.opt_state = keep
    step.xla_run.__self__._run = run


def half_batch(wf):
    """Half of the batch left out, the mean taken over the rest."""
    loader = wf.loader
    serve = loader.serve_next_minibatch

    def serve_half():
        serve()
        mask = loader.minibatch_mask.map_write()
        mask[len(mask) // 2:] = 0.0
    loader.serve_next_minibatch = serve_half


@pytest.mark.parametrize("break_it,correct,number", [
    (None, True, None), (state_unchanged, False, "delta_norm_gap"),
    (half_batch, False, "grad_norm_gap")],
    ids=["sound", "state_unchanged", "half_batch"])
def test_train_cell_end_to_end(break_it, correct, number):
    report = run_train_cell(break_it)
    assert report["correct"] == correct, report["checks"]
    assert report["steps"] >= 1 and report["tokens"] == (
        report["steps"] * TRAIN["minibatch"] * TRAIN["seq_len"])
    if number:
        n = report["checks"][number]
        assert not n["value"] <= n["limit"]


def test_the_profiler_is_on_for_a_slice_in_the_middle(monkeypatch):
    """With ``--trace 1`` the window is as long as without; the per-layer
    metrics are of a slice in its middle (the profiler and its file are
    stood in for: the CPU's trace has no device plane)."""
    import jax
    from chipbench import reduce
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("on"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("off"))
    monkeypatch.setattr(reduce, "load_xplane", lambda d: [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["a", 0, 1000]]}]}])
    seconds = 1.0
    report = run_train_cell(seconds=seconds, trace=1)
    piece = report["slice"]
    assert calls == ["on", "off"]
    assert report["window_s"] >= seconds
    assert piece["from_s"] >= (seconds - TRAIN["trace_seconds"]) / 2.0 > 0
    assert 0 < piece["steps"] < report["steps"]
    assert piece["from_s"] + piece["window_s"] <= report["window_s"]
    assert report["trace"]["window_s"] == piece["window_s"]
    # the slice's counters are of its own steps, the window's of all
    assert piece["counters"]["veles_dispatches_total"] == piece["steps"]
    assert report["counters"]["veles_dispatches_total"] == report["steps"]


# -- a rehearsal run's last line ---------------------------------------------

def rehearse(workload, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), "--workload",
         workload, "--seed", str(2 ** 31 + 3), "--seconds", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def rehearse_kept(workload, keep):
    """A rehearsal with ``--keep``: the run, and as its ``report`` the
    child's whole report (with what the parent added) where it ended well."""
    run = rehearse(workload, "--trace", "0", "--rehearse", "--keep",
                   str(keep))
    run.report = None
    if run.returncode == 0:
        (name,) = os.listdir(keep)
        with open(os.path.join(str(keep), name)) as f:
            run.report = json.load(f)
    return run


@pytest.fixture(scope="module", params=sorted(SERVING))
def served_run(request, tmp_path_factory):
    return rehearse_kept(request.param, tmp_path_factory.mktemp("keep"))


@pytest.fixture(scope="module")
def trained_run(tmp_path_factory):
    return rehearse_kept("tiny_train", tmp_path_factory.mktemp("keep"))


def rehearsal_line(run):
    """The last line of a rehearsal that ended well: the keys the driver
    reads, ``checks`` last, the CPU named and no metric."""
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert line["metrics"] == {}, "a CPU run carries no metric"
    assert line["correct"] is True and line["failed"] == 0
    assert "compilations inside the window: 0" in run.stdout
    return line


def test_rehearsal_line(served_run):
    assert rehearsal_line(served_run)["attempted"] >= 4
    assert "served_logit_gap" in served_run.stderr.strip().splitlines()[-1]


#: a serving report's keys, and a request's, as they were before the training
#: report gained its counters: the serving side gained and lost none
SERVING_REPORT = [
    "attempted", "checks", "compile_s", "compiles", "compiles_in_window",
    "correct", "device", "failed", "generator_late_ms", "memory", "program",
    "reference_s", "requests", "setup_s", "slice", "window_s"]
SERVED_REQUEST = [
    "due", "error", "first", "i", "last", "n_new", "ok", "prompt_len",
    "sampled", "sent", "stamps", "tokens", "tokens_in_window"]


def test_a_serving_report_is_key_for_key_what_it_was(served_run):
    report = served_run.report
    assert sorted(report) == SERVING_REPORT
    assert report["slice"] is None, "the slice is the profiler's"
    assert all(sorted(r) == SERVED_REQUEST for r in report["requests"])


def test_training_rehearsal_line(trained_run):
    assert sorted(rehearsal_line(trained_run)["checks"]) == [
        "delta_norm_gap", "grad_norm_gap", "loss_gap"]


def test_a_training_report_carries_the_window_s_counters(trained_run):
    """Without the profiler there is no slice; the window's counters are
    the rise of the program's ``/metrics`` series over it, under the names
    and in the shape that a serving slice's have."""
    report = trained_run.report
    assert "slice" not in report
    counters = report["counters"]
    assert counters["veles_dispatches_total"] >= report["steps"] >= 1
    assert counters["veles_compiles_total"] == 0
    assert all(k.endswith(("_total", "_sum", "_count"))
               and isinstance(v, float) for k, v in counters.items())


def test_counters_rise_is_one_shape_for_both_kinds_of_cell():
    from chipbench import reduce
    before = {"a_total": 2.0, "h_seconds_sum": 0.5, "h_seconds_count": 4.0,
              "h_seconds_p50": 0.1, "a_gauge": 7.0}
    after = dict(before, a_total=5.0, h_seconds_sum=0.75, h_seconds_count=6.0,
                 b_total=3.0, h_seconds_p50=0.2)
    assert reduce.counters_rise(before, after) == {
        "a_total": 3.0, "h_seconds_sum": 0.25, "h_seconds_count": 2.0,
        "b_total": 3.0}


def test_no_accelerator_no_result():
    """Without ``--rehearse`` the same command finds no TPU here: another
    exit code than 0 and no result line."""
    out = rehearse("internlm2_train4k", "--trace", "0")
    assert out.returncode != 0
    assert not any(ln.startswith("{") for ln in out.stdout.splitlines())


def alter_a_token(engine, vocab):
    """The decode program's own output with one slot's token altered on
    every fifth step: it is recorded, streamed and fed back as served."""
    program = engine._program
    calls = [0]

    def patched(kind, bucket=None):
        prog = program(kind, bucket)
        if kind != "step":
            return prog

        def step(*args):
            toks, keys, caches = prog(*args)
            calls[0] += 1
            if calls[0] % 5 == 0:
                toks = toks.at[:, 0].set((toks[:, 0] + 17) % vocab)
            return toks, keys, caches
        return step
    engine._program = patched


@pytest.mark.parametrize("cell", sorted(SERVING))
@pytest.mark.parametrize("break_it,correct", [
    (None, True), (alter_a_token, False)], ids=["sound", "token_altered"])
def test_serve_cell_end_to_end(tmp_path, cell, break_it, correct):
    """The rest of a serving run, minus the look for a chip and the CLI's
    own loop: the child's side around a live ``GenerationAPI``, the
    client's streamed requests over real HTTP, the sample, the reference."""
    from veles_tpu.backends import XLADevice
    from veles_tpu.restful_api import GenerationAPI
    from chipbench import model_file, serve_client, serve_side, traffic
    wl, cfg = SERVING[cell]
    spec = {"workload": wl, "config": cfg, "seed": 31, "seconds": 1,
            "trace": 0, "trace_dir": None, "t_start": time.time(),
            "platform": "cpu", "study": False,
            "sample_path": str(tmp_path / "sample.json")}
    model_file.skip_host_draw()
    wf = serve_side.build_workflow(cfg, wl)
    wf.initialize(device=XLADevice("cpu", mesh_axes={"data": 1}))
    side = serve_side.ServeSide(spec, wf, model_file.Probe())
    side.attach()
    flag = dict(zip(wl["cli"][::2], wl["cli"][1::2]))
    api = GenerationAPI(wf, port=0, max_slots=int(flag["--serve-slots"]),
                        buckets=[int(b) for b in
                                 flag["--serve-buckets"].split(",")],
                        max_context=int(flag["--serve-max-context"]),
                        name="chipbench_test_%s_%s" % (
                            cell, break_it is not None))
    api.initialize()
    try:
        if break_it:
            break_it(api._engine, cfg["vocab_size"])
        side.on_signal()
        records = []
        for item in traffic.schedule(wl["traffic"], 31,
                                     cfg["vocab_size"])[:6]:
            rec = {"i": item["i"], "sampled": item["sampled"], "item": item}
            serve_client.stream_request(api.port, item, rec)
            assert rec["ok"], rec["error"]
            records.append(rec)
        side.on_signal()
    finally:
        api.stop()
    with open(spec["sample_path"], "w") as f:
        json.dump(serve_client.pick_sample(records, 31, 4), f)
    report = side.finish()
    assert report["program"]["served_tokens"] >= 16
    assert report["correct"] == correct, report["checks"]


def test_an_altered_token_reads_far_below_the_best():
    """The comparison alone: the reference's own greedy continuation reads
    0, and with one token altered the gap passes the limit."""
    from chipbench import check, reference
    prompt = list(range(10, 30))
    served = []
    for _ in range(12):
        _, best = reference.served_gaps(TINY, 13, prompt + served, [0],
                                        pad=64)
        served.append(int(best[0]))
    sound, _ = reference.served_gaps(TINY, 13, prompt, served, pad=64)
    assert check.compare_served(list(sound), SERVE["limits"])[0]
    broken = list(served)
    broken[5] = (broken[5] + 17) % TINY["vocab_size"]
    gaps, _ = reference.served_gaps(TINY, 13, prompt, broken, pad=64)
    assert not check.compare_served(list(gaps), SERVE["limits"])[0]
