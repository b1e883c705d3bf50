"""Elastic, preemption-tolerant training (veles_tpu/resilience/
elastic.py): generation lifecycle, host-loss detection, survivor
barrier, manifest cursor, quarantine link repair, the respawn
Supervisor, the falsifiable scaling model, and the bench gate.

Tier-1 scope: unit math, fault/counter plumbing and the in-process
single-host chaos round-trip (injected host loss mid-epoch → new
generation resumes from the newest valid checkpoint → state tree
equals the uninterrupted run). The multi-process kill drill and the
N=4 → N=2/N=8 reshard round-trip spawn real subprocess fleets and ride
the @slow lane (alongside tests/test_multihost.py's coordinator-kill).
"""
import os
import subprocess
import sys
import textwrap
import time

import numpy
import pytest

import veles_tpu as vt
from veles_tpu import nn, prng
from veles_tpu.config import root
from veles_tpu.launcher import Launcher
from veles_tpu.loader import FullBatchLoader
from veles_tpu.resilience import checkpoint_chain, faults
from veles_tpu.resilience import elastic
from veles_tpu.resilience.elastic import (
    ELASTIC_COUNTERS, GENERATION_EXIT_CODE, HostLostError, Supervisor,
    generation_barrier, predict_step_time, psum_bytes_per_step)
from veles_tpu.resilience.health import HeartbeatRegistry, heartbeats
from veles_tpu.telemetry.counters import DESCRIPTIONS, counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _elastic_state_guard():
    """Every test leaves the elastic knob/gauge state and the host
    heartbeats the way it found them."""
    saved = elastic.state()
    enabled = root.common.resilience.elastic.get("enabled", False)
    yield
    root.common.resilience.elastic.enabled = enabled
    elastic._set_state(**saved)
    for name in list(heartbeats.status()):
        if name.startswith(elastic.HOST_BEAT_PREFIX):
            heartbeats.unregister(name)


# ---------------------------------------------------------------------------
# fault points + counters
# ---------------------------------------------------------------------------

def test_fault_points_registered():
    points = faults.list_points()
    assert "distributed.host_loss" in points
    assert "distributed.generation_barrier" in points


def test_elastic_counters_registered():
    for name in ELASTIC_COUNTERS + (
            "veles_manifest_cursor_defaults_total",):
        assert name in DESCRIPTIONS, name


def test_check_hosts_injected_fault_raises_host_lost(monkeypatch):
    monkeypatch.setenv("VELES_FAULTS",
                       "distributed.host_loss:raise:times=1")
    faults.plane.configure()
    with pytest.raises(HostLostError):
        elastic.check_hosts(registry=HeartbeatRegistry())
    monkeypatch.delenv("VELES_FAULTS")
    faults.plane.configure()
    elastic.check_hosts(registry=HeartbeatRegistry())  # clean: no-op


def test_check_hosts_heartbeat_lapse(monkeypatch):
    monkeypatch.delenv("VELES_FAULTS", raising=False)
    faults.plane.configure()
    reg = HeartbeatRegistry()
    reg.beat("host:7", timeout=0.01)
    reg.beat("not_a_host", timeout=0.01)   # non-host lapses don't trip
    time.sleep(0.03)
    with pytest.raises(HostLostError) as e:
        elastic.check_hosts(registry=reg)
    assert "host:7" in str(e.value)
    # the loss was DECLARED: the lapsed entry is dropped, so the next
    # generation's probe does not instantly re-raise on the same beat
    assert "host:7" not in reg.status()
    reg.unregister("not_a_host")
    elastic.check_hosts(registry=reg)
    reg.beat("host:7", timeout=60.0)       # a returning host re-joins
    elastic.check_hosts(registry=reg)


def test_generation_barrier_failure_counted(monkeypatch):
    monkeypatch.setenv("VELES_FAULTS",
                       "distributed.generation_barrier:raise:times=1")
    faults.plane.configure()
    before = counters.get("veles_elastic_barrier_timeouts_total")
    with pytest.raises(HostLostError):
        generation_barrier(3, timeout=1.0)
    assert counters.get("veles_elastic_barrier_timeouts_total") \
        == before + 1
    monkeypatch.delenv("VELES_FAULTS")
    faults.plane.configure()
    # single process, clean: the barrier agrees with itself
    assert generation_barrier(4) == 4


def test_generation_barrier_timeout_enforced(monkeypatch):
    """A dead peer never arrives at the collective: the barrier's
    watchdog thread abandons the wait after generation_timeout and the
    overrun is counted."""
    monkeypatch.delenv("VELES_FAULTS", raising=False)
    faults.plane.configure()
    from veles_tpu.parallel import distributed
    monkeypatch.setattr(distributed, "survivor_barrier",
                        lambda g: time.sleep(30))
    before = counters.get("veles_elastic_barrier_timeouts_total")
    t0 = time.time()
    with pytest.raises(HostLostError) as e:
        generation_barrier(2, timeout=0.2)
    assert time.time() - t0 < 5
    assert "timed out" in str(e.value)
    assert counters.get("veles_elastic_barrier_timeouts_total") \
        == before + 1


def test_repair_skips_tmp_link_debris(tmp_path):
    """A crash between symlink() and os.replace() leaves a
    *_current.pickle*.tmp — quarantine's link repair must ignore the
    debris instead of minting a second pseudo-current link."""
    paths, link = _fake_chain(tmp_path, n=2)
    tmp_link = str(tmp_path / "wf_current.pickle.tmp")
    os.symlink("nonexistent.pickle", tmp_link)
    checkpoint_chain.quarantine(paths[-1])
    assert os.readlink(link) == os.path.basename(paths[-2])
    # the debris was never "repaired" into a second live current link:
    # it is either consumed as the atomic repoint's scratch name or
    # left as-is — never left pointing at a chain survivor
    assert not os.path.lexists(tmp_link) \
        or os.readlink(tmp_link) == "nonexistent.pickle"


def test_gauges_no_rows_until_enabled():
    elastic._set_state(enabled=False)
    assert elastic.gauges() == {}
    elastic._set_state(enabled=True, generation=2, world_size=3,
                       last_reshard_s=0.25, min_hosts=1)
    g = elastic.gauges()
    assert g["veles_elastic_generation"][0] == 2
    assert g["veles_elastic_world_size"][0] == 3
    assert g["veles_elastic_last_reshard_seconds"][0] == 0.25


# ---------------------------------------------------------------------------
# manifest cursor
# ---------------------------------------------------------------------------

def test_cursor_roundtrip_and_legacy_defaults(tmp_path):
    snap = tmp_path / "wf_x_0001.pickle"
    snap.write_bytes(b"payload")
    checkpoint_chain.write_manifest(
        str(snap), cursor={"epoch": 5, "step": 42, "world_size": 4})
    assert checkpoint_chain.cursor_of(str(snap)) == {
        "epoch": 5, "step": 42, "world_size": 4}

    # legacy manifest (pre-cursor): defaults + counted warning, no crash
    legacy = tmp_path / "wf_y_0001.pickle"
    legacy.write_bytes(b"old")
    checkpoint_chain.write_manifest(str(legacy))
    before = counters.get("veles_manifest_cursor_defaults_total")
    assert checkpoint_chain.cursor_of(str(legacy)) == \
        checkpoint_chain.CURSOR_DEFAULT
    assert counters.get("veles_manifest_cursor_defaults_total") \
        == before + 1

    # partial cursor: present keys kept, missing ones defaulted+counted
    partial = tmp_path / "wf_z_0001.pickle"
    partial.write_bytes(b"p")
    checkpoint_chain.write_manifest(str(partial), cursor={"epoch": 9})
    cur = checkpoint_chain.cursor_of(str(partial))
    assert cur["epoch"] == 9 and cur["world_size"] == 1
    assert counters.get("veles_manifest_cursor_defaults_total") \
        == before + 2

    # no manifest at all: defaults, counted, never a crash
    bare = tmp_path / "wf_w_0001.pickle"
    bare.write_bytes(b"b")
    assert checkpoint_chain.cursor_of(str(bare)) == \
        checkpoint_chain.CURSOR_DEFAULT


def test_latest_cursor_walks_newest_first(tmp_path):
    older = tmp_path / "wf_a_0001.pickle"
    older.write_bytes(b"a")
    checkpoint_chain.write_manifest(
        str(older), cursor={"epoch": 1, "step": 4, "world_size": 2})
    time.sleep(0.02)
    newer = tmp_path / "wf_a_0002.pickle"
    newer.write_bytes(b"b")
    checkpoint_chain.write_manifest(
        str(newer), cursor={"epoch": 2, "step": 8, "world_size": 2})
    path, cur = checkpoint_chain.latest_cursor(str(tmp_path), "wf")
    assert path == str(newer) and cur["epoch"] == 2
    assert checkpoint_chain.latest_cursor(str(tmp_path), "nope") is None


# ---------------------------------------------------------------------------
# quarantine link repair (the __main__ silent-rerun seam)
# ---------------------------------------------------------------------------

def _fake_chain(tmp_path, prefix="wf", n=2):
    """n fake verified snapshots, oldest→newest, plus a _current link
    pointing at the newest (what Snapshotter leaves behind)."""
    paths = []
    for i in range(1, n + 1):
        p = tmp_path / ("%s_t_%04d.pickle" % (prefix, i))
        p.write_bytes(b"state-%d" % i)
        checkpoint_chain.write_manifest(
            str(p), cursor={"epoch": i, "step": i, "world_size": 1})
        os.utime(p, (time.time() - (n - i), time.time() - (n - i)))
        paths.append(str(p))
    link = tmp_path / ("%s_current.pickle" % prefix)
    os.symlink(os.path.basename(paths[-1]), str(link))
    return paths, str(link)


def test_quarantine_repoints_current_link(tmp_path):
    paths, link = _fake_chain(tmp_path)
    # bitrot the newest; the chain walk quarantines it
    with open(paths[-1], "r+b") as f:
        f.write(b"XX")
    found = checkpoint_chain.load_latest(str(tmp_path), "wf")
    # fake payloads don't unpickle: the whole chain quarantines — the
    # point here is the LINK, not the payloads
    assert found is None
    assert os.path.exists(paths[-1] + ".corrupt")
    # the link was repointed at the older entry while it survived,
    # then removed when the chain emptied — never left dangling
    assert not os.path.lexists(link) or os.path.exists(link)


def test_quarantine_link_skips_to_older_valid_entry(tmp_path):
    paths, link = _fake_chain(tmp_path, n=3)
    checkpoint_chain.quarantine(paths[-1])
    # the link now points at the next-newest valid-named snapshot
    assert os.path.islink(link) and os.path.exists(link)
    assert os.readlink(link) == os.path.basename(paths[-2])
    # idempotent: a second quarantine pass (rerun) keeps it valid
    checkpoint_chain.quarantine(paths[-2])
    assert os.readlink(link) == os.path.basename(paths[-3])
    # chain empties -> link removed, not dangling
    checkpoint_chain.quarantine(paths[-3])
    assert not os.path.lexists(link)


# ---------------------------------------------------------------------------
# scaling model
# ---------------------------------------------------------------------------

def test_psum_bytes_model():
    assert psum_bytes_per_step(1000, 1) == 0.0
    assert psum_bytes_per_step(1000, 2) == pytest.approx(1000.0)
    assert psum_bytes_per_step(1000, 4) == pytest.approx(1500.0)
    # monotone toward 2x grad bytes as N grows
    assert psum_bytes_per_step(1000, 64) < 2000.0


def test_predict_step_time_states_inputs():
    pred = predict_step_time(0.08, 1e6, 8, device_kind="TPU v4")
    assert pred["predicted_step_s"] == pytest.approx(
        pred["compute_s"] + pred["comm_s"])
    assert pred["compute_s"] == pytest.approx(0.01)
    ins = pred["inputs"]
    assert ins["t1_step_s"] == 0.08
    assert ins["psum_bytes_per_step"] == pytest.approx(1.75e6)
    assert ins["ici_bw_bytes_per_s"] == pytest.approx(2.4e11)
    # unknown chips fall back to the stated loopback-class assumption
    from veles_tpu.telemetry.cost import DEFAULT_ICI_BW
    pred2 = predict_step_time(0.08, 1e6, 8, device_kind="weird")
    assert pred2["inputs"]["ici_bw_bytes_per_s"] == DEFAULT_ICI_BW


def test_supervisor_classifies_loss_vs_restart(tmp_path):
    """Respawn-plane arithmetic on real (trivial) subprocesses: a
    crashed worker is a lost host (world shrinks), a worker exiting
    GENERATION_EXIT_CODE is a healthy survivor (world holds), and a
    clean generation ends the job."""
    log = []

    def spawn(generation, world):
        # the respawn plane exports the generation so worker
        # controllers (and their gauges) continue the job's numbering
        assert os.environ.get(elastic.GENERATION_ENV) \
            == str(generation)
        log.append((generation, world))
        codes = []
        if generation == 1:
            codes = [42] + [GENERATION_EXIT_CODE] * (world - 1)
        elif generation == 2:
            codes = [GENERATION_EXIT_CODE] * world
        else:
            codes = [0] * world
        return [subprocess.Popen([sys.executable, "-c",
                                  "import sys; sys.exit(%d)" % c])
                for c in codes]

    sup = Supervisor(spawn, world_size=3, min_hosts=1,
                     max_generations=5, poll_interval=0.05,
                     reap_timeout=5.0)
    assert sup.run() == 3
    # gen 1: 3 hosts, one dies -> world 2; gen 2: healthy restarts
    # keep world 2; gen 3 completes
    assert log == [(1, 3), (2, 2), (3, 2)]
    # the supervisor's own environment is restored after the run
    assert elastic.GENERATION_ENV not in os.environ


def test_respawned_worker_continues_generation_numbering(
        tmp_path, monkeypatch):
    """A respawned worker seeds its controller from GENERATION_ENV so
    gauges/cursor logs continue the job's true generation count."""
    monkeypatch.delenv("VELES_FAULTS", raising=False)
    faults.plane.configure()
    assert elastic.base_generation() == 1
    monkeypatch.setenv(elastic.GENERATION_ENV, "5")
    assert elastic.base_generation() == 5
    snapdir = tmp_path / "g"
    snapdir.mkdir()
    root.common.resilience.elastic.enabled = True
    prng.seed_all(11)
    wf = _build(snapdir, "gen")
    launcher = Launcher(backend="cpu", random_seed=11)
    launcher.initialize(wf)
    results = launcher.run_elastic()
    assert results["elastic_generations"] == 5
    assert elastic.state()["generation"] == 5
    monkeypatch.setenv(elastic.GENERATION_ENV, "junk")
    assert elastic.base_generation() == 1


def test_supervisor_generation_deadline_reaps_wedged_fleet():
    """A generation where every process wedges (network-partitioned
    peer: nobody exits) is reaped at generation_deadline and respawned
    instead of blocking the respawn plane forever."""
    log = []

    def spawn(generation, world):
        log.append(generation)
        if generation == 1:
            return [subprocess.Popen(
                [sys.executable, "-c", "import time; time.sleep(60)"])
                for _ in range(world)]
        return [subprocess.Popen([sys.executable, "-c", "pass"])
                for _ in range(world)]

    sup = Supervisor(spawn, world_size=2, min_hosts=1,
                     max_generations=3, poll_interval=0.05,
                     reap_timeout=0.3, generation_deadline=1.0)
    t0 = time.time()
    assert sup.run() == 2
    assert time.time() - t0 < 30
    # wedged fleet was reaped (healthy survivors), world held at 2
    assert log == [1, 2]
    assert sup.world == 2


def test_controller_refuses_start_below_min_hosts(tmp_path,
                                                  monkeypatch):
    """A run whose world is already under the floor refuses BEFORE
    training a generation, with the real cause in the error."""
    monkeypatch.delenv("VELES_FAULTS", raising=False)
    faults.plane.configure()
    root.common.resilience.elastic.enabled = True
    root.common.resilience.elastic.min_hosts = 2
    try:
        snapdir = tmp_path / "floor"
        snapdir.mkdir()
        prng.seed_all(11)
        wf = _build(snapdir, "fl")
        launcher = Launcher(backend="cpu", random_seed=11)
        launcher.initialize(wf)
        with pytest.raises(HostLostError) as e:
            launcher.run_elastic()
        assert "min_hosts" in str(e.value)
        assert not checkpoint_chain.chain(str(snapdir), "fl"), \
            "a generation trained despite the floor"
    finally:
        root.common.resilience.elastic.min_hosts = 1


def test_supervisor_min_hosts_floor():
    def spawn(generation, world):
        return [subprocess.Popen([sys.executable, "-c",
                                  "import sys; sys.exit(42)"])
                for _ in range(world)]

    sup = Supervisor(spawn, world_size=2, min_hosts=2,
                     max_generations=4, poll_interval=0.05,
                     reap_timeout=5.0)
    with pytest.raises(HostLostError):
        sup.run()


# ---------------------------------------------------------------------------
# in-process single-host chaos round-trip (the tier-1 acceptance leg)
# ---------------------------------------------------------------------------

class _Blobs(FullBatchLoader):
    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.RandomState(3)
        centers = rng.randn(3, 8) * 3
        y = rng.randint(0, 3, 120).astype(numpy.int32)
        x = (centers[y] + rng.randn(120, 8)).astype(numpy.float32)
        self.create_originals(x, y)
        self.class_lengths = [0, 24, 96]


def _build(snapdir, prefix):
    snap = vt.Snapshotter(None, prefix=prefix, directory=str(snapdir),
                          interval=1)
    return nn.StandardWorkflow(
        name=prefix,
        layers=[{"type": "all2all_tanh", "output_sample_shape": 8},
                {"type": "softmax", "output_sample_shape": 3}],
        loader_unit=_Blobs(None, minibatch_size=24, name="l"),
        loss_function="softmax",
        decision_config=dict(max_epochs=4, fail_iterations=100),
        snapshotter_unit=snap)


def _assert_trees_equal(a, b, path="root"):
    assert type(a) is type(b), (path, type(a), type(b))
    if isinstance(a, dict):
        assert sorted(a) == sorted(b), (path, sorted(a), sorted(b))
        for k in a:
            _assert_trees_equal(a[k], b[k], "%s.%s" % (path, k))
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_trees_equal(x, y, "%s[%d]" % (path, i))
    elif isinstance(a, numpy.ndarray):
        numpy.testing.assert_array_equal(a, b, err_msg=path)
    elif isinstance(a, float):
        assert a == pytest.approx(b), path
    else:
        assert a == b, path


def test_injected_host_loss_resumes_and_matches_uninterrupted(
        tmp_path, monkeypatch):
    """ISSUE acceptance (single-host leg, tier-1): a host-loss fault
    fired mid-epoch ends generation 1; generation 2 restores the
    newest valid checkpoint (epoch cursor logged from the manifest)
    and the completed run's state tree equals an uninterrupted run's
    bit for bit."""
    monkeypatch.delenv("VELES_FAULTS", raising=False)

    # uninterrupted reference
    clean_dir = tmp_path / "clean"
    clean_dir.mkdir()
    prng.seed_all(11)
    wf = _build(clean_dir, "el")
    launcher = Launcher(backend="cpu", random_seed=11)
    launcher.initialize(wf)
    launcher.run()

    # elastic run: host lost on the 5th train-step dispatch (the fused
    # step runs ~2 dispatches per epoch -> mid-run, after snapshots
    # for epochs 1-2 are already on the chain)
    chaos_dir = tmp_path / "chaos"
    chaos_dir.mkdir()
    root.common.resilience.elastic.enabled = True
    monkeypatch.setenv(
        "VELES_FAULTS", "distributed.host_loss:raise:after=4,times=1")
    faults.plane.configure()
    gen_before = counters.get("veles_elastic_generations_total")
    pre_before = counters.get("veles_elastic_preemptions_total")
    prng.seed_all(11)
    wf2 = _build(chaos_dir, "el")
    launcher2 = Launcher(backend="cpu", random_seed=11)
    launcher2.initialize(wf2)
    results = launcher2.run_elastic()
    monkeypatch.delenv("VELES_FAULTS")
    faults.plane.configure()

    assert results["elastic_generations"] == 2
    assert counters.get("veles_elastic_generations_total") \
        == gen_before + 2
    assert counters.get("veles_elastic_preemptions_total") \
        == pre_before + 1
    assert counters.get("veles_elastic_reshard_seconds_total") > 0
    # the host beat was unregistered with the run — it must not age
    # into a false /healthz failure on a process that keeps serving
    assert not any(n.startswith(elastic.HOST_BEAT_PREFIX)
                   for n in heartbeats.status())

    # the snapshot manifests carry the elastic cursor
    found = checkpoint_chain.latest_cursor(str(chaos_dir), "el")
    assert found is not None
    _, cur = found
    assert cur["epoch"] >= 1 and cur["world_size"] == 1 \
        and cur["step"] > 0

    # converged state tree equals the uninterrupted run
    clean_state = checkpoint_chain.load_latest(str(clean_dir), "el")[1]
    chaos_state = checkpoint_chain.load_latest(str(chaos_dir), "el")[1]
    _assert_trees_equal(chaos_state["__units__"],
                        clean_state["__units__"])
    _assert_trees_equal(chaos_state["__prng__"],
                        clean_state["__prng__"])


# ---------------------------------------------------------------------------
# @slow: multi-process kill drill + cross-width reshard round-trip
# ---------------------------------------------------------------------------

ELASTIC_CHILD = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)      # exactly 1 device per process
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, %(repo)r)
    import numpy
    import veles_tpu as vt
    from veles_tpu import nn, prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.loader import FullBatchLoader

    class Blobs(FullBatchLoader):
        hide_from_registry = True
        def load_data(self):
            rng = numpy.random.RandomState(3)
            centers = rng.randn(3, 8) * 3
            y = rng.randint(0, 3, 120).astype(numpy.int32)
            x = (centers[y] + rng.randn(120, 8)).astype(numpy.float32)
            self.create_originals(x, y)
            self.class_lengths = [0, 24, 96]

    pid = int(sys.argv[1]); port = int(sys.argv[2])
    nproc = int(sys.argv[3]); snapdir = sys.argv[4]
    max_epochs = int(sys.argv[5])
    root.common.resilience.elastic.enabled = True
    launcher = Launcher(
        coordinator="127.0.0.1:%%d" %% port if nproc > 1 else None,
        num_processes=nproc if nproc > 1 else None,
        process_id=pid if nproc > 1 else None,
        mesh={"data": nproc}, random_seed=11)
    snap = vt.Snapshotter(None, prefix="esup", directory=snapdir,
                          interval=1)
    wf = nn.StandardWorkflow(
        name="esup",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 8},
                {"type": "softmax", "output_sample_shape": 3}],
        loader_unit=Blobs(None, minibatch_size=24, name="l"),
        loss_function="softmax",
        decision_config=dict(max_epochs=max_epochs,
                             fail_iterations=100),
        snapshotter_unit=snap)
    launcher.initialize(wf)
    results = launcher.run_elastic()
    print("RANK%%d DONE generations=%%s epoch=%%d" %% (
        pid, results.get("elastic_generations"),
        wf.decision.epoch_number), flush=True)
""")


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_chaos_host_kill_mid_epoch_supervisor_reshards(tmp_path):
    """ISSUE acceptance (multi-host leg): a 2-process SPMD job loses a
    host mid-epoch to an injected ``distributed.host_loss:crash``
    fault; the Supervisor reaps the wedged survivor, declares
    generation 2 at world 1, and the respawned job reshards from the
    newest valid checkpoint and converges to the same state tree as an
    uninterrupted run (psum-DP equivalence makes the world-size change
    invisible up to summation order)."""
    snapdir = tmp_path / "esup"
    snapdir.mkdir()
    script = tmp_path / "echild.py"
    script.write_text(ELASTIC_CHILD % {"repo": REPO})
    outs = {}

    def spawn(generation, world):
        port = _free_port()
        procs = []
        for pid in range(world):
            env = dict(os.environ)
            env["PYTHONPATH"] = REPO
            env.pop("VELES_FAULTS", None)
            if generation == 1 and pid == 1:
                # the preemption: rank 1 dies on its 5th armed
                # dispatch (mid-epoch 3; epochs 1-2 are on the chain)
                env["VELES_FAULTS"] = \
                    "distributed.host_loss:crash:after=4,times=1"
            p = subprocess.Popen(
                [sys.executable, str(script), str(pid), str(port),
                 str(world), str(snapdir), "6"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=REPO, env=env)
            procs.append(p)
        outs[generation] = procs
        return procs

    sup = Supervisor(spawn, world_size=2, min_hosts=1,
                     max_generations=4, poll_interval=0.2,
                     reap_timeout=20.0)
    final_generation = sup.run()
    assert final_generation >= 2
    assert sup.world == 1
    last = outs[final_generation][0]
    stdout = last.communicate()[0]
    assert "RANK0 DONE" in stdout, stdout[-2000:]

    # uninterrupted reference at world 1, same seed/config
    clean_dir = tmp_path / "clean"
    clean_dir.mkdir()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.pop("VELES_FAULTS", None)
    r = subprocess.run(
        [sys.executable, str(script), "0", "0", "1", str(clean_dir),
         "6"], capture_output=True, text=True, cwd=REPO, env=env,
        timeout=300)
    assert r.returncode == 0, r.stdout[-3000:]

    chaos = checkpoint_chain.load_latest(str(snapdir), "esup")[1]
    clean = checkpoint_chain.load_latest(str(clean_dir), "esup")[1]
    cu, xu = clean["__units__"], chaos["__units__"]
    assert sorted(cu) == sorted(xu)
    # weights converge to the uninterrupted trajectory (allclose: the
    # 2-proc epochs psum partial sums in a different order)
    for unit_name, sd in cu.items():
        for key, val in sd.items():
            if isinstance(val, numpy.ndarray) \
                    and val.dtype.kind == "f":
                numpy.testing.assert_allclose(
                    xu[unit_name][key], val, rtol=1e-5, atol=1e-6,
                    err_msg="%s.%s" % (unit_name, key))
    assert xu["l"]["epoch_number"] == cu["l"]["epoch_number"]
    # the manifest cursor of the final snapshot records world 1
    _, cur = checkpoint_chain.latest_cursor(str(snapdir), "esup")
    assert cur["world_size"] == 1 and cur["epoch"] >= 5


RESHARD_CHILD = textwrap.dedent("""
    import os, sys
    import numpy
    sys.path.insert(0, %(repo)r)
    import veles_tpu as vt
    from veles_tpu import nn, prng
    from veles_tpu.loader import FullBatchLoader

    class Blobs(FullBatchLoader):
        hide_from_registry = True
        def load_data(self):
            rng = numpy.random.RandomState(3)
            centers = rng.randn(3, 8) * 3
            y = rng.randint(0, 3, 120).astype(numpy.int32)
            x = (centers[y] + rng.randn(120, 8)).astype(numpy.float32)
            self.create_originals(x, y)
            self.class_lengths = [0, 24, 96]

    mode = sys.argv[1]; n = int(sys.argv[2])
    snapdir = sys.argv[3]; out = sys.argv[4]
    prng.seed_all(11)
    snap = vt.Snapshotter(None, prefix="rs", directory=snapdir,
                          interval=1)
    wf = nn.StandardWorkflow(
        name="rs",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 8},
                {"type": "softmax", "output_sample_shape": 3}],
        loader_unit=Blobs(None, minibatch_size=24, name="l"),
        loss_function="softmax",
        decision_config=dict(max_epochs=2, fail_iterations=100),
        snapshotter_unit=snap)
    dev = vt.XLADevice(mesh_axes={"data": n})
    wf.initialize(device=dev)
    assert wf.train_step.params["all2all_tanh0"][
        "weights"].sharding.num_devices == n or n == 1
    if mode == "save":
        wf.run()
    else:
        from veles_tpu.parallel.distributed import restore_latest
        assert restore_latest(wf, snapdir, "rs"), "nothing to restore"
    # forward logits on a fixed batch through the restored params —
    # the device-count-agnostic snapshot contract: identical at any N
    fwf = wf.extract_forward_workflow()
    from veles_tpu.memory import Array
    x = wf.loader.original_data.mem[:24]
    wf.forwards[0].input = Array(x, name="x")
    fwf.initialize(device=dev)
    fwf.run()
    logits = numpy.asarray(wf.forwards[-1].output.map_read())
    numpy.savez(out, logits=logits,
                w0=numpy.asarray(wf.forwards[0].weights.map_read()))
    print("RESHARD OK n=%%d" %% n, flush=True)
""")


@pytest.mark.slow
def test_reshard_snapshot_n4_restores_at_n2_and_n8(tmp_path):
    """Device-count-agnostic snapshot layout: a snapshot saved on a
    4-device mesh restores on 2- and 8-device meshes with identical
    forward logits (unsharded logical trees on disk, shard on load)."""
    script = tmp_path / "rchild.py"
    script.write_text(RESHARD_CHILD % {"repo": REPO})
    snapdir = tmp_path / "rs"
    snapdir.mkdir()

    def run(mode, n):
        out = str(tmp_path / ("logits_%s_%d.npz" % (mode, n)))
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env.pop("VELES_FAULTS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_"
                              "count=%d" % n)
        r = subprocess.run(
            [sys.executable, str(script), mode, str(n), str(snapdir),
             out], capture_output=True, text=True, cwd=REPO, env=env,
            timeout=300)
        assert r.returncode == 0, (mode, n, r.stdout[-2000:],
                                   r.stderr[-2000:])
        return numpy.load(out)

    saved = run("save", 4)
    at4 = run("restore", 4)
    at2 = run("restore", 2)
    at8 = run("restore", 8)
    for tag, doc in (("n4", at4), ("n2", at2), ("n8", at8)):
        numpy.testing.assert_allclose(
            doc["logits"], saved["logits"], rtol=1e-6, atol=1e-7,
            err_msg=tag)
        numpy.testing.assert_array_equal(doc["w0"], saved["w0"],
                                         err_msg=tag)


def test_barrier_failure_ends_generation_not_run(tmp_path, monkeypatch):
    """An injected generation-barrier failure is a preemption like any
    other: generation 1 dies at the barrier, generation 2 proceeds and
    the run completes — the barrier failure never kills the whole
    elastic run (single-process leg; multi-process survivors exit 43
    for the respawn plane)."""
    snapdir = tmp_path / "b"
    snapdir.mkdir()
    root.common.resilience.elastic.enabled = True
    monkeypatch.setenv(
        "VELES_FAULTS", "distributed.generation_barrier:raise:times=1")
    faults.plane.configure()
    bt_before = counters.get("veles_elastic_barrier_timeouts_total")
    pre_before = counters.get("veles_elastic_preemptions_total")
    prng.seed_all(11)
    wf = _build(snapdir, "bar")
    launcher = Launcher(backend="cpu", random_seed=11)
    launcher.initialize(wf)
    results = launcher.run_elastic()
    monkeypatch.delenv("VELES_FAULTS")
    faults.plane.configure()
    assert results["elastic_generations"] == 2
    assert counters.get("veles_elastic_barrier_timeouts_total") \
        == bt_before + 1
    assert counters.get("veles_elastic_preemptions_total") \
        == pre_before + 1


def test_resume_via_quarantined_current_link_falls_back(
        tmp_path, monkeypatch):
    """The __main__ silent-rerun seam: `--snapshot <dir>/el_current...`
    after the previous run's newest entry was quarantined (link
    dangles) must skip straight to the older valid snapshot instead of
    dying — the elastic restart is idempotent."""
    monkeypatch.delenv("VELES_FAULTS", raising=False)
    faults.plane.configure()
    snapdir = tmp_path / "snaps"
    snapdir.mkdir()
    prng.seed_all(7)
    wf = _build(snapdir, "el")
    launcher = Launcher(backend="cpu", random_seed=7)
    launcher.initialize(wf)
    launcher.run()
    chain = checkpoint_chain.chain(str(snapdir), "el")
    assert len(chain) >= 2
    # previous run quarantined the newest entry: link dangles
    newest = chain[0]
    os.replace(newest, newest + ".corrupt")
    link = os.path.join(str(snapdir), "el_current.pickle.gz")
    assert os.path.islink(link) and not os.path.exists(link)

    prng.seed_all(7)
    wf2 = _build(snapdir, "el")    # fresh units, same topology
    launcher2 = Launcher(backend="cpu", random_seed=7)
    launcher2.initialize(wf2)
    launcher2.resume(link)          # must fall back, not raise
    assert wf2.restored_from_snapshot
    assert wf2.decision.epoch_number >= 1
