"""Launcher: run-mode resolution and workflow lifecycle.

Equivalent of the reference's veles/launcher.py:100-906. Mode resolution
simplifies radically: the reference arbitrated standalone/master/slave and
spawned slaves over SSH; here every process is a peer in one SPMD job
(jax distributed runtime), so the modes are standalone vs multi-host
participant (+ train vs test). Preserved surface: device creation,
workflow initialize ordering, snapshot resume, graceful stop, results
gathering/reporting, elapsed/timing reporting, status beacon hook.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

from .backends import Device_for, XLADevice
from .config import root
from .logger import Logger
from . import prng
from .parallel import distributed


class Launcher(Logger):
    def __init__(self, backend: Optional[str] = None,
                 mesh: Optional[Dict[str, int]] = None,
                 coordinator: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None,
                 random_seed: Optional[int] = None,
                 test_mode: bool = False,
                 graphics: bool = False,
                 plots_dir: Optional[str] = None,
                 status_url: Optional[str] = None,
                 notification_interval: float = 10.0,
                 profile_dir: Optional[str] = None) -> None:
        super().__init__()
        self.test_mode = test_mode
        self.workflow = None
        self.device = None
        self._graphics_enabled = graphics
        self._plots_dir = plots_dir
        self.graphics_server = None
        self._status_url = status_url
        self._notification_interval = notification_interval
        self.status_reporter = None
        self._backend = backend
        self._mesh = mesh
        #: XPlane trace capture (SURVEY.md §5.1 TPU mapping of the
        #: reference's event spans + --timings): device timeline,
        #: compiled-op breakdown, host/device overlap
        self._profile_dir = profile_dir
        self._dist = (coordinator, num_processes, process_id)
        if random_seed is not None:
            prng.seed_all(random_seed)
        self._start_time = None
        self.stopped = False
        self.interrupted = False

    # -- lifecycle -----------------------------------------------------------
    def make_device(self):
        """Distributed init + device/mesh resolution; shared by the normal
        path and the meta-learning modes (--optimize/--ensemble-*)."""
        from .error import VelesError
        coordinator, nproc, pid = self._dist
        distributed.initialize_multihost(coordinator, nproc, pid)
        if self._mesh:
            if self._backend == "numpy" or root.common.engine.force_numpy:
                raise VelesError(
                    "--mesh requires an XLA backend; it cannot combine "
                    "with numpy/--force-numpy")
            platform = (self._backend
                        if self._backend in ("cpu", "tpu") else None)
            self.device = XLADevice(platform=platform,
                                    mesh_axes=self._mesh)
        else:
            self.device = Device_for(self._backend)
        return self.device

    def initialize(self, workflow) -> None:
        self.make_device()
        self.workflow = workflow
        if self._graphics_enabled and not root.common.disable.plotting:
            from .graphics import GraphicsServer
            self.graphics_server = GraphicsServer()
            workflow.graphics = self.graphics_server
            # per-run default dir: a shared cache/plots would let the
            # newest-by-mtime gallery pick up a CONCURRENT run's PNGs
            # and misattribute them on the drill-down page
            plots_dir = self._plots_dir or os.path.join(
                root.common.dirs.cache, "plots",
                "%s@%d" % (getattr(workflow, "name", "wf"), os.getpid()))
            self.graphics_server.launch_client(out_dir=plots_dir)
        workflow.initialize(device=self.device)
        distributed.verify_checksums(workflow)
        self._arm_failure_hooks(workflow)
        if self._status_url and distributed.is_coordinator():
            from .web_status import StatusReporter
            self.status_reporter = StatusReporter(
                self._status_url, self._notification_interval)
            self.status_reporter.start_periodic(self._status_payload)
        if self.test_mode:
            self._enter_test_mode(workflow)
        self.event("launcher.initialize", "single",
                   device=self.device.name,
                   processes=distributed.process_count())

    def _enter_test_mode(self, workflow) -> None:
        """--test: one evaluation-only pass — no parameter updates
        (reference test mode, veles/launcher.py mode resolution)."""
        step = getattr(workflow, "train_step", None)
        decision = getattr(workflow, "decision", None)
        if step is not None:
            step.evaluation_mode = True
        if decision is not None:
            decision.max_epochs = decision.epoch_number + 1

    def _arm_failure_hooks(self, workflow) -> None:
        """Production wiring of the failure story (SURVEY.md §5.3): every
        TrainStep dispatch runs under the hang watchdog (the reference's
        job-timeout dropper, veles/server.py:619-635, as a local monitor),
        passes the ``dispatch`` fault-injection point, beats the health
        registry, and — when --slave-death-probability is set — rolls the
        legacy fault-injection die (veles/client.py:303-307)."""
        step = getattr(workflow, "train_step", None)
        if step is None or getattr(step, "_failure_hooks_armed", False):
            return
        from .resilience import elastic
        from .resilience.faults import fire as fire_fault
        from .resilience.health import heartbeats
        death_p = float(
            root.common.get("slave_death_probability", 0.0) or 0.0)
        timeout = float(root.common.get("job_timeout", 0.0) or 0.0)
        elastic_on = elastic.enabled()
        host_beat = None
        if elastic_on:
            try:
                import jax
                host_beat = (elastic.HOST_BEAT_PREFIX
                             + str(jax.process_index()))
            except Exception:         # noqa: BLE001 — numpy backend
                host_beat = elastic.HOST_BEAT_PREFIX + "0"
        #: run()'s finally unregisters it — a completed run's host beat
        #: must not age into a false /healthz failure on a process that
        #: keeps serving
        self._host_beat = host_beat
        self.step_history = []      # per-dispatch wall times (telemetry)
        inner_run = step.run

        def armed_run():
            fire_fault("dispatch")
            if elastic_on:
                # elastic plane: this host's liveness beat + one
                # host-loss probe per dispatch (injected faults and
                # lapsed host:* heartbeats raise HostLostError, which
                # ends the generation — resilience/elastic.py)
                heartbeats.beat(host_beat)
                elastic.check_hosts()
            with distributed.step_watchdog(
                    step.name, timeout=timeout, history=self.step_history):
                inner_run()
            heartbeats.beat("train_step")
            if death_p > 0:
                distributed.fault_injection(death_p)
        step.run = armed_run
        step._failure_hooks_armed = True

    def try_restore_latest(self) -> bool:
        """Elastic restart: resume from the newest snapshot in the
        configured snapshot directory, if any (preemption/crash recovery —
        the reference's 'recover from any disaster' story,
        docs/manualrst_veles_distributed_training.rst:10)."""
        wf = self.workflow
        directory, prefix = root.common.dirs.snapshots, "wf"
        from .snapshotter import Snapshotter, SnapshotterToDB, resume
        snap_unit = None
        for u in getattr(wf, "units", ()):
            if isinstance(u, Snapshotter):
                snap_unit = u
                directory, prefix = u.directory, u.prefix
                break
        if snap_unit is None:
            # restoring works off bare directory contents, but WRITING
            # needs a Snapshotter unit: a user running with
            # --snapshot-dir and none linked thinks they have disaster
            # recovery and doesn't
            self.warning(
                "workflow %r has no Snapshotter unit — snapshots will "
                "NOT be written this run; link "
                "vt.Snapshotter(None, prefix=...) (directory defaults "
                "to the --snapshot-dir / root.common.dirs.snapshots "
                "setting) via StandardWorkflow(snapshotter_unit=...)",
                getattr(wf, "name", "?"))
        if isinstance(snap_unit, SnapshotterToDB):
            # DB sink: newest row in the sqlite store
            dsn = snap_unit._resolve_dsn()
            if not os.path.exists(dsn):
                return False
            try:
                resume(wf, "sqlite://" + dsn)
            except FileNotFoundError:
                return False
        else:
            if not directory or not os.path.isdir(directory):
                return False
            if not distributed.restore_latest(wf, directory, prefix):
                return False
        decision = getattr(wf, "decision", None)
        if decision is not None:
            decision.complete <<= False
        #: where the chain lives — the elastic controller logs the
        #: manifest cursor of this chain at generation handoffs
        self._last_restore_dir = directory
        self._last_restore_prefix = prefix
        self.info("auto-resumed from latest snapshot in %s", directory)
        return True

    def resume(self, snapshot_path: str) -> None:
        from .resilience.checkpoint_chain import SnapshotCorruptError
        from .snapshotter import resume
        try:
            resume(self.workflow, snapshot_path)
        except (FileNotFoundError, SnapshotCorruptError) as e:
            # elastic rerun idempotency: resuming via the `_current`
            # link after the previous run quarantined its target (the
            # link dangles, or points at a not-yet-quarantined corrupt
            # file) must skip straight to the older valid snapshot in
            # the same chain instead of killing the relaunch
            base = os.path.basename(snapshot_path)
            if "_current.pickle" not in base:
                raise
            prefix = base.split("_current.pickle")[0]
            directory = os.path.dirname(snapshot_path) or "."
            self.warning(
                "snapshot link %s is unusable (%s: %s) — falling back "
                "to the newest valid snapshot of chain %r in %s",
                snapshot_path, type(e).__name__, e, prefix, directory)
            from .resilience.checkpoint_chain import (
                restore_latest as walk)
            restored = walk(self.workflow, directory, prefix)
            if restored is None:
                raise
            self._last_restore_dir = directory
            self._last_restore_prefix = prefix
            snapshot_path = restored   # log the REAL source, not the
            # dead link — quarantine forensics must name the snapshot
            # the run actually resumed from
        decision = getattr(self.workflow, "decision", None)
        if decision is not None:
            decision.complete <<= False
        self.info("resumed from %s", snapshot_path)

    def run_elastic(self) -> Dict[str, Any]:
        """Run under the elastic generation controller
        (resilience/elastic.py): on detected host loss the run resumes
        from the newest valid checkpoint in a new generation instead
        of dying — ``--elastic`` /
        ``root.common.resilience.elastic.enabled``."""
        from .resilience.elastic import ElasticController
        return ElasticController(self).run()

    def run(self, keep_services: bool = False) -> Dict[str, Any]:
        """``keep_services=True`` (elastic generations) defers the
        plotter/graphics/status teardown to :meth:`finalize_services`
        — generation 2..N must keep the dashboard and beacon alive,
        not train against services generation 1's finally killed."""
        from .resilience.health import heartbeats
        from .telemetry.recorder import flight
        # preemption forensics: a SIGTERM (the k8s/preemption kill)
        # dumps the flight recorder before the previous disposition
        # runs — only when autodump is armed (crash_dump gates itself)
        if flight.autodump_enabled():
            flight.install_sigterm()
        self._start_time = time.time()
        heartbeats.beat("launcher")
        self.event("launcher.work", "begin")
        profiling = False
        if self._profile_dir:
            try:
                import jax
                jax.profiler.start_trace(self._profile_dir)
                profiling = True
                self.info("profiler trace → %s", self._profile_dir)
            except Exception as e:
                self.warning("profiler unavailable: %s", e)
        try:
            self.workflow.run()
        except KeyboardInterrupt:
            self.warning("interrupted — stopping workflow")
            self.workflow.stop()
            self.interrupted = True
        finally:
            if profiling:
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception as e:
                    self.warning("profiler stop failed: %s", e)
            self.event("launcher.work", "end")
            self.stopped = True
            if not keep_services:
                self.finalize_services()
            # the run is over (completed OR raised) — these beats are
            # not hangs; leaving them registered would age into a false
            # /healthz failure on any long-lived process
            heartbeats.unregister("launcher")
            heartbeats.unregister("train_step")
            if getattr(self, "_host_beat", None):
                heartbeats.unregister(self._host_beat)
        elapsed = time.time() - self._start_time
        self.info("elapsed: %.1fs", elapsed)
        results = self.workflow.gather_results()
        results["elapsed_sec"] = round(elapsed, 3)
        if self.interrupted:
            results["interrupted"] = True
        return results

    def finalize_services(self) -> None:
        """Final plot redraws, graphics shutdown, last status beacon —
        the once-per-JOB half of run()'s teardown. Idempotent: the
        elastic controller calls it after the last generation."""
        from .plotter import Plotter
        for u in getattr(self.workflow, "units", ()):
            if isinstance(u, Plotter):
                try:
                    u.finalize()
                except Exception as e:   # noqa: BLE001 — best effort
                    self.warning("final redraw of %s failed: %s",
                                 u.name, e)
        if self.graphics_server is not None:
            self.graphics_server.shutdown()
            self.graphics_server = None
        if self.status_reporter is not None:
            self.status_reporter.send(self._status_payload())
            self.status_reporter.stop()
            self.status_reporter = None

    def stop(self) -> None:
        if self.workflow is not None:
            self.workflow.stop()
        self.stopped = True

    def _status_payload(self) -> Dict[str, Any]:
        """Beacon body (reference: veles/launcher.py:852-885)."""
        wf = self.workflow
        decision = getattr(wf, "decision", None)
        metric = None
        if decision is not None:
            try:
                values = decision.get_metric_values()
                for key in ("best_err", "best_rmse", "err", "rmse"):
                    if key in values:
                        metric = values[key]
                        break
            except Exception:
                metric = None
        payload = {
            "id": "%s@%d" % (getattr(wf, "name", "?"), os.getpid()),
            "name": getattr(wf, "name", "?"),
            "device": getattr(self.device, "name", None),
            "epoch": getattr(decision, "epoch_number", None),
            "metric": metric,
            "elapsed_sec": (round(time.time() - self._start_time, 1)
                            if self._start_time else 0.0),
            "stopped": self.stopped,
        }
        # drill-down detail (reference: the web/ app's per-master pages
        # served unit tables and event/log views, veles/web_status.py:
        # 66-111): per-unit timing, recent event spans, and the latest
        # rendered plots ride the same stateless beacon
        try:
            payload["units"] = [
                {"name": n, "cls": c, "runs": r, "time_s": round(t, 4)}
                for t, n, c, r in sorted(
                    ((u.timers.get("run", 0.0), u.name,
                      type(u).__name__, u.run_count) for u in wf),
                    reverse=True)[:40]]
        except Exception:       # a half-built workflow must not kill
            pass                # the beacon thread
        from .logger import events
        payload["events"] = [
            {"name": e.get("name"), "type": e.get("type"),
             "time": e.get("time"), "who": e.get("who")}
            for e in events()[-60:]]
        plots = self._plot_payload()
        if plots is not None:
            payload["plots"] = plots
        return payload

    def _plot_payload(self, max_plots: int = 6,
                      max_bytes: int = 150_000):
        """Newest rendered plot PNGs, inlined base64 so the dashboard
        works across hosts (the reference backed its gallery with
        Mongo-stored blobs for the same reason). Returns None when the
        plot set is unchanged since the last beacon — the key is then
        omitted and the server carries the previous gallery forward,
        so steady-state ticks don't re-ship megabytes of identical
        PNGs. Every REFRESH_EVERY-th beacon re-ships regardless: the
        signature lives launcher-side, so a restarted web-status server
        (carried-forward state lost) would otherwise show an empty
        gallery until some plot file changed (ADVICE r4)."""
        import base64
        import glob as _glob

        REFRESH_EVERY = 10
        self._plot_beacons = getattr(self, "_plot_beacons", -1) + 1
        force = self._plot_beacons % REFRESH_EVERY == 0

        def mtime(p):
            # the renderer rewrites files concurrently: a vanished path
            # must not kill the beacon thread via the sort key
            try:
                return os.path.getmtime(p)
            except OSError:
                return 0.0

        gs = self.graphics_server
        out_dir = getattr(gs, "out_dir", None) if gs is not None else None
        if not out_dir or not os.path.isdir(out_dir):
            pngs = []
        else:
            pngs = sorted(_glob.glob(os.path.join(out_dir, "*.png")),
                          key=mtime, reverse=True)[:max_plots]
        signature = tuple((p, mtime(p)) for p in pngs)
        if not force and \
                signature == getattr(self, "_plot_signature", None):
            return None
        self._plot_signature = signature
        out = []
        for p in pngs:
            try:
                if os.path.getsize(p) > max_bytes:
                    continue
                with open(p, "rb") as fin:
                    out.append({
                        "name": os.path.basename(p),
                        "png_b64": base64.b64encode(
                            fin.read()).decode()})
            except OSError:
                continue
        return out

    # -- reporting -----------------------------------------------------------
    def write_results(self, results: Dict[str, Any], path: str) -> None:
        """--result-file (reference: veles/workflow.py:827-849)."""
        if not distributed.is_coordinator():
            return
        from .json_encoders import NumpyJSONEncoder
        with open(path, "w") as fout:
            json.dump(results, fout, indent=2, cls=NumpyJSONEncoder)
        self.info("results → %s", path)

    def print_stats(self) -> None:
        self.workflow.print_stats()
