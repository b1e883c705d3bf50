"""Parallel trial scheduler: the job farm under GA / ensemble search.

The reference farmed chromosome evaluations and ensemble members out as
master–slave jobs over its ZeroMQ server (veles/genetics/
optimization_workflow.py:70, veles/ensemble/model_workflow.py:137,
veles/server.py job protocol). TPU-first redesign (SURVEY.md §2.4
"ensemble/GA parallelism → trial scheduler over TPU slices"): a trial
is one OS subprocess running the normal CLI; a fixed pool of worker
SLOTS runs up to ``n_workers`` trials concurrently; a *placement hook*
maps each slot to the environment that pins its device resources:

- ``cpu_placement`` (default): every slot gets its own single-device
  XLA:CPU platform — correctness fan-out on any host, including CI.
- ``mesh_slice_placement(...)``: slots map onto disjoint accelerator
  slices via env (TPU_VISIBLE_CHIPS on multi-chip hosts). Drilled once
  on a four-chip TPU v5 lite host (PR 21, CHANGES.md): two concurrent
  children under ``mesh_slice_placement(1, 4)`` and under ``(2, 4)`` each
  enumerated exactly their own 1 resp. 2 ``tpu`` devices and exited 0.
  No trial has trained there yet, and no test runs this on a chip.

Trials never share a process with the scheduler (device state isolation
— the reference's exact reason for slave processes), and an overrunning
or crashing trial is killed by process group and reported, never
propagated.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import threading
import time
from queue import Queue
from typing import Callable, Dict, List, Optional, Sequence

from ..logger import Logger


def cpu_placement(slot: int) -> Dict[str, str]:
    """One private XLA:CPU device per worker slot. Strips any forced
    host-device-count (the test harness exports 8) so concurrent trials
    don't each spin up 8 virtual devices' worth of threads."""
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(t for t in flags.split()
                     if "xla_force_host_platform_device_count" not in t)
    return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
            # slots must not fight over host cores via intra-op pools
            "XLA_CPU_MULTI_THREAD_EIGEN": "false"}


def mesh_slice_placement(devices_per_trial: int = 1,
                         total_devices: Optional[int] = None
                         ) -> Callable[[int], Dict[str, str]]:
    """Placement hook for real multi-chip hosts: slot *i* sees chips
    ``[i*d, (i+1)*d)`` via TPU_VISIBLE_CHIPS, so trials train on
    disjoint slices of one host's chips concurrently (the TPU analog of
    the reference's one-job-per-slave placement)."""
    def place(slot: int) -> Dict[str, str]:
        d = int(devices_per_trial)
        chips = range(slot * d, (slot + 1) * d)
        if total_devices is not None and chips[-1] >= total_devices:
            raise ValueError(
                "slot %d needs chips %s but only %d exist"
                % (slot, list(chips), total_devices))
        return {"TPU_VISIBLE_CHIPS": ",".join(map(str, chips)),
                # bounds must cover the d visible chips (flat topology);
                # a 1,1,1 bound would contradict a multi-chip slice
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "%d,1,1" % d}
    return place


def run_json_trials(make_argv, n: int, n_workers: int,
                    placement: Optional[Callable[[int],
                                                 Dict[str, str]]] = None,
                    timeout: Optional[float] = None,
                    tags: Optional[Sequence[object]] = None):
    """Run ``n`` CLI trials that each write a JSON result file; returns
    ``[(TrialResult, parsed_json_or_None), ...]`` in submission order.

    ``make_argv(i, result_file) -> argv``. Owns the whole result-file
    lifecycle (mkstemp, guarded parse, unlink) so every caller — GA
    generations, ensemble members — shares one failure contract: a
    trial whose process failed OR whose result file is unreadable
    yields ``doc=None`` and never raises."""
    import json
    import tempfile
    result_files, trials = [], []
    for i in range(n):
        fd, rf = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        result_files.append(rf)
        trials.append(Trial(argv=make_argv(i, rf),
                            tag=tags[i] if tags else i, timeout=timeout))
    sched = TrialScheduler(n_workers=n_workers,
                           placement=placement or cpu_placement)
    try:
        out = []
        for res, rf in zip(sched.run(trials), result_files):
            doc = None
            if res.ok:
                try:
                    with open(rf) as fin:
                        doc = json.load(fin)
                except (ValueError, OSError):
                    doc = None      # rc=0 but no usable result: caller
                    # treats it exactly like a failed trial
            out.append((res, doc))
        return out
    finally:
        for rf in result_files:
            try:
                os.unlink(rf)
            except OSError:
                pass


@dataclasses.dataclass
class Trial:
    """One unit of farmed work: an argv command plus per-trial env."""
    argv: Sequence[str]
    tag: object = None
    env: Optional[Dict[str, str]] = None
    timeout: Optional[float] = None


@dataclasses.dataclass
class TrialResult:
    tag: object
    returncode: int
    stderr_tail: str
    elapsed: float
    slot: int
    timed_out: bool = False

    @property
    def ok(self) -> bool:
        return self.returncode == 0 and not self.timed_out


class TrialScheduler(Logger):
    """Run trials with bounded concurrency and per-slot placement.

    ``run`` preserves submission order in its result list; a failed or
    overrunning trial yields a TrialResult with ``ok == False`` (killed
    by process group) and never raises — one divergent candidate must
    not take down a whole generation (same contract the reference's
    job farm kept, veles/server.py:315-338 slave-death handling).
    """

    def __init__(self, n_workers: Optional[int] = None,
                 placement: Callable[[int], Dict[str, str]] = cpu_placement,
                 timeout: Optional[float] = None) -> None:
        super().__init__()
        if n_workers is None:
            n_workers = min(4, os.cpu_count() or 1)
        self.n_workers = int(n_workers)
        if self.n_workers < 1:
            raise ValueError("n_workers must be >= 1")
        self.placement = placement
        self.timeout = timeout

    def _run_one(self, trial: Trial, slot: int) -> TrialResult:
        env = dict(os.environ)
        env.update(self.placement(slot))
        if trial.env:
            env.update(trial.env)
        t0 = time.time()
        timeout = trial.timeout or self.timeout
        timed_out = False
        proc = subprocess.Popen(
            list(trial.argv), env=env, text=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            start_new_session=True)     # killable with its children
        try:
            _, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                proc.kill()
            _, err = proc.communicate()
        return TrialResult(tag=trial.tag, returncode=proc.returncode,
                           stderr_tail=(err or "")[-2000:],
                           elapsed=time.time() - t0, slot=slot,
                           timed_out=timed_out)

    def run(self, trials: Sequence[Trial]) -> List[TrialResult]:
        trials = list(trials)
        # placement misconfiguration (e.g. a slice past the host's last
        # chip) is a caller error and must raise BEFORE any trial runs,
        # not surface as N per-trial "failures"; only slots that can
        # ever be taken are validated (returned slots re-enter at the
        # queue tail, so indices ≥ the worker count never circulate)
        for s in range(min(self.n_workers, len(trials))):
            self.placement(s)
        results: List[Optional[TrialResult]] = [None] * len(trials)
        slots: Queue = Queue()
        for s in range(self.n_workers):
            slots.put(s)
        pending: Queue = Queue()
        for i, t in enumerate(trials):
            pending.put((i, t))

        def worker() -> None:
            while True:
                try:
                    i, trial = pending.get_nowait()
                except Exception:
                    return
                slot = slots.get()
                try:
                    res = self._run_one(trial, slot)
                except Exception as exc:   # spawn failure: report, go on
                    res = TrialResult(tag=trial.tag, returncode=-1,
                                      stderr_tail=str(exc), elapsed=0.0,
                                      slot=slot)
                finally:
                    slots.put(slot)
                if not res.ok:
                    self.warning(
                        "trial %r failed (rc=%s%s): %s", trial.tag,
                        res.returncode,
                        ", timed out" if res.timed_out else "",
                        res.stderr_tail[-500:])
                results[i] = res

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(min(self.n_workers, len(trials)))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for i, r in enumerate(results):
            if r is None:      # worker thread died outside _run_one
                results[i] = TrialResult(
                    tag=trials[i].tag, returncode=-1,
                    stderr_tail="worker thread died", elapsed=0.0,
                    slot=-1)
        return results  # type: ignore[return-value]
