"""Flight recorder: the crash black box.

The reference's failure story ended at a traceback; under the
north-star's traffic a crash, a watchdog trip or a NaN-poisoned model
needs *forensics* — what the process was doing in the seconds before it
died. This module keeps a bounded, thread-safe ring (default 4096
events) subscribed to the observability surfaces that already exist:

- **span closes** (:mod:`~veles_tpu.telemetry.spans` close hook) —
  every completed ``unit.run`` / ``workflow.run`` / decode span;
- **alarm-counter increments** (:mod:`~veles_tpu.telemetry.counters`
  inc hook) — fault injections, watchdog trips, shed requests,
  snapshot quarantines, side-plane task errors, model NaNs — plus any
  single increment over ``root.common.telemetry.recorder.
  counter_threshold`` (byte bursts);
- **logger events** (:mod:`veles_tpu.logger` event hook) — workflow
  begin/end, snapshot commits, launcher transitions;
- **health transitions** and **tensormon samples** — noted explicitly
  by :mod:`~veles_tpu.resilience.health` / :mod:`~veles_tpu.telemetry.
  tensormon`.

On an unhandled ``Workflow.run`` exception, a ``step_watchdog`` trip
or SIGTERM (and always on a NaN-sentinel halt) the ring dumps to
``blackbox-<ts>_<pid>.jsonl`` next to the snapshot directory;
``veles-tpu blackbox dump|inspect`` writes/reads it back. Crash-path
dumps honor ``root.common.telemetry.recorder.autodump`` (default off —
test suites raise through ``Workflow.run`` on purpose all the time).

NOTE on naming: ``veles_tpu.telemetry.recorder`` the *module* (this
file) is distinct from ``veles_tpu.telemetry.recorder`` the *package
attribute*, which stays bound to the span recorder instance for
backward compatibility (``telemetry/__init__.py`` import order).
Always import this module by full path::

    from veles_tpu.telemetry.recorder import flight, FlightRecorder
"""

from __future__ import annotations

import collections
import json
import logging
import os
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

from ..config import root
# direct from-imports, not `from . import counters`: the package
# __init__ rebinds the `counters`/`recorder` package attributes to the
# registry/span-recorder instances, so module-attribute access through
# the package is unreliable during (and after) package init
from .counters import add_inc_hook as _add_inc_hook
from .counters import inc as _counter_inc
from .spans import SPAN_HISTOGRAMS as _SPAN_HISTOGRAMS
from .spans import add_close_hook as _add_close_hook
# the ONE request-correlation predicate (spans.py owns it), re-
# exported here because `blackbox inspect --request` is its flight-
# recorder face: a crashed replica's dump cross-references a merged
# fleet trace by either request_id or trace_id
from .spans import matches_request                    # noqa: F401

#: default ring capacity (events)
DEFAULT_CAPACITY = 4096

#: counters whose EVERY increment is a flight-recorder event — the
#: "something went wrong" set; ordinary accounting counters
#: (dispatches, bytes) only record above ``counter_threshold``
ALARM_COUNTERS = frozenset((
    "veles_faults_injected_total",
    "veles_watchdog_trips_total",
    "veles_shed_requests_total",
    "veles_snapshots_quarantined_total",
    "veles_sideplane_errors_total",
    "veles_model_nan_total",
    "veles_model_health_errors_total",
))



#: cached config NODE (not values): the auto-vivified node object is
#: stable, so caching it turns the per-event attribute traversal into
#: one dict lookup while config writes stay immediately visible —
#: these lookups sit on the span-close and counter-inc hot paths
_cfg_node = None


def _cfg(name: str, default):
    global _cfg_node
    try:
        if _cfg_node is None:
            _cfg_node = root.common.telemetry.recorder
        return _cfg_node.get(name, default)
    except Exception:        # noqa: BLE001 — config not importable
        return default


class FlightRecorder:
    """Bounded, thread-safe ring of observability events + dump."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 follow_config: bool = False) -> None:
        self._lock = threading.Lock()
        self._ring: Deque[Dict[str, Any]] = collections.deque(
            maxlen=int(capacity))
        self._recorded = 0
        self._sigterm_installed = False
        #: True only on the process-global instance: tracks the
        #: root.common.telemetry.recorder.capacity knob (explicit
        #: capacities — tests — stay fixed)
        self._follow_config = follow_config

    # -- recording -----------------------------------------------------------
    def enabled(self) -> bool:
        return bool(_cfg("enabled", True))

    def note(self, kind: str, **data: Any) -> None:
        """Append one event to the ring (newest wins once full)."""
        if not self.enabled():
            return
        rec = {"kind": kind, "t": time.time()}
        rec.update(data)
        with self._lock:
            if self._follow_config:
                # honor a changed capacity knob (the global instance
                # is constructed at import, before any config lands)
                want = int(_cfg("capacity", self._ring.maxlen)
                           or self._ring.maxlen)
                if want > 0 and want != self._ring.maxlen:
                    self._ring = collections.deque(self._ring,
                                                   maxlen=want)
            self._ring.append(rec)
            self._recorded += 1

    def records(self, kind: Optional[str] = None) -> List[Dict[str, Any]]:
        with self._lock:
            recs = list(self._ring)
        if kind is not None:
            recs = [r for r in recs if r.get("kind") == kind]
        return recs

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"recorded": self._recorded,
                    "buffered": len(self._ring),
                    "capacity": self._ring.maxlen}

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._recorded = 0

    # -- dumping -------------------------------------------------------------
    def dump(self, reason: str, directory: Optional[str] = None,
             path: Optional[str] = None) -> str:
        """Write the ring as ``blackbox-<ts>_<pid>.jsonl`` (header line
        first) into ``directory`` (default: the snapshot dir, so the
        forensics land next to the checkpoints they explain). Atomic
        tmp-write + fsync + rename, like the checkpoint chain."""
        from ..resilience.faults import fire as fire_fault
        # the `recorder.dump` injection point: raise/crash exercise the
        # "black box itself fails" path, corrupt damages the dump bytes
        fault = fire_fault("recorder.dump")
        with self._lock:
            events = list(self._ring)
        if path is None:
            if directory is None:
                directory = str(root.common.dirs.snapshots)
            os.makedirs(directory, exist_ok=True)
            base = os.path.join(directory, "blackbox-%s_%d" % (
                time.strftime("%Y%m%d_%H%M%S"), os.getpid()))
            # 1s timestamp resolution: a second dump in the same
            # second (watchdog trip then crash) must not os.replace
            # the first's forensics away
            path, n = base + ".jsonl", 1
            while os.path.exists(path):
                n += 1
                path = "%s-%d.jsonl" % (base, n)
        else:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        header = {"kind": "blackbox.header", "reason": reason,
                  "t": time.time(), "pid": os.getpid(),
                  "events": len(events)}
        payload = "\n".join(json.dumps(r, default=str)
                            for r in [header] + events) + "\n"
        data = payload.encode()
        if fault is not None:
            data = fault.corrupt(data)
        tmp = path + ".tmp"
        with open(tmp, "wb") as fout:
            fout.write(data)
            fout.flush()
            os.fsync(fout.fileno())
        os.replace(tmp, path)
        _counter_inc("veles_blackbox_dumps_total")
        logging.getLogger("veles_tpu.telemetry").warning(
            "flight recorder black box -> %s (%d events; reason: %s)",
            path, len(events), reason)
        return path

    def autodump_enabled(self) -> bool:
        return bool(_cfg("autodump", False))

    def crash_dump(self, reason: str) -> Optional[str]:
        """The crash-path dump: a no-op unless ``autodump`` is armed,
        and NEVER raises — the black box must not mask the crash it is
        documenting."""
        if not self.autodump_enabled():
            return None
        try:
            return self.dump(reason)
        except Exception as e:        # noqa: BLE001 — see docstring
            logging.getLogger("veles_tpu.telemetry").warning(
                "flight recorder dump failed (%s: %s)",
                type(e).__name__, e)
            return None

    # -- SIGTERM -------------------------------------------------------------
    def install_sigterm(self) -> bool:
        """Chain a SIGTERM handler that crash-dumps before the previous
        disposition runs (preemption forensics). Main thread only;
        returns True when installed."""
        if self._sigterm_installed:
            return True
        import signal
        if threading.current_thread() is not threading.main_thread():
            return False
        prev = signal.getsignal(signal.SIGTERM)

        def handler(signum, frame):
            self.crash_dump("SIGTERM")
            if callable(prev):
                prev(signum, frame)
            elif prev is signal.SIG_IGN:
                return          # keep the previously-ignored fate
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        try:
            signal.signal(signal.SIGTERM, handler)
        except (ValueError, OSError):   # non-main thread / exotic host
            return False
        self._sigterm_installed = True
        return True


#: THE process-global flight recorder (mirrors counters.counters)
flight = FlightRecorder(follow_config=True)


# -- black-box file access ----------------------------------------------------

def read_blackbox(path: str) -> Tuple[Optional[Dict[str, Any]],
                                      List[Dict[str, Any]]]:
    """(header, events) from a black-box dump; malformed lines are
    skipped (a dump written mid-crash may be torn)."""
    header: Optional[Dict[str, Any]] = None
    events: List[Dict[str, Any]] = []
    # errors="replace": a dump torn/corrupted mid-crash may carry
    # invalid UTF-8 — the readable lines must still come back
    with open(path, errors="replace") as fin:
        for line in fin:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not isinstance(rec, dict):
                continue
            if rec.get("kind") == "blackbox.header" and header is None:
                header = rec
            else:
                events.append(rec)
    return header, events


def inspect(path: str, request: Optional[str] = None
            ) -> Dict[str, Any]:
    """Summary of a black-box dump: reason, event count, per-kind
    counts, covered time range — what ``veles-tpu blackbox inspect``
    prints. ``request`` narrows the view to one request's events
    (request_id or trace_id — ``blackbox inspect --request ID``): the
    crashed replica's last seconds for exactly the request a fleet
    trace says died there."""
    header, events = read_blackbox(path)
    total = len(events)
    if request is not None:
        events = [e for e in events if matches_request(e, request)]
    by_kind: Dict[str, int] = {}
    for rec in events:
        kind = str(rec.get("kind", "?"))
        by_kind[kind] = by_kind.get(kind, 0) + 1
    times = [r["t"] for r in events if isinstance(r.get("t"), (int, float))]
    out = {
        "path": path,
        "reason": (header or {}).get("reason"),
        "dumped_at": (header or {}).get("t"),
        "pid": (header or {}).get("pid"),
        "events": len(events),
        "by_kind": by_kind,
        "span_seconds": (round(max(times) - min(times), 3)
                         if len(times) > 1 else 0.0),
    }
    if request is not None:
        out["request"] = str(request)
        out["events_total"] = total
    return out


# -- subscriptions ------------------------------------------------------------

def _on_counter(name: str, value: float, total: float) -> None:
    if name in ALARM_COUNTERS:
        flight.note("counter", counter=name, delta=value, total=total)
        return
    thr = _cfg("counter_threshold", 0)
    if thr and value >= float(thr):
        flight.note("counter", counter=name, delta=value, total=total)


def _on_span_close(rec: Dict[str, Any]) -> None:
    if rec.get("name") in _SPAN_HISTOGRAMS:
        # the phases of a serving tick close nine times a tick; their
        # histograms keep them, and the black box keeps its horizon
        return
    ev = {"name": rec.get("name"), "dur": rec.get("dur"),
          "tid": rec.get("tid")}
    for key in ("unit", "workflow", "error", "steps", "counters"):
        if key in rec:
            ev[key] = rec[key]
    flight.note("span", **ev)


def _on_event(rec: Dict[str, Any]) -> None:
    flight.note("event", **{k: v for k, v in rec.items() if k != "t"})


_add_inc_hook(_on_counter)
_add_close_hook(_on_span_close)

# logger events: imported lazily-but-once here; veles_tpu.logger is a
# leaf module (no telemetry imports), so this cannot cycle
from ..logger import add_event_hook as _add_event_hook  # noqa: E402

_add_event_hook(_on_event)
