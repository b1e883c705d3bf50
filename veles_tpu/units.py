"""Unit: the dataflow node of the framework.

Equivalent of the reference's veles/units.py:59-927 (IUnit/Unit contract:
control links, gates, attribute links, demand, lifecycle) — with one
deliberate architectural change (SURVEY.md §7): in the reference, the unit
graph IS the per-minibatch dispatch engine (every unit's ``run`` enqueues a
GPU kernel from a thread pool, veles/units.py:782-505). On TPU that would
defeat XLA: here the unit graph is the *authoring and orchestration* layer.
Units whose work is on-device declare pure functions that the workflow traces
into one jitted SPMD step; the gate/link machinery below runs in plain Python
*between* steps (epoch logic, decisions, snapshots, plotting).

Gate semantics preserved from the reference (veles/units.py:139-141,280-308,
524-552):
- ``gate_block``   — when True the unit neither runs nor propagates;
- ``gate_skip``    — when True the unit does not run but still propagates;
- ``ignores_gate`` — run as soon as any upstream fires, not all.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Set, Tuple

from .config import root
from .error import BadUnitLink, Bug
from .logger import Logger
from .mutable import Bool, LinkableAttribute


class UnitRegistry(type):
    """Metaclass census of every unit class, for introspection, the CLI
    frontend and the forge (reference: veles/unit_registry.py:51)."""

    units: Set[type] = set()
    #: name → class for units registered with ``MAPPING``
    mapping: Dict[str, type] = {}

    def __init__(cls, name, bases, clsdict):
        super().__init__(name, bases, clsdict)
        if not clsdict.get("hide_from_registry", False):
            UnitRegistry.units.add(cls)
        mapping = clsdict.get("MAPPING")
        if mapping:
            existing = UnitRegistry.mapping.get(mapping)
            if existing is not None and existing.__name__ != name:
                raise Bug("duplicate unit MAPPING %r (%s vs %s)" %
                          (mapping, existing.__name__, name))
            UnitRegistry.mapping[mapping] = cls


class Unit(Logger, metaclass=UnitRegistry):
    """A node in a Workflow graph (reference: veles/units.py:108)."""

    hide_from_registry = True

    #: a unit whose ``run`` only EMITS (plots, reports, saved images,
    #: status pushes) and is never read back by the compute path may
    #: declare True: with the overlap engine on (root.common.overlap.
    #: enabled, docs/overlap.md) the scheduler dispatches its run to
    #: the async side-plane instead of blocking the step loop. Gate
    #: evaluation and downstream propagation stay inline either way —
    #: only the run body moves off-thread, so scheduling decisions are
    #: bit-identical with overlap on or off.
    side_effect_only = False

    def __init__(self, workflow, **kwargs) -> None:
        super().__init__()
        self.name: str = kwargs.pop("name", type(self).__name__)
        self.view_group: str = kwargs.pop("view_group", "PLUMBING")
        self.gate_block = Bool(False)
        self.gate_skip = Bool(False)
        self.ignores_gate = Bool(kwargs.pop("ignores_gate", False))
        #: upstream control edges: unit → fired flag
        self.links_from: Dict["Unit", bool] = {}
        #: downstream control edges
        self.links_to: Set["Unit"] = set()
        self._demanded: Set[str] = set()
        self._initialized = False
        self.timers: Dict[str, float] = {"run": 0.0}
        self.run_count = 0
        self.workflow = workflow
        if workflow is not None:
            workflow.add_ref(self)

    # -- graph wiring -------------------------------------------------------
    def link_from(self, *units: "Unit") -> "Unit":
        """Add control edges ``unit → self``
        (reference: veles/units.py:554)."""
        for u in units:
            if u is self:
                raise BadUnitLink("%s: cannot link to itself" % self.name)
            self.links_from[u] = False
            u.links_to.add(self)
        return self

    def unlink_from(self, *units: "Unit") -> "Unit":
        for u in units:
            self.links_from.pop(u, None)
            u.links_to.discard(self)
        return self

    def unlink_all(self) -> None:
        for u in list(self.links_from):
            self.unlink_from(u)
        for u in list(self.links_to):
            u.unlink_from(self)

    def link_attrs(self, other: "Unit",
                   *mappings: Any, two_way: bool = False) -> "Unit":
        """Alias attributes of ``other`` into self: each mapping is either
        ``"attr"`` or ``("my_attr", "their_attr")``
        (reference: veles/units.py:638)."""
        for m in mappings:
            mine, theirs = (m, m) if isinstance(m, str) else m
            LinkableAttribute.link(self, mine, other, theirs,
                                   two_way=two_way)
        return self

    def demand(self, *attrs: str) -> None:
        """Declare attributes that must be present (non-None) by initialize
        time (reference: veles/units.py:682)."""
        self._demanded.update(attrs)

    # -- lifecycle ----------------------------------------------------------
    @property
    def is_initialized(self) -> bool:
        return self._initialized

    def verify_demands(self) -> List[str]:
        return [a for a in sorted(self._demanded)
                if getattr(self, a, None) is None]

    def initialize(self, **kwargs) -> Optional[bool]:
        """Prepare to run. Return True to request re-queue after the rest of
        the graph initializes (partial init, reference
        veles/workflow.py:331-336)."""
        missing = self.verify_demands()
        if missing:
            self.debug("%s: waiting for demanded attrs %s", self.name,
                       missing)
            return True
        self._initialized = True
        return None

    def run(self) -> None:  # pragma: no cover - abstract
        """One unit of work. Runs between jitted steps, in Python."""

    def stop(self) -> None:
        """Cooperative cancellation hook."""

    # -- gate machinery (reference: veles/units.py:524-552,782-803) ---------
    def open_gate(self, src: "Unit") -> bool:
        """Record that ``src`` fired; True when self may proceed."""
        if src not in self.links_from:
            raise Bug("%s notified by non-upstream %s" % (self.name,
                                                          src.name))
        self.links_from[src] = True
        if bool(self.ignores_gate):
            self._reset_fired()
            return True
        if all(self.links_from.values()):
            self._reset_fired()
            return True
        return False

    def _reset_fired(self) -> None:
        for k in self.links_from:
            self.links_from[k] = False

    def process(self, side_plane=None) -> Iterable["Unit"]:
        """Run (honoring gates) and yield downstream units to notify.
        Called by the Workflow scheduler. When a side plane is given
        and this unit is ``side_effect_only``, the run body executes
        on the unit's own FIFO lane instead of inline — the scheduler
        keeps walking the graph while the I/O happens."""
        if bool(self.gate_block):
            return ()
        if not bool(self.gate_skip):
            if side_plane is not None and self.side_effect_only:
                side_plane.submit("unit." + self.name, self._timed_run)
            else:
                self._timed_run()
        # stable name order: keeps the scheduler deterministic across runs
        return tuple(sorted(self.links_to, key=lambda u: u.name))

    def _timed_run(self) -> None:
        """The instrumented run body process() executes inline or the
        side-plane lane executes async (spans nest per thread, so the
        instrumentation is identical either way)."""
        t0 = time.time()
        if root.common.trace.run:
            self.debug("running %s", self.name)
        from .telemetry.spans import span
        # telemetry span: nesting + per-run dispatch/transfer
        # counter deltas. The root.common.trace.spans switch is
        # honored centrally by the recorder — one knob, every site
        with span("unit.run", unit=self.name,
                  cls=type(self).__name__):
            self.run()
        self.timers["run"] += time.time() - t0
        self.run_count += 1

    def __repr__(self) -> str:
        return "<%s %r>" % (type(self).__name__, self.name)


class TrivialUnit(Unit):
    """A unit that does nothing when run (useful as a join point)."""

    hide_from_registry = True
