"""AcceleratedUnit: base for every compute unit.

Equivalent of the reference's veles/accelerated_units.py:130-867, minus
everything XLA makes obsolete: there is no kernel source templating, no
build_program/nvcc, no binary cache tarballs — a compute unit declares pure
functions and ``jax.jit`` (with the persistent compilation cache) replaces
the whole kernel build/cache machinery (reference :298-673).

Preserved contract (SURVEY.md §4 "numpy is the oracle"):
- every accelerated unit implements ``numpy_run`` (host oracle) and an XLA
  path; ``--force-numpy`` (root.common.engine.force_numpy) switches, and the
  test harness asserts both agree (reference: @multi_device,
  veles/tests/accelerated_test.py:41-61);
- ``initialize(device=...)`` attaches the device; per-backend method dispatch
  (reference ocl_run/cuda_run/numpy_run binding, veles/backends.py:244-262)
  collapses to two: ``xla_run`` / ``numpy_run``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from .backends import Device, NumpyDevice, XLADevice
from .config import root
from .units import Unit
from .workflow import Workflow


def _abstract_shapes(args):
    """Pytree of ShapeDtypeStructs mirroring ``args`` (non-array leaves
    pass through — jit treats them as static-compatible values)."""
    import jax

    def leaf(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map(leaf, args)


class AcceleratedUnit(Unit):
    """Compute unit with device dispatch (reference:
    veles/accelerated_units.py:130)."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.device: Optional[Device] = None
        self._jit_cache: Dict[str, Any] = {}
        #: raw fn + jit kwargs per key — program_cost() re-lowers from
        #: these (the jitted callable hides its Compiled objects)
        self._jit_fns: Dict[str, Any] = {}
        #: abstract arg shapes of the LAST dispatch per key (donated
        #: buffers die at dispatch, so cost analysis lowers on shapes)
        self._jit_arg_shapes: Dict[str, Any] = {}
        #: dispatches per jit key — lets cost accounting bill each
        #: program (train vs eval vs epoch_block) at its OWN cost
        self._dispatch_counts: Dict[str, int] = {}

    # -- lifecycle ----------------------------------------------------------
    def initialize(self, device: Optional[Device] = None, **kwargs):
        res = super().initialize(device=device, **kwargs)
        if res:
            return res
        self.device = device if device is not None else NumpyDevice()
        if isinstance(self.device, XLADevice):
            self.xla_init()
        else:
            self.numpy_init()
        return None

    def xla_init(self) -> None:
        """Backend-specific setup (reference ocl_init/cuda_init)."""

    def numpy_init(self) -> None:
        pass

    # -- dispatch -----------------------------------------------------------
    @property
    def accelerated(self) -> bool:
        return (isinstance(self.device, XLADevice)
                and not root.common.engine.force_numpy)

    def run(self) -> None:
        if self.accelerated:
            self.xla_run()
            if root.common.engine.sync_run:
                self.device.sync()
        else:
            self.numpy_run()

    def xla_run(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError("%s.xla_run" % type(self).__name__)

    def numpy_run(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError("%s.numpy_run" % type(self).__name__)

    # -- jit helper ---------------------------------------------------------
    def jit(self, key: str, fn: Callable, **jit_kwargs) -> Callable:
        """Cache a jitted callable per unit (the reference cached built
        kernels per device, veles/accelerated_units.py:605-673; XLA's own
        compilation cache does the heavy lifting — this only avoids
        re-tracing).

        The returned callable is telemetry-instrumented: every call
        counts one ``veles_dispatches_total``; a call that grows the
        jit's trace cache counts one ``veles_compiles_total``
        (recompiles are a deterministic regression signal that no
        clock can see)."""
        cached = self._jit_cache.get(key)
        if cached is None:
            import jax
            from .telemetry.counters import inc
            jitted = jax.jit(fn, **jit_kwargs)
            self._jit_fns[key] = (fn, dict(jit_kwargs))
            unit = self

            def dispatch(*args, **kwargs):
                unit._dispatch_counts[key] = \
                    unit._dispatch_counts.get(key, 0) + 1
                try:
                    before = jitted._cache_size()
                except AttributeError:       # non-pjit backends
                    before = None
                out = jitted(*args, **kwargs)
                inc("veles_dispatches_total")
                if before is None:
                    # no cache introspection: capture shapes per call
                    unit._jit_arg_shapes[key] = _abstract_shapes(args)
                elif jitted._cache_size() > before:
                    inc("veles_compiles_total")
                    # shapes only change on retrace, and a retrace IS a
                    # cache growth — capturing here keeps the hot path
                    # free of the per-call pytree walk
                    unit._jit_arg_shapes[key] = _abstract_shapes(args)
                return out

            dispatch._jitted = jitted
            cached = self._jit_cache[key] = dispatch
        return cached

    def program_cost(self, key: str):
        """FLOPs/bytes/peak-memory of the LAST program dispatched under
        ``key``, via ``Compiled.cost_analysis()`` on a re-lower at the
        recorded arg shapes (in-process, so XLA's compilation cache
        absorbs most of the cost). Returns a telemetry ``Cost`` or None
        when nothing has been dispatched under ``key``. On-demand only
        (``TrainStep.cost_report``, tests) — never on the hot path."""
        entry = self._jit_fns.get(key)
        shapes = self._jit_arg_shapes.get(key)
        if entry is None or shapes is None:
            return None
        import jax
        from .telemetry.cost import (collecting_kernel_costs,
                                     cost_of_compiled)
        fn, jit_kwargs = entry
        # donation changes buffer reuse, not the cost model; dropping it
        # lets the lowering accept abstract args without aliasing checks
        jit_kwargs = {k: v for k, v in jit_kwargs.items()
                      if k != "donate_argnums"}
        # the re-lower re-traces fn, so Pallas kernels (opaque to the
        # HLO cost model) note their analytic costs into the collector
        # — body-once, the same convention cost_analysis uses for
        # scan/while bodies
        with collecting_kernel_costs() as notes:
            compiled = jax.jit(fn, **jit_kwargs).lower(*shapes).compile()
        cost = cost_of_compiled(compiled)
        for kernel_cost in notes:
            cost = cost + kernel_cost
        return cost

    def __getstate__(self):
        d = dict(self.__dict__)
        d["_jit_cache"] = {}
        d["_jit_fns"] = {}
        d["_jit_arg_shapes"] = {}
        d["_dispatch_counts"] = {}
        d["device"] = None
        return d


class AcceleratedWorkflow(Workflow):
    """Workflow owning a device (reference:
    veles/accelerated_units.py:827-858)."""

    hide_from_registry = True

    def __init__(self, workflow=None, **kwargs):
        super().__init__(workflow, **kwargs)
        self.device: Optional[Device] = None

    def initialize(self, device: Optional[Device] = None, **kwargs):
        self.device = device if device is not None else NumpyDevice()
        return super().initialize(device=self.device, **kwargs)

    @property
    def computing_power(self) -> float:
        """GFLOP/s of the attached device; the reference reported this to
        the master for load balancing (veles/accelerated_units.py:843-858);
        kept as telemetry."""
        if isinstance(self.device, XLADevice):
            return self.device.compute_power()
        return 0.0
