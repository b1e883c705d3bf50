"""Device backends: XLA (TPU/CPU) and NumPy oracle.

Equivalent of the reference's veles/backends.py:166-949 (BackendRegistry,
Device/OpenCLDevice/CUDADevice/NumpyDevice/AutoDevice). TPU-first redesign:

- One accelerated backend — XLA — instead of per-vendor kernel dispatch;
  ``XLADevice`` owns the device set, the logical ``jax.sharding.Mesh`` and
  the dtype policy. The reference's OpenCL block-size auto-tuner
  (veles/backends.py:672-731) has no equivalent: XLA tiles for the MXU.
- ``NumpyDevice`` is kept as the universal test oracle (the reference's
  "numpy is the oracle" property, SURVEY.md §4).
- Selection via ``root.common.engine.backend`` or ``VELES_BACKEND`` env.
  A platform name (``tpu``/``cpu``/``gpu``) is strict — that platform or
  an error; ``auto`` takes jax's default device set (the reference's
  AutoDevice, veles/backends.py:406-424, minus its numpy fallback).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import numpy

from .config import root
from .error import VelesError
from .logger import Logger


class BackendRegistry(type):
    """name → Device class (reference: veles/backends.py:166)."""

    backends: Dict[str, type] = {}

    def __init__(cls, name, bases, clsdict):
        super().__init__(name, bases, clsdict)
        backend = clsdict.get("BACKEND")
        if backend:
            BackendRegistry.backends[backend] = cls


def _resolve_dtype(name) -> numpy.dtype:
    """numpy.dtype() extended with the ml_dtypes names (bfloat16 &c.) —
    plain numpy does not know them, so NumpyDevice would crash on the
    default bf16 compute policy."""
    try:
        return numpy.dtype(name)
    except TypeError:
        import ml_dtypes
        return numpy.dtype(getattr(ml_dtypes, str(name)))


class Device(Logger, metaclass=BackendRegistry):
    """Abstract device (reference: veles/backends.py:184)."""

    BACKEND: Optional[str] = None

    def __init__(self) -> None:
        super().__init__()
        self.compute_dtype = _resolve_dtype(
            root.common.engine.compute_dtype)
        self.precision_dtype = _resolve_dtype(
            root.common.engine.precision_type)

    @property
    def is_accelerated(self) -> bool:
        return True

    @property
    def name(self) -> str:
        return self.BACKEND or type(self).__name__

    def sync(self) -> None:
        """Block until outstanding device work completes."""

    def exists(self) -> bool:
        return True


class NumpyDevice(Device):
    """Pure-host oracle backend (reference: veles/backends.py:918)."""

    BACKEND = "numpy"

    @property
    def is_accelerated(self) -> bool:
        return False


#: the one fixed place the persistent XLA compile cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` does not place it: inside the checkout
#: (git-ignored), because the directory is part of the cache key — a
#: path built from $HOME, a temp name or a pid never hits on a machine
#: that receives a fresh copy of the tree
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _place_compilation_cache(jax, platform: str) -> Optional[str]:
    """Decide where compiled programs persist; returns the directory in
    use (None = no persistent cache). An environment-provided
    ``JAX_COMPILATION_CACHE_DIR`` is jax's own business on every
    backend — nothing is set in code. Otherwise accelerators cache at
    ``COMPILE_CACHE_DIR``; the CPU backend stays uncached (XLA:CPU AOT
    results are keyed without host machine features, so reloading one
    on another host risks SIGILL, and its compiles are fast)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if platform == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


class XLADevice(Device):
    """JAX/XLA device set + logical mesh (the reference's
    Device-per-accelerator model collapses to one object owning all chips:
    SPMD means the framework addresses the *mesh*, not a chip)."""

    BACKEND = "xla"

    def __init__(self, platform: Optional[str] = None,
                 mesh_axes: Optional[Dict[str, int]] = None) -> None:
        super().__init__()
        import jax
        self._jax = jax
        try:
            self.jax_devices = (jax.devices(platform) if platform
                                else jax.devices())
        except RuntimeError as e:
            # jax's own message lists the backends it does have
            raise VelesError(
                "backend %r requested but jax %s offers no such device: %s"
                % (platform, jax.__version__, e))
        self.platform = self.jax_devices[0].platform
        self.compile_cache = _place_compilation_cache(jax, self.platform)
        axes = dict(mesh_axes if mesh_axes is not None
                    else root.common.mesh.axes.as_dict()
                    if hasattr(root.common.mesh.axes, "as_dict")
                    else root.common.mesh.axes)
        self.mesh = make_mesh(self.jax_devices, axes)
        self.info("XLA backend: %d %s device(s) (%s), mesh %s, "
                  "compile cache %s",
                  len(self.jax_devices), self.platform,
                  self.jax_devices[0].device_kind,
                  dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
                  self.compile_cache or "off")

    @property
    def device_count(self) -> int:
        return len(self.jax_devices)

    def sync(self) -> None:
        # a freshly enqueued scalar op completes after everything ahead
        # of it on the (in-order) compute stream
        self._jax.block_until_ready(self._jax.device_put(0.0) + 0)

    def compute_power(self, n: int = 2048) -> float:
        """GEMM benchmark → GFLOP/s; the reference used the same measurement
        for load balancing (veles/accelerated_units.py:843-858); kept here
        as telemetry."""
        import jax
        import jax.numpy as jnp
        import time
        import numpy
        a = jnp.ones((n, n), dtype=jnp.bfloat16) * 1e-3
        f = jax.jit(lambda x: x @ x * 1e-3)
        numpy.asarray(f(a)[0, :1].astype(jnp.float32))   # warm + true sync
        t0 = time.time()
        reps = 8
        r = a
        for _ in range(reps):            # dependency chain: no overlap games
            r = f(r)
        numpy.asarray(r[0, :1].astype(jnp.float32))      # host fetch = sync
        dt = (time.time() - t0) / reps
        return 2.0 * n ** 3 / dt / 1e9


def make_mesh(devices, axes: Dict[str, int]):
    """Build a jax Mesh from an axis-name → size spec; one axis may be -1
    (absorbs remaining devices). Reserved axis vocabulary:
    data / fsdp / tensor / sequence / expert / pipeline (SURVEY.md §5.7)."""
    import numpy as np
    from jax.sharding import Mesh
    total = len(devices)
    sizes = dict(axes)
    wild = [k for k, v in sizes.items() if v == -1]
    fixed = int(np.prod([v for v in sizes.values() if v != -1])) if sizes \
        else 1
    if len(wild) > 1:
        raise VelesError("at most one mesh axis may be -1: %s" % axes)
    if wild:
        if total % fixed:
            raise VelesError("mesh %s does not divide %d devices" %
                             (axes, total))
        sizes[wild[0]] = total // fixed
    shape = tuple(sizes.values()) or (total,)
    names = tuple(sizes.keys()) or ("data",)
    need = int(np.prod(shape))
    if need > total:
        raise VelesError("mesh %s needs %d devices, only %d present" %
                         (sizes, need, total))
    # a submesh over the first N devices is allowed, but never silently
    if need < total:
        import logging
        logging.getLogger("make_mesh").warning(
            "mesh %s uses %d of %d devices; %d idle", sizes, need, total,
            total - need)
    return Mesh(np.asarray(devices[:need]).reshape(shape), names)


_auto_device: Optional[Device] = None


def Device_for(backend: Optional[str] = None) -> Device:
    """Resolve a backend name to a Device (reference: Device.__new__
    dispatch on -a/--backend or VELES_BACKEND, veles/backends.py:184-243).
    An explicit platform name is strict: ``"tpu"`` means
    ``jax.devices("tpu")`` or an error that says what jax saw — only
    ``"auto"``/``"xla"`` take whatever default device set jax offers."""
    backend = (backend or os.environ.get("VELES_BACKEND") or
               root.common.engine.backend)
    if backend == "numpy" or root.common.engine.force_numpy:
        return NumpyDevice()
    if backend in ("auto", None):
        return AutoDevice()
    if backend == "xla":
        return XLADevice()
    if backend in ("tpu", "cpu", "gpu"):
        return XLADevice(backend)
    raise VelesError("unknown backend %r (have: %s)" %
                     (backend, sorted(BackendRegistry.backends)))


def AutoDevice() -> Device:
    """The default XLA device set (reference: veles/backends.py:406).
    May resolve to the CPU — nothing that smokes or measures uses it."""
    global _auto_device
    if _auto_device is None:
        _auto_device = XLADevice()
    return _auto_device
