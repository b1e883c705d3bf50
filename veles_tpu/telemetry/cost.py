"""Cost accounting: FLOPs / bytes / peak memory from the compiler.

Roofline-style accounting is how the TPU linear-algebra literature
reports utilization; this module makes the framework itself the source
of those numbers instead of hand-derivations in docs/perf.md. Primary
source: ``jax.stages.Compiled.cost_analysis()`` on the lowered
computation — exact for everything XLA compiles. Pallas kernels report
nothing through that interface (the custom-call is opaque to the HLO
cost model), so the ops that own kernels publish an ``analytic_cost``
(ops/flash_attention.py, ops/fused_fc.py) and the
:class:`CostModel` merges both sources into one per-unit ledger.

MFU here is the standard quotient: analytic/compiler model FLOPs per
second over the chip's nominal dense bf16 peak (numerator convention:
2·spatial·weights per conv position, ×3 for training), computed and
reported by the framework.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

#: nominal dense bf16 peak FLOP/s per chip by device kind (public
#: numbers; substring-matched against jax device_kind, first hit wins).
#: THE one copy in the program. A device_kind with no
#: row is an error (``UnknownDevice``), never a default: a utilization
#: graded against another chip's peak is not a measurement.
#: ``"TPU v5 lite"`` (the v5e, as jax names it) matches the ``v5`` row:
#: 197 TFLOP/s bf16 — Google Cloud documentation, "TPU v5e".
PEAK_BF16 = [
    ("v6", 918e12), ("v5p", 459e12), ("v5", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
]

#: nominal dense f32 peak FLOP/s per chip. The MXU computes bf16
#: products with f32 accumulation; full-f32 matmul throughput is the
#: STATED assumption peak_bf16/2 (a bf16x3-style decomposition costs
#: at least that), written down as its own table so an f32 workload's
#: MFU is graded against an f32 roofline instead of being understated
#: 2× against the bf16 peak. Same substring matching as PEAK_BF16.
PEAK_F32 = [
    ("v6", 459e12), ("v5p", 229.5e12), ("v5", 98.5e12),
    ("v4", 137.5e12), ("v3", 61.5e12), ("v2", 22.5e12),
]


class UnknownDevice(LookupError):
    """No peak FLOP/s on file for this device_kind: a utilization
    cannot be graded here (callers that also run on the CPU drop the
    field)."""


#: assumed aggregate ICI bandwidth per chip, bytes/s (public nominal
#: numbers, substring-matched like PEAK_BF16; first hit wins). This is
#: the STATED input of the elastic scaling model
#: (resilience/elastic.py predict_step_time): change a
#: value here and every prediction re-anchors — the point is that the
#: assumption is written down where one measurement can refute it.
ICI_BW_BYTES = [
    ("v6", 3.584e11), ("v5p", 4.8e11), ("v5", 1.6e11),
    ("v4", 2.4e11), ("v3", 1.4e11), ("v2", 6.4e10),
]
#: hosts without a known interconnect (CPU meshes, unknown chips):
#: loopback-class assumption, stamped as such in the prediction record
DEFAULT_ICI_BW = 1.0e11


def _device_kind(device_kind: Optional[str]) -> str:
    """``device_kind`` lower-cased, defaulting to the first visible jax
    device's."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    return str(device_kind).lower()


def ici_bandwidth_entry(device_kind: Optional[str] = None):
    """(source label, assumed per-chip ICI bytes/s) for
    ``device_kind`` — the label names the EXACT assumption used
    (``ICI_BW_BYTES[<key>]`` on a table hit, ``DEFAULT_ICI_BW``
    otherwise), so the scaling model's falsifiability record can never
    misattribute its own input."""
    kind = _device_kind(device_kind)
    for key, bw in ICI_BW_BYTES:
        if key in kind:
            return "telemetry.cost.ICI_BW_BYTES[%s]" % key, bw
    return ("telemetry.cost.DEFAULT_ICI_BW (loopback-class "
            "assumption: %g)" % DEFAULT_ICI_BW), DEFAULT_ICI_BW


def ici_bandwidth(device_kind: Optional[str] = None) -> float:
    """Assumed per-chip ICI bytes/s for ``device_kind`` (default: the
    first visible jax device) — the scaling model's comm denominator."""
    return ici_bandwidth_entry(device_kind)[1]


def peak_bf16_flops(device_kind: Optional[str] = None) -> float:
    """Nominal dense bf16 peak FLOP/s for ``device_kind`` (default: the
    first visible jax device); ``UnknownDevice`` when there is no row."""
    return peak_flops_entry("bfloat16", device_kind)[1]


def peak_flops_entry(dtype=None, device_kind: Optional[str] = None):
    """(source label, nominal dense peak FLOP/s) keyed on the
    COMPUTATION dtype: f32 (and f64, which has no MXU path at all —
    priced at the f32 table as the optimistic bound) resolves through
    PEAK_F32, everything else (bf16/f16/int8-ish mixed precision)
    through PEAK_BF16. The label names the exact table entry used so
    a report can stamp the peak it was graded against. Raises
    ``UnknownDevice`` for a device_kind neither table lists."""
    if dtype is None:
        name = "bfloat16"
    else:
        try:            # accepts "float32", numpy.float32, dtype objects
            import numpy
            name = numpy.dtype(dtype).name
        except TypeError:       # e.g. "bf16" shorthand, jax weak types
            name = str(getattr(dtype, "name", dtype))
    f32_class = name.lower() in ("float32", "f32", "float64", "f64")
    table, tname = ((PEAK_F32, "PEAK_F32") if f32_class
                    else (PEAK_BF16, "PEAK_BF16"))
    kind = _device_kind(device_kind)
    for key, p in table:
        if key in kind:
            return "telemetry.cost.%s[%s]" % (tname, key), p
    raise UnknownDevice(
        "no peak FLOP/s on file for device_kind %r (telemetry/cost.py "
        "%s): utilization cannot be graded on this device"
        % (kind, tname))


class Cost:
    """One computation's cost: model FLOPs, bytes accessed (HBM traffic
    as the compiler models it), peak live memory."""

    __slots__ = ("flops", "bytes_accessed", "peak_memory", "source")

    def __init__(self, flops: float = 0.0, bytes_accessed: float = 0.0,
                 peak_memory: float = 0.0, source: str = "analytic"):
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.peak_memory = float(peak_memory)
        #: "xla" (compiler-reported) | "analytic" (fallback table)
        self.source = source

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.flops + other.flops,
                    self.bytes_accessed + other.bytes_accessed,
                    max(self.peak_memory, other.peak_memory),
                    self.source if self.source == other.source
                    else "mixed")

    def scaled(self, n: float) -> "Cost":
        """Cost of running this computation ``n`` times (peak memory is
        per-execution and does not scale)."""
        return Cost(self.flops * n, self.bytes_accessed * n,
                    self.peak_memory, self.source)

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per byte accessed — the roofline x-axis."""
        return self.flops / self.bytes_accessed if self.bytes_accessed \
            else 0.0

    def mfu(self, seconds: float, peak_flops: Optional[float] = None,
            n_chips: int = 1) -> float:
        """Model FLOP utilization of executing this cost in
        ``seconds`` on ``n_chips`` chips of ``peak_flops`` each."""
        if seconds <= 0:
            return 0.0
        peak = peak_flops if peak_flops is not None else peak_bf16_flops()
        return self.flops / seconds / (peak * n_chips)

    def as_dict(self) -> Dict[str, float]:
        return {"flops": self.flops, "bytes": self.bytes_accessed,
                "peak_memory": self.peak_memory, "source": self.source}

    def __repr__(self) -> str:
        return ("Cost(flops=%.3e, bytes=%.3e, peak=%.3e, %s)"
                % (self.flops, self.bytes_accessed, self.peak_memory,
                   self.source))


def _sum_cost_analysis(ca: Any) -> Dict[str, float]:
    """cost_analysis() returns a dict (new jax) or list of per-
    computation dicts (older); flatten to summed keys."""
    if ca is None:
        return {}
    if isinstance(ca, dict):
        dicts = [ca]
    else:
        dicts = [d for d in ca if isinstance(d, dict)]
    out: Dict[str, float] = {}
    for d in dicts:
        for k, v in d.items():
            if isinstance(v, (int, float)):
                out[k] = out.get(k, 0.0) + float(v)
    return out


#: thread-local collector for Pallas kernel costs noted at TRACE time.
#: XLA's HLO cost model counts a scan/while body ONCE (verified: a
#: 10-step scanned matmul reports one matmul's flops), and a kernel's
#: Python builder also runs once per call site per trace — so costs
#: noted here share the compiler's body-once convention and can be
#: summed with cost_analysis() numbers without double counting.
_trace_notes = threading.local()


class collecting_kernel_costs:
    """``with collecting_kernel_costs() as notes:`` — while tracing
    inside the block, kernels that call :func:`note_kernel_cost`
    (ops/flash_attention.py) append their analytic costs to
    ``notes``."""

    def __enter__(self):
        self._prev = getattr(_trace_notes, "acc", None)
        _trace_notes.acc = []
        return _trace_notes.acc

    def __exit__(self, *exc: Any) -> None:
        _trace_notes.acc = self._prev


def note_kernel_cost(cost: Cost) -> None:
    """Called by Pallas kernel entry points at trace time: registers
    the kernel's analytic cost with whatever
    :class:`collecting_kernel_costs` block is active (no-op outside
    one — normal jit tracing pays nothing)."""
    acc = getattr(_trace_notes, "acc", None)
    if acc is not None:
        acc.append(cost)


def cost_of_compiled(compiled: Any) -> Cost:
    """Extract a :class:`Cost` from a ``jax.stages.Compiled``."""
    summed = {}
    try:
        summed = _sum_cost_analysis(compiled.cost_analysis())
    except Exception:                # noqa: BLE001 — backend-optional API
        pass
    peak = 0.0
    try:
        mem = compiled.memory_analysis()
        peak = float(mem.argument_size_in_bytes
                     + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes)
    except Exception:                # noqa: BLE001
        pass
    return Cost(summed.get("flops", 0.0),
                summed.get("bytes accessed", 0.0), peak, source="xla")


def cost_of_fn(fn: Callable, *args: Any, **kwargs: Any) -> Cost:
    """Lower + compile ``fn`` on the given abstract/concrete args and
    read its cost. Compilation hits jax's persistent cache, so calling
    this on an already-used jitted function is cheap."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return cost_of_compiled(jitted.lower(*args, **kwargs).compile())


class CostModel:
    """Per-unit cost ledger: the framework's own measured-MFU source.

    Units record the cost of their compiled
    programs under a name; :meth:`report` divides accumulated FLOPs by
    measured seconds and the chip's nominal peak — MFU as a framework
    output, not a hand calculation. Thread-safe (serving counters and
    training record concurrently).
    """

    def __init__(self, peak_flops: Optional[float] = None) -> None:
        self._lock = threading.Lock()
        self._ledger: Dict[str, Cost] = {}
        self._execs: Dict[str, int] = {}
        self._peak = peak_flops

    @property
    def peak_flops(self) -> float:
        if self._peak is None:
            self._peak = peak_bf16_flops()
        return self._peak

    def record(self, name: str, cost: Cost, executions: float = 1) -> None:
        """Accumulate ``cost`` × ``executions`` under ``name``."""
        with self._lock:
            add = cost.scaled(executions)
            cur = self._ledger.get(name)
            self._ledger[name] = add if cur is None else cur + add
            self._execs[name] = self._execs.get(name, 0) + int(executions)

    def record_compiled(self, name: str, compiled: Any,
                        executions: float = 1) -> Cost:
        cost = cost_of_compiled(compiled)
        self.record(name, cost, executions)
        return cost

    def get(self, name: str) -> Optional[Cost]:
        with self._lock:
            return self._ledger.get(name)

    def total(self) -> Cost:
        with self._lock:
            total = Cost()
            for c in self._ledger.values():
                total = total + c
            return total

    def mfu(self, name: str, seconds: float, n_chips: int = 1) -> float:
        cost = self.get(name)
        if cost is None:
            return 0.0
        return cost.mfu(seconds, self.peak_flops, n_chips)

    def report(self, seconds_by_name: Optional[Dict[str, float]] = None,
               n_chips: int = 1) -> Dict[str, Dict[str, float]]:
        """Structured per-name summary; entries with measured seconds
        carry ``tflops_per_sec`` and ``mfu``."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            items = list(self._ledger.items())
            execs = dict(self._execs)
        for name, cost in items:
            row = cost.as_dict()
            row["executions"] = execs.get(name, 0)
            row["arithmetic_intensity"] = cost.arithmetic_intensity
            secs = (seconds_by_name or {}).get(name)
            if secs:
                row["seconds"] = secs
                row["tflops_per_sec"] = cost.flops / secs / 1e12
                row["mfu"] = cost.mfu(secs, self.peak_flops, n_chips)
            out[name] = row
        return out

    def clear(self) -> None:
        with self._lock:
            self._ledger.clear()
            self._execs.clear()


#: process-global ledger instrumented units record into (mirrors
#: counters.counters / spans.recorder).
model = CostModel()
