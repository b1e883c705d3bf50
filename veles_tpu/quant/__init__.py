"""Quantization subsystem: int8 weights, int8 KV cache, AOT serving.

The production-loop closer named by ROADMAP item 3 (reference analog:
``Workflow.package_export`` → ``libVeles/src/workflow_loader.cc``).
Three planes, all OFF by default and bit-identical when off:

- **Weights** (:mod:`weights`): per-channel symmetric int8 for the
  decode matmul weights, dequantized on read inside the jitted serving
  programs (``root.common.quant.weights`` / ``--quant-weights``), plus
  the offline ``veles-tpu quantize <snapshot>`` CLI producing
  snapshots with ~4× smaller weight payloads any build can resume
  from.
- **KV cache** (:mod:`kv`): int8 slot-pool storage with per-slot,
  per-position scales — half the pool HBM at the same ``max_slots``
  (``root.common.quant.kv`` / ``--quant-kv``).
- **AOT artifacts** (``export/serve_artifact.py``): ``veles-tpu export
  serve-artifact`` serializes the engine's per-bucket prefill programs
  and its one fixed-shape decode step via ``jax.export`` into the
  package format; the engine loads them at initialize, so serving
  startup performs ZERO jit traces/compiles.

Numeric primitives live in ``ops/precision.py`` (the MXU precision
policy's home). Operator guide: docs/services.md "Quantized serving".
"""

from __future__ import annotations

from .weights import (dequantize_params, dequantize_state,  # noqa: F401
                      is_quantized_params, quantize_params,
                      quantize_params_spec, quantize_state,
                      quantize_tensor, GRANULARITIES)
from .kv import (block_page_pool, block_pool,                # noqa: F401
                 dequantize_rows_int8, pool_nbytes,
                 quantize_rows_int8)

#: every counter the quantization/artifact plane increments —
#: registered with HELP strings in telemetry/counters.py DESCRIPTIONS
#: and asserted zero in quant-off runs by
#: tests/test_telemetry.py test_feature_off_counters_stay_zero
QUANT_COUNTERS = (
    "veles_quant_params_total",
    "veles_quant_bytes_saved_total",
    "veles_quant_calibrations_total",
    "veles_artifact_loads_total",
    "veles_artifact_load_failures_total",
)


def policy() -> dict:
    """The active quantization policy
    (``root.common.quant.{weights,kv,granularity}``) as plain values —
    what the engine and the /metrics gauges read."""
    from ..config import root
    from .weights import granularity_from_config
    return {
        "weights": bool(root.common.quant.get("weights", False)),
        "kv": bool(root.common.quant.get("kv", False)),
        "granularity": granularity_from_config(),
    }
