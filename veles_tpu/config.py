"""Auto-vivifying configuration tree.

TPU-era equivalent of the reference's veles/config.py:60-325: a global
attribute tree ``root`` where any ``root.a.b.c = v`` path springs into
existence, with layered overrides (site file, user file, environment,
explicit ``update()``), protected keys, and a printable/dumpable form.

Differences from the reference, by design:
- overrides come from python/JSON files and ``VELES_TPU_*`` env vars instead
  of runpy-exec'd model config files (those still work via ``update_from_file``);
- engine defaults describe the XLA/TPU backend (dtype policy, mesh axes,
  compilation cache) instead of OpenCL block sizes.
"""

from __future__ import annotations

import json
import os
import runpy
from typing import Any, Dict, Iterator, Tuple

_PROTECTED = "_protected_"


class Config:
    """A node in the auto-vivifying config tree."""

    def __init__(self, path: str = "root") -> None:
        object.__setattr__(self, "_path_", path)
        object.__setattr__(self, _PROTECTED, set())

    # -- attribute protocol -------------------------------------------------
    def __getattr__(self, name: str) -> "Config":
        if name.startswith("__") and name.endswith("__"):
            raise AttributeError(name)
        child = Config("%s.%s" % (self._path_, name))
        object.__setattr__(self, name, child)
        return child

    def __setattr__(self, name: str, value: Any) -> None:
        if name in (self._protected_set()):
            raise AttributeError(
                "config key %s.%s is protected" % (self._path_, name))
        object.__setattr__(self, name, value)

    def _protected_set(self):
        return object.__getattribute__(self, _PROTECTED)

    def protect(self, *names: str) -> None:
        """Forbid further assignment of the given child keys
        (reference: veles/config.py:79-84)."""
        self._protected_set().update(names)

    # -- collection-ish protocol -------------------------------------------
    def __contains__(self, name: str) -> bool:
        return name in self.__dict__ and not name.endswith("_")

    def _is_husk(self) -> bool:
        """True when this node holds NOTHING but (recursively) empty
        Config children — the shape mere reads auto-vivify."""
        for _k, v in self.items():
            if not (isinstance(v, Config) and v._is_husk()):
                return False
        return True

    def get(self, name: str, default: Any = None) -> Any:
        """Like dict.get — and a node vivified by mere READS counts as
        unset. ``__getattr__`` auto-vivifies (truthy) nodes, so
        ``if root.x.y.z:`` creates the whole x→y→z chain; the husk test
        recurses, or ``get("y")`` one level up would still hand back
        the all-husk subtree (the footgun guards in
        train_step/publishing existed for exactly this)."""
        if name in self:
            val = self.__dict__[name]
            if isinstance(val, Config) and val._is_husk():
                return default
            return val
        return default

    def items(self) -> Iterator[Tuple[str, Any]]:
        # insertion order preserved: mesh-axis order etc. is semantic
        for k, v in self.__dict__.items():
            if k.endswith("_") or k.startswith("_"):
                continue
            yield k, v

    def update(self, tree: Dict[str, Any] = None, **kwargs: Any) -> "Config":
        """Deep-merge a nested dict (or kwargs) into this subtree
        (reference: veles/config.py:103-133 ``Config.update``)."""
        tree = dict(tree or {})
        tree.update(kwargs)
        for k, v in tree.items():
            if isinstance(v, dict):
                getattr(self, k).update(v)
            else:
                setattr(self, k, v)
        return self

    def update_from_file(self, path: str) -> "Config":
        """Apply a .py (exec'd with ``root`` in scope, like the reference's
        runpy path, veles/__main__.py:426-472) or .json override file."""
        if path.endswith(".json"):
            with open(path, "r") as fin:
                self.update(json.load(fin))
        else:
            runpy.run_path(path, init_globals={"root": self})
        return self

    def update_from_env(self, prefix: str = "VELES_TPU_CFG_") -> "Config":
        """``VELES_TPU_CFG_ENGINE__FORCE_NUMPY=true`` → engine.force_numpy.
        Path components are separated by a DOUBLE underscore so config keys
        containing single underscores survive; the CFG_ prefix keeps
        non-config control variables (VELES_TPU_TEST, ...) out of the
        tree."""
        for key, val in os.environ.items():
            if not key.startswith(prefix):
                continue
            node = self
            *parents, leaf = key[len(prefix):].lower().split("__")
            for part in parents:
                node = getattr(node, part)
            try:
                val = json.loads(val)
            except ValueError:
                pass
            setattr(node, leaf, val)
        return self

    # -- introspection ------------------------------------------------------
    def as_dict(self) -> Dict[str, Any]:
        out = {}
        for k, v in self.items():
            out[k] = v.as_dict() if isinstance(v, Config) else v
        return out

    def print_(self, indent: int = 0, file=None) -> None:
        """Dump the tree (reference ``--dump-config``, veles/config.py:136)."""
        import sys
        file = file or sys.stdout
        for k, v in self.items():
            if isinstance(v, Config):
                print("%s%s:" % ("  " * indent, k), file=file)
                v.print_(indent + 1, file)
            else:
                print("%s%s: %r" % ("  " * indent, k, v), file=file)

    def __repr__(self) -> str:
        return "<Config %s: %s>" % (self._path_, sorted(
            k for k, _ in self.items()))


def _default_root() -> Config:
    r = Config("root")
    r.common.update({
        "dirs": {
            "cache": os.path.expanduser("~/.veles_tpu/cache"),
            "snapshots": os.path.expanduser("~/.veles_tpu/snapshots"),
            "datasets": os.path.expanduser("~/.veles_tpu/datasets"),
        },
        "engine": {
            # dtype policy: params/compute dtype (reference precision_type,
            # veles/config.py:241-248; on TPU the MXU wants bfloat16 compute)
            "precision_type": "float32",
            "compute_dtype": "bfloat16",
            "backend": "auto",       # auto | tpu | cpu | numpy
            "sync_run": False,       # block after each step (profiling aid)
            "force_numpy": False,    # run numpy oracle instead of XLA
            # pallas flash-attention kernel for the single-chip attention
            # core. True = use it when compiled on a TPU backend and the
            # shapes qualify; False = always the fused XLA reference;
            # "force" = run it even off-TPU via pallas interpret mode
            # (slow — test harness use only)
            "flash_attention": True,
            # below this sequence length the fused-XLA reference wins on
            # the MXU. "auto" (default) = the per-device MEASURED
            # crossover from the chip attn sweep (ops/autotune.py
            # flash_min_t, per head size: 2048 on the v5e at 64 and
            # at 128, devices/kernel_tuning.json; 4096 until a sweep
            # has run on this device_kind and head size); an int pins
            # it; "force" engine mode ignores the threshold entirely
            "flash_attention_min_t": "auto",
            # long-context scheme over the 'sequence' mesh axis:
            # "ring" (K/V rotation, memory-flat in T) or "ulysses"
            # (all-to-all head re-sharding; needs heads % n_seq == 0)
            "sequence_parallel": "ring",
        },
        "mesh": {
            # logical mesh axes reserved up front (SURVEY.md §5.7/§5.8):
            # data, fsdp, tensor, sequence, expert, pipeline
            "axes": {"data": -1},    # -1 = all remaining devices
        },
        # trace.spans: telemetry span recording — honored centrally by
        # the recorder, so it covers Unit.run, workflow.run/initialize,
        # the train step and the decoders (veles_tpu/telemetry/
        # spans.py — in-memory ring + optional --trace-file JSONL; a
        # deque append per span, cheap enough to stay on by default)
        "trace": {"run": False, "timings": False, "spans": True},
        # model-health observability (veles_tpu/telemetry/tensormon.py
        # + recorder.py, docs/observability.md "Model health")
        "telemetry": {
            # in-graph tensor-statistics taps on the fused train step.
            # OFF by default: the off path is bit-identical to a build
            # without the feature (locked by tests/test_tensormon.py)
            "tensormon": {
                "enabled": False,
                # host-side observation cadence: process every Nth
                # drained sample (the device accumulators always ride
                # the existing per-epoch metric drain — zero extra
                # host syncs either way); NaN detection runs on every
                # sample regardless
                "every": 1,
                # NaN/Inf sentinel: warn | halt | snapshot_and_halt
                "nan_policy": "warn",
                # |activation| at/above this counts as saturated
                "sat_threshold": 6.0,
            },
            # flight recorder (crash black box): bounded in-memory ring
            # subscribed to span closes, alarm-counter increments,
            # logger events, health transitions and tensormon samples
            "recorder": {
                "enabled": True,
                "capacity": 4096,
                # dump blackbox-<ts>.jsonl on unhandled Workflow.run
                # exceptions / watchdog trips / SIGTERM (the NaN
                # sentinel's halt policies always dump)
                "autodump": False,
                # additionally record any single counter increment of
                # at least this value (0 = alarm counters only)
                "counter_threshold": 0,
            },
        },
        # resilience subsystem (veles_tpu/resilience/, docs/resilience.md)
        "resilience": {
            # fault-injection spec (point:action[:k=v,...];...);
            # the VELES_FAULTS env var overrides this key
            "faults": "",
            # default RetryPolicy knobs (exponential backoff + jitter)
            "retry": {"max_attempts": 4, "base_delay": 0.5,
                      "max_delay": 30.0},
            "keep_last": 0,           # snapshot retention; 0 = keep all
            "download_timeout": 60.0,  # socket timeout per HTTP attempt
            "max_pending": 64,        # RESTfulAPI in-flight bound
            "max_queue": 256,         # GenerationAPI queue bound
            "heartbeat_timeout": 300.0,
        },
        # continuous-batching serving engine (veles_tpu/serving/,
        # docs/services.md "Continuous batching"): GenerationAPI's
        # decode plane — a persistent max_slots-row KV-cache pool with
        # iteration-level scheduling. "recurrent" pins the O(1)-state
        # slot pool (serving/recurrent.py — fixed per-slot recurrent
        # state instead of a page table; "continuous" auto-falls-back
        # to it for Embedding→LSTM/SSM→LMHead stacks). "window" falls
        # back to the legacy shape-keyed coalescing worker. The O(1)
        # lane's own knobs ride this block too: state_cache (bool,
        # default False — the state-checkpoint prefix cache) and
        # state_cache_blocks (soft LRU budget, 0/None = unbounded);
        # page_size doubles as its checkpoint interval.
        "serving": {
            "engine": "continuous",
            # KV-cache slot rows decoded by the one fixed-shape step
            "max_slots": 8,
            # prefill pad-to lengths: jit cache is bounded by
            # len(buckets) + the decode step's 1 or 2 view lengths
            # (programs_bound()), not by distinct prompt lengths
            "buckets": [16, 32, 64, 128],
            # per-row KV capacity; admission requires
            # len(prompt) + n_new <= max_context (else the request
            # falls back to the window path)
            "max_context": 640,
            # decode steps fused per dispatch (lax.scan): 1 = pure
            # per-token scheduling; larger amortizes dispatch overhead
            # at the cost of up to N-1 wasted row-steps per retirement
            "decode_block": 1,
            # AOT serving artifact (veles-tpu export serve-artifact):
            # a package directory whose pre-exported prefill/decode
            # programs the engine loads at initialize — zero jit
            # traces/compiles on the serving path. "" = live jit.
            # A missing/corrupt/mismatched artifact falls back to
            # live jit with a counted warning, never a crash.
            "artifact": "",
        },
        # quantization subsystem (veles_tpu/quant/, docs/services.md
        # "Quantized serving"): OFF by default — the off path is
        # bit-identical to a build without the feature (locked by
        # tests/test_quant.py)
        "quant": {
            # per-channel symmetric int8 decode matmul weights,
            # dequantized on read inside the serving programs
            "weights": False,
            # int8 KV-cache slot pool with per-slot/-position scales
            # (half the pool HBM at the same max_slots)
            "kv": False,
            # weight scale granularity: per_channel (one scale per
            # output column — the accuracy default) | per_tensor
            "granularity": "per_channel",
        },
        # overlap engine (veles_tpu/overlap/, docs/overlap.md): async
        # side-plane for side-effect units, non-blocking checkpoints,
        # data-plane prefetch. Off by default — identical results
        # either way (locked by tests/test_overlap.py), enabling only
        # changes WHEN host I/O happens
        "overlap": {
            "enabled": False,
            "queue_depth": 64,        # per-lane bounded queue (backpressure)
            "async_snapshots": False,  # Snapshotter default async_mode
            "prefetch_depth": 0,       # Loader default prefetch depth
        },
        "disable": {"plotting": bool(os.environ.get("VELES_TPU_TEST"))},
        "random_seed": 1234,
    })
    # layered overrides, weakest first (reference: veles/config.py:294-308):
    # site file < user file < CWD file < environment
    for site in ("/etc/veles_tpu.json",
                 os.path.expanduser("~/.veles_tpu.json"),
                 os.path.join(os.getcwd(), ".veles_tpu.json")):
        if os.path.exists(site):
            r.update_from_file(site)
    r.common.update_from_env()
    return r


#: The global configuration tree (reference: veles/config.py:152 ``root``).
root = _default_root()
