"""TrainStep: the fused, jitted, SPMD training step.

THIS is the architectural heart of the TPU build (SURVEY.md §7 design
stance). The reference executed one GPU kernel per unit per minibatch from
Python threads (veles/units.py:782-505 hot loop) and aggregated gradients
through a ZeroMQ master–slave parameter server (veles/server.py,
veles/client.py). Here the entire minibatch — on-device dataset gather
(fullbatch_loader.cl equivalent), every forward, the loss, every gradient
(jax.grad — replacing all hand-written gd_* kernels), every optimizer
update, and metric accumulation — is ONE compiled XLA program. Data
parallelism falls out of sharding the minibatch over the mesh 'data' axis:
XLA's SPMD partitioner inserts the gradient psum over ICI automatically
(the BASELINE.json north star: "ZeroMQ master–slave → jax.lax.psum").

Per-step host traffic is ZERO except the int32 index vector; metrics
accumulate on device and are drained once per epoch by the Decision unit.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy

from ..accelerated import AcceleratedUnit
from ..backends import XLADevice
from ..error import Bug
from ..loader.base import TEST, VALID, TRAIN
from .. import prng
from .nn_units import ForwardBase, GradientDescentBase, MATCHING
from .all2all import All2AllSoftmax
from .evaluator import EvaluatorSoftmax


class TrainStep(AcceleratedUnit):
    """Owns the canonical device-side parameter pytree and the compiled
    train/eval step functions."""

    MAPPING = "train_step"
    hide_from_registry = False

    def __init__(self, workflow, forwards: List[ForwardBase] = (),
                 evaluator=None, loader=None, gds=None,
                 target_mode: str = "labels", steps_per_dispatch: int = 16,
                 epochs_per_dispatch: int = 1,
                 pipeline_microbatches: Optional[int] = None,
                 remat: bool = False, grad_accumulation: int = 1,
                 **kwargs):
        super().__init__(workflow, **kwargs)
        self.view_group = "TRAINER"
        self.forwards = list(forwards)
        self.evaluator = evaluator
        self.loader = loader
        #: H > 1 fuses H WHOLE epochs (eval+train segments) into one
        #: dispatch — the per-epoch host round trips (train dispatch +
        #: eval dispatch + metric drain) collapse to 1/H. Decision
        #: bookkeeping stays per-epoch (drain_epoch_blocks); early-stop
        #: granularity coarsens to the block (documented trade).
        self.epochs_per_dispatch = max(1, int(epochs_per_dispatch))
        #: G > 1: each optimizer step back-propagates G sequential
        #: minibatch chunks (activation memory / G) and applies ONE
        #: update from their weighted-mean gradient — the large-
        #: effective-batch lever when activations, not params, bound
        #: HBM (see _train_step_accum_fn)
        self.grad_accumulation = max(1, int(grad_accumulation))
        if loader is not None:
            # fused consumption: host minibatch fill skipped; K minibatches
            # scanned per dispatch (must be set before loader.initialize)
            loader.fused = True
            loader.plan_steps = max(1, int(steps_per_dispatch))
            if self.epochs_per_dispatch > 1:
                loader.block_epochs = self.epochs_per_dispatch
        #: "labels" (classification) | "targets" (regression) | "input"
        #: (autoencoder: reconstruct the input batch) | "auto" (resolve at
        #: initialize, after the loader has loaded: targets if present)
        self.target_mode = target_mode
        self.gds: List[GradientDescentBase] = list(gds) if gds else []
        self.lr_scale = 1.0        # linked from LearningRateAdjust
        #: --test mode: TRAIN minibatches evaluate without updating params
        #: (property: setting it downgrades block serving, see setter)
        self.evaluation_mode = False
        self.params: Dict[str, Dict[str, Any]] = {}
        self.opt_state: Dict[str, Dict[str, Any]] = {}
        #: microbatches per minibatch under a 'pipeline' mesh axis
        #: (default: one per stage; more shrinks the fill/drain bubble)
        self.pipeline_microbatches = pipeline_microbatches
        #: pipeline plan ({"pipeline": N} mesh axis): set by
        #: _setup_pipeline when the mesh has the axis, else None
        self._pp = None
        #: heterogeneous-pipeline plan (shape-changing chains the
        #: uniform planner refuses): list-of-stage-groups; params stay
        #: per-unit (replicated over the axis), so checkpoints/masks
        #: need no special casing
        self._pp_hetero = None
        #: rematerialize the forward under jax.checkpoint: activations
        #: are recomputed in the backward instead of living in HBM for
        #: the whole step — FLOPs traded for memory (SURVEY.md HBM
        #: guidance); numerics are identical
        self.remat = bool(remat)
        #: classic AMP (resolved at initialize from
        #: root.common.engine.mixed_precision): forward/backward run on a
        #: bfloat16 cast of params + batch, so ACTIVATION STORAGE halves —
        #: conv nets at image scale are HBM-bandwidth-bound, not
        #: FLOP-bound, and bf16 activations double the effective
        #: bandwidth. Master params, optimizer state, loss and metric
        #: accumulation stay float32 (evaluators upcast); MXU
        #: accumulation stays f32 via preferred_element_type. The
        #: compute_dtype knob (ops/precision.py) only steers MXU operand
        #: rounding — THIS one changes what lives in HBM between layers.
        self.mixed_precision = False
        #: {unit name: {param key: mask array}} — applied multiplicatively
        #: after EVERY optimizer update inside the fused step (ZeroFiller's
        #: sparsity contract must hold within a multi-step dispatch, not
        #: just at dispatch boundaries)
        self.param_masks: Dict[str, Dict[str, Any]] = {}
        self._param_masks_np: Dict[Any, numpy.ndarray] = {}
        self._accum: Dict[int, Any] = {}
        self._zero_accum = None
        #: ops/fused_fc.py whole-epoch kernel plan (engine.fused_fc_scan
        #: + strict eligibility, _setup_fused_fc); None = general path
        self._fused_fc = None
        #: fused scale-bias-activation epilogue plan
        #: (engine.fused_epilogue, _setup_epilogue); None = unfused
        self._epilogue = None
        #: bf16 interlayer activation storage under AMP
        #: (engine.bf16_activations, resolved at initialize)
        self._bf16_acts = False
        #: tensormon plan (telemetry/tensormon.py, resolved at
        #: initialize from root.common.telemetry.tensormon): None = no
        #: taps — the step traces EXACTLY as a build without the
        #: feature (bit-identical state trees, same dispatch count,
        #: locked by tests/test_tensormon.py)
        self._tensormon = None
        #: accumulator keys of what the forward units count inside the
        #: step (telemetry/steptaps.py; ``step_taps()`` of a unit), set at
        #: initialize; empty where no unit counts or the chain is not a
        #: plain one (pipelined, or gradients accumulated over chunks)
        self._step_taps = ()
        #: (stacked device accums, H) from the last block dispatch —
        #: converted to per-epoch dicts lazily in drain_epoch_blocks
        self._block_metrics = None
        #: {(class, h): (idx, mask) device arrays} — eval plans are
        #: epoch-invariant, uploaded once per scan length
        self._eval_plan_dev: Dict[Any, Any] = {}
        self.last_loss = None
        self.demand("evaluator", "loader")

    # -- construction helpers ------------------------------------------------
    def _ensure_gds(self) -> None:
        """Create matched GD units for parameterized forwards lacking one
        (Znicz MatchingObject pairing)."""
        have = {gd.forward for gd in self.gds}
        for f in self.forwards:
            if f.PARAMETERIZED and f not in have:
                gd_cls = MATCHING.get(type(f))
                if gd_cls is None:
                    for klass in type(f).__mro__:
                        if klass in MATCHING:
                            gd_cls = MATCHING[klass]
                            break
                if gd_cls is None:
                    raise Bug("no GD unit matched for %s" % type(f).__name__)
                gd = gd_cls(self.workflow, name="gd_" + f.name,
                            **getattr(f, "gd_config", {}))
                gd.forward = f
                self.gds.append(gd)

    def initialize(self, device=None, **kwargs):
        res = super().initialize(device=device, **kwargs)
        if res:
            return res
        # forwards must be initialized (params created) before us — they
        # are if they appear earlier in dependency order; otherwise re-queue
        for f in self.forwards:
            if f.PARAMETERIZED and not f.param_arrays():
                return True
        self._ensure_gds()
        gd_by_fwd = {gd.forward: gd for gd in self.gds}
        self._gd_for = {f.name: gd_by_fwd[f]
                        for f in self.forwards if f.PARAMETERIZED}
        # canonical device pytree
        import jax
        self.params = {
            f.name: {k: v.device_view() for k, v in f.param_arrays().items()}
            for f in self.forwards if f.PARAMETERIZED}
        self.opt_state = {
            name: self._gd_for[name].init_state(p)
            for name, p in self.params.items()}
        # the step owns (and donates) the device-side params from here on;
        # the forwards' Arrays keep their host mirror only
        for f in self.forwards:
            for arr in f.param_arrays().values():
                arr.detach_devmem()
        self._rng = prng.get(self.name)
        from ..config import root
        # Config.get treats auto-vivified empty nodes as unset
        self.mixed_precision = bool(
            root.common.engine.get("mixed_precision", False))
        # model-health taps (telemetry/tensormon.py): resolved ONCE
        # here — the flag keys what the jitted step traces, so a
        # mid-run config flip must not desync the jit cache
        from ..telemetry import tensormon
        self._tensormon = tensormon.settings() if tensormon.enabled() \
            else None
        if self.target_mode == "auto":
            # resolvable only now: the loader's load_data has run
            has_t = getattr(self.loader, "original_targets", None)
            self.target_mode = ("targets" if has_t is not None and has_t
                                else "input")
        self._setup_pipeline()
        if self.grad_accumulation > 1:
            if self._pp is not None or self._pp_hetero is not None:
                raise Bug("grad_accumulation does not compose with a "
                          "'pipeline' mesh axis (both re-chunk the "
                          "minibatch); drop one")
            mb = self.loader.max_minibatch_size
            if mb % self.grad_accumulation:
                raise Bug("minibatch size %d not divisible into %d "
                          "gradient-accumulation chunks"
                          % (mb, self.grad_accumulation))
            if isinstance(self.device, XLADevice):
                n_data = dict(self.device.mesh.shape).get("data", 1)
                if (mb // self.grad_accumulation) % n_data:
                    raise Bug("accumulation chunk size %d not divisible "
                              "by data-axis size %d"
                              % (mb // self.grad_accumulation, n_data))
        self._bf16_acts = bool(
            root.common.engine.get("bf16_activations", False))
        if self._bf16_acts and not self.mixed_precision:
            # bf16 ACTIVATION storage only makes sense under AMP: the
            # masters stay f32 either way, and without the bf16 cast
            # of params+batch the interlayer casts would just round a
            # full-precision forward for nothing
            self.warning("bf16_activations needs "
                         "engine.mixed_precision — ignored")
            self._bf16_acts = False
        self._setup_shardings()
        self._setup_fused_fc()
        self._setup_epilogue()
        if self._pp is None and self._pp_hetero is None \
                and self.grad_accumulation == 1:
            self._step_taps = tuple(sorted({
                k for f in self.forwards
                for k in getattr(f, "step_taps", tuple)()}))
        return None

    def _setup_epilogue(self) -> None:
        """Fused scale-bias-activation epilogue plan
        (``root.common.engine.fused_epilogue``, ops/fused_fc.py): runs
        of standalone elementwise units (``activation_*`` layers) fold
        into their producing matmul's consumer inside the traced step
        — identical ops in identical order, so ON is bit-identical to
        OFF here; the dispatch win lives on the standalone forward
        path (install_epilogues). Composes with TensorMonitor taps:
        the taps read the post-epilogue head output, so monitoring
        NEVER forces the unfused path (test-locked — a future
        incompatibility must warn and count, not silently unfuse)."""
        from ..config import root
        from ..ops import fused_fc as _ff
        self._epilogue = None
        if not root.common.engine.get("fused_epilogue", False):
            return
        if self._pp is not None or self._pp_hetero is not None:
            self.warning("fused_epilogue does not fold across "
                         "pipeline stage boundaries — running the "
                         "unfused chain")
            return
        plan = _ff.plan_epilogues(self.forwards)
        if not plan:
            return
        self._epilogue = plan
        self.info("fused epilogue engaged%s: %s",
                  " (composes with tensormon taps)"
                  if self._tensormon is not None else "",
                  "; ".join("%s ← %s" % (p.name,
                                         "+".join(t.name for t in ts))
                            for p, ts in plan))

    def _setup_fused_fc(self) -> None:
        """Opt-in whole-epoch Pallas fast path
        (``root.common.engine.fused_fc_scan``, ops/fused_fc.py): the
        sequential-SGD-bound FC configs (the MNIST-784 headline) run
        each epoch's K optimizer steps as ONE kernel with VMEM-resident
        weights. Strict eligibility — anything outside the proven
        envelope silently keeps the general scan path (and logs why)."""
        from ..config import root
        self._fused_fc = None
        flag = root.common.engine.get("fused_fc_scan", False)
        if not flag:
            return

        def reject(why):
            self.info("fused_fc_scan requested but ineligible: %s", why)

        # the kernel computes in f32; the general path's matmuls follow
        # the compute_dtype policy — on TPU the default bfloat16 policy
        # means one bf16 MXU pass (Precision.DEFAULT), so the two paths
        # would not be trajectory-exact there. On CPU DEFAULT is full
        # f32 and parity holds. "force" opts out of the parity claim
        # (bench A/Bs carry their own method tag instead) and of the
        # backend gate: off-TPU it runs the kernel interpreted
        import jax
        on_tpu = jax.default_backend() == "tpu"
        if flag != "force" and not on_tpu:
            return reject("not a TPU backend — the Pallas kernel would "
                          "only run in interpret mode (fused_fc_scan="
                          "'force' does that; test harness only)")
        if flag != "force" and str(root.common.engine.get(
                "compute_dtype", "bfloat16")) in ("bfloat16", "bf16"):
            return reject("TPU compute_dtype policy is bfloat16 — the "
                          "f32 kernel would not be trajectory-exact "
                          "vs the bf16-pass scan path (set "
                          "compute_dtype=float32 or fused_fc_scan="
                          "'force' to opt out of the parity claim)")

        from .all2all import All2AllSoftmax, All2AllTanh
        fs = [f for f in self.forwards if f.PARAMETERIZED]
        if (len(self.forwards) != len(fs) or len(fs) < 2
                or any(type(f) is not All2AllTanh for f in fs[:-1])
                or type(fs[-1]) is not All2AllSoftmax):
            return reject("needs an [all2all_tanh ... all2all_tanh, "
                          "softmax] chain")
        if not isinstance(self.evaluator, EvaluatorSoftmax) \
                or getattr(self.evaluator, "label_smoothing", 0.0) \
                or getattr(self.evaluator, "compute_confusion", False):
            return reject("needs plain softmax-CE evaluator")
        if self.mixed_precision or self.remat \
                or self.grad_accumulation > 1:
            return reject("amp/remat/grad-accumulation not fused")
        if self._tensormon is not None:
            return reject("tensormon taps are not computed by the "
                          "fused kernel — the general scan path keeps "
                          "the fused scale-bias-activation epilogue "
                          "(engine.fused_epilogue), so the elementwise "
                          "tail stays fused there; disable "
                          "telemetry.tensormon or fused_fc_scan")
        if self._pp is not None or self._pp_hetero is not None:
            return reject("pipeline mesh not fused")
        if isinstance(self.device, XLADevice) \
                and self.device.mesh.devices.size != 1:
            return reject("single-device only (the kernel owns the "
                          "whole update; no psum inside)")
        if self.param_masks:
            return reject("sparsity masks not fused")
        knobs = set()
        for f in fs:
            if set(self.params[f.name]) != {"weights", "bias"}:
                return reject("%s params beyond weights+bias (LoRA?)"
                              % f.name)
            if getattr(f, "freeze_base", False):
                return reject("%s is frozen (freeze_base) — the "
                              "kernel updates unconditionally" % f.name)
            gd = self._gd_for[f.name]
            if gd.solver != "sgd" or gd.gradient_clip \
                    or gd.gradient_clip_norm:
                return reject("%s: fused path is Znicz SGD only "
                              "(momentum/decay ok; no clipping)"
                              % f.name)
            knobs.add((float(gd.learning_rate),
                       float(gd.learning_rate_bias),
                       float(gd.weight_decay),
                       float(gd.weight_decay_bias),
                       float(gd.momentum)))
        if len(knobs) != 1:
            return reject("per-layer SGD knobs differ (uniform "
                          "lr/decay/momentum required)")
        # the kernel bakes ONE (A, B) tanh scaling for the whole chain
        # (fused_fc._kernel act_a/act_b) — a per-layer override would
        # silently diverge from the scan trajectory while still
        # claiming parity (ADVICE r4)
        acts = {(float(f.A), float(f.B)) for f in fs[:-1]}
        if len(acts) > 1:
            return reject("per-layer tanh (A, B) scales differ "
                          "(uniform activation required)")
        lr, lr_bias, wd, wd_bias, momentum = knobs.pop()
        if lr <= 0:
            return reject("non-positive learning rate")
        if getattr(self.loader, "device_augment_fn", None) is not None:
            return reject("device-side augmentation not fused")
        if self.target_mode != "labels":
            return reject("labels targets only")
        # VMEM budget: the kernel holds weights + biases + the delta
        # recurrence (×2) plus a minibatch block resident; an oversized
        # chain must FALL BACK, not die in an opaque Mosaic allocation
        # error inside the jitted epoch block. The residency estimate
        # is the kernel owner's (ops.fused_fc.analytic_cost
        # peak_memory) — ONE formula for the gate and the cost model
        from ..ops.fused_fc import analytic_cost as _ff_cost
        mb = self.loader.max_minibatch_size
        peak = _ff_cost([self.params[f.name]["weights"].shape
                         for f in fs], mb, steps=1).peak_memory
        budget = 12 * 2 ** 20          # leave headroom in ~16 MiB VMEM
        if peak > budget:
            return reject("VMEM budget: ~%.1f MiB state + batch "
                          "exceeds the %.0f MiB kernel budget"
                          % (peak / 2 ** 20, budget / 2 ** 20))
        ds = self.loader.original_data
        if ds is None or ds.mem.ndim != 2:
            return reject("flat (N, features) dataset only")
        self._fused_fc = {
            "lr": lr, "lr_bias_ratio": lr_bias / lr,
            "wd": wd, "wd_bias": wd_bias, "momentum": momentum,
            "act_a": float(fs[0].A), "act_b": float(fs[0].B),
            "names": tuple(f.name for f in fs),
            "interpret": not on_tpu,
        }
        self.info("fused_fc_scan engaged: whole-epoch Pallas SGD "
                  "kernel (%s)", " → ".join(f.name for f in fs))

    def _setup_pipeline(self) -> None:
        """{"pipeline": N} mesh axis: stage-group the forward chain and
        restructure the canonical pytree so each device on the axis holds
        only its stages' parameters (pipeline.py gpipe schedule inside
        the fused step — a capability the reference never had, SURVEY.md
        §2.4 'new capability' row)."""
        dev = self.device
        if not isinstance(dev, XLADevice):
            return
        mesh = dev.mesh
        n_stages = dict(mesh.shape).get("pipeline", 1)
        if n_stages <= 1:
            return
        if "sequence" in mesh.axis_names:
            # ring/Ulysses attention wraps its own shard_map over
            # 'sequence'; inside the pipeline's manual mesh region that
            # nests two manual meshes and XLA refuses with an opaque
            # context-mesh mismatch — fail at plan time with the real
            # reason instead (v1 scope: pipeline composes with
            # data/tensor/fsdp/expert, sequence composes with
            # data/tensor; not with each other)
            raise Bug(
                "'pipeline' and 'sequence' mesh axes cannot compose: "
                "sequence-parallel attention runs its own shard_map, "
                "which cannot nest inside the pipelined region. Drop "
                "one of the axes.")
        from ..parallel.pipeline import plan_pipeline
        from ..parallel.sharding import PP_BLOCK
        try:
            pre, block, post = plan_pipeline(self.forwards, n_stages)
        except ValueError as uniform_err:
            # no identical shape-preserving run: fall back to the
            # heterogeneous schedule (lax.switch per stage, padded-wire
            # ppermute ring) — AlexNet/ImagenetAE-shaped chains pipeline
            # too, trading parameter-memory scaling for compute overlap
            # (parallel/pipeline.py gpipe_hetero docstring)
            self._setup_pipeline_hetero(n_stages, mesh, uniform_err)
            return
        import jax.numpy as jnp
        names = [f.name for f in block]
        for masked in self.param_masks:
            if masked in names:
                raise Bug("ZeroFiller masks are not supported on "
                          "pipelined layers (%s)" % masked)
        stacked = {k: jnp.stack([self.params[n][k] for n in names])
                   for k in self.params[names[0]]}
        gd = self._gd_for[names[0]]
        for n in names:
            del self.params[n]
            del self.opt_state[n]
            del self._gd_for[n]
        self.params[PP_BLOCK] = stacked
        self.opt_state[PP_BLOCK] = gd.init_state(stacked)
        self._gd_for[PP_BLOCK] = gd
        # per-layer semantics (e.g. gradient_clip_norm) must survive the
        # stacking: tell the GD its tree now carries a leading layer axis
        gd.stacked_layers = len(names)
        n_micro = self._plan_microbatches(mesh, n_stages)
        self._pp = {"pre": pre, "block": block, "post": post,
                    "names": names, "n_stages": n_stages,
                    "n_micro": n_micro, "mesh": mesh}
        self.info("pipeline plan: %d stages x %d layers, %d microbatches "
                  "(%d pre, %d post replicated)",
                  n_stages, len(names) // n_stages, n_micro,
                  len(pre), len(post))

    @property
    def evaluation_mode(self) -> bool:
        return self._evaluation_mode

    @evaluation_mode.setter
    def evaluation_mode(self, value) -> None:
        """Entering evaluation mode downgrades epoch-block serving to the
        classic per-epoch loop: evaluation has no dispatch-amortization
        need, and a fused H-epoch block would re-evaluate the same sets H
        times — so ``--test`` of a snapshot trained with
        ``epochs_per_dispatch>1`` is a capability, not an error."""
        self._evaluation_mode = bool(value)
        loader = getattr(self, "loader", None)
        if self._evaluation_mode and loader is not None \
                and getattr(loader, "block_epochs", 1) > 1:
            loader.block_epochs = 1

    def _plan_microbatches(self, mesh, n_stages: int) -> int:
        """Resolve the microbatch count (default: one per stage) and
        check the divisibility chain: minibatch → microbatches →
        data-axis shards."""
        mb = self.loader.max_minibatch_size
        n_micro = int(self.pipeline_microbatches or n_stages)
        if mb % n_micro:
            raise Bug("minibatch size %d not divisible into %d pipeline "
                      "microbatches" % (mb, n_micro))
        n_data = dict(mesh.shape).get("data", 1)
        if (mb // n_micro) % n_data:
            raise Bug("pipeline microbatch size %d not divisible by "
                      "data-axis size %d" % (mb // n_micro, n_data))
        return n_micro

    def _setup_pipeline_hetero(self, n_stages, mesh, uniform_err) -> None:
        """Stage-group a shape-changing forward chain for the
        heterogeneous gpipe schedule. The head (last forward) stays
        outside the pipelined region so the softmax-logits/loss fusion
        and evaluator wiring are untouched; everything before it is
        split into ``n_stages`` contiguous groups balanced by the
        stage_cost FLOP proxy. Params remain per-unit (replicated over
        the axis), so snapshots, masks and the update loop are exactly
        the non-pipelined ones."""
        from ..parallel.pipeline import plan_pipeline_hetero
        pipe = self.forwards[:-1]
        try:
            stages = plan_pipeline_hetero(pipe, n_stages)
        except ValueError as e:
            raise Bug("%s (uniform-stage plan also failed: %s)"
                      % (e, uniform_err))
        n_micro = self._plan_microbatches(mesh, n_stages)
        self._pp_hetero = {"stages": stages, "post": [self.forwards[-1]],
                           "n_micro": n_micro, "mesh": mesh}
        # Quantify the documented memory trade (VERDICT r4 item 8)
        # instead of just naming it: per-stage param bytes, the
        # transient in-region gather (lax.switch needs every branch's
        # operands, so ALL stages' params are device-resident during
        # the pipelined region), and — when 'fsdp' coexists — the
        # persistent-storage scaling the sharding planner already
        # applies to these per-unit params (param_shardings shards
        # them over 'fsdp'/'tensor' exactly like non-pipelined ones;
        # only the transient peak stays O(total)).
        def _stage_bytes(us):
            return sum(a.nbytes for f in us if f.PARAMETERIZED
                       for a in f.param_arrays().values())
        per_stage = [_stage_bytes(us) for us in stages]
        total_mb = sum(per_stage) / 2 ** 20
        n_fsdp = dict(mesh.shape).get("fsdp", 1)
        self.info(
            "heterogeneous pipeline plan: %d stages (%s units each), %d "
            "microbatches; stage params %s MiB, transient in-region "
            "gather %.2f MiB/device, persistent storage %s",
            n_stages, "/".join(str(len(s)) for s in stages), n_micro,
            "/".join("%.2f" % (b / 2 ** 20) for b in per_stage),
            total_mb,
            ("~%.2f MiB/device (fsdp=%d shards the divisible params)"
             % (total_mb / n_fsdp, n_fsdp) if n_fsdp > 1
             else "%.2f MiB/device (replicated — add an 'fsdp' axis "
                  "to shard it)" % total_mb))
        self._pp_hetero["stage_param_bytes"] = per_stage

    def _setup_shardings(self) -> None:
        """SPMD parallelism from mesh axes (see veles_tpu/parallel/):
        minibatch sharded over 'data' (grad psum over ICI — the reference's
        entire ZeroMQ master–slave plane, veles/server.py + veles/client.py,
        collapses to this annotation); params sharded over 'tensor'
        (column-parallel kernels) and/or 'fsdp' (ZeRO-3 style) when those
        axes exist, else replicated. XLA inserts every collective."""
        self._shardings = None
        dev = self.device
        if not isinstance(dev, XLADevice):
            return
        mesh = dev.mesh
        if mesh.devices.size <= 1:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.sharding import param_shardings, replicated
        repl = replicated(mesh)
        if "data" in mesh.axis_names:
            batch = NamedSharding(mesh, P("data"))
            n_data = mesh.shape["data"]
            if self.loader.max_minibatch_size % n_data:
                raise Bug(
                    "minibatch size %d not divisible by data-axis size %d"
                    % (self.loader.max_minibatch_size, n_data))
        else:
            batch = repl
        self._shardings = {"repl": repl, "batch": batch}
        from ..parallel.sharding import state_shardings
        pspec = param_shardings(self.params, mesh)
        sspec = state_shardings(self.opt_state, self.params, pspec, mesh)
        self.params = jax.tree_util.tree_map(
            jax.device_put, self.params, pspec)
        self.opt_state = jax.tree_util.tree_map(
            jax.device_put, self.opt_state, sspec)

    def register_param_mask(self, unit_name: str, key: str, mask) -> None:
        """Install (or refresh) a sparsity mask enforced after every update
        inside the compiled step. Masks are baked into the jitted program as
        constants, so (re)registration invalidates the jit cache — callers
        re-registering an identical mask are a no-op (checked host-side:
        no device transfer or stream sync on the steady-state path)."""
        if self._pp is not None and unit_name in self._pp["names"]:
            raise Bug("ZeroFiller masks are not supported on pipelined "
                      "layers (%s)" % unit_name)
        m_np = numpy.asarray(mask)
        cur_np = self._param_masks_np.get((unit_name, key))
        if cur_np is not None and numpy.array_equal(cur_np, m_np):
            return
        self._param_masks_np[(unit_name, key)] = m_np
        import jax.numpy as jnp
        m = jnp.asarray(m_np)
        self.param_masks.setdefault(unit_name, {})[key] = m
        self._jit_cache.clear()
        # enforce immediately on the canonical pytree too
        if self.params.get(unit_name) and key in self.params[unit_name]:
            p = dict(self.params[unit_name])
            p[key] = p[key] * m.astype(p[key].dtype)
            self.params[unit_name] = p

    @property
    def _step_impl(self):
        return (self._train_step_accum_fn if self.grad_accumulation > 1
                else self._train_step_fn)

    # -- pure functions -------------------------------------------------------
    def _apply_chain(self, units, params, x, train: bool, rng, base: int):
        """Apply a replicated run of forwards (``base`` offsets the
        per-layer rng streams); the softmax head yields logits when the
        evaluator fuses the stable cross-entropy. The single copy of
        the head-handling loop all three forward paths share.

        Epilogue plan active: each producer's planned elementwise
        tails apply through ``ops.fused_fc.apply_epilogue`` right
        after it and are skipped at their own position — the SAME ops
        in the SAME order (and enumerate indices, hence dropout rng
        streams, unchanged), so the traced program is bit-identical
        to the unfused chain. ``bf16_activations``: interlayer
        activations that left a unit as float32 are stored bfloat16
        (masters, loss and metric accumulation stay f32 — this knob
        only changes what lives in HBM between layers)."""
        import jax
        import jax.numpy as jnp
        from ..ops.fused_fc import apply_epilogue
        last = self.forwards[-1] if self.forwards else None
        use_logits = (isinstance(last, All2AllSoftmax)
                      and isinstance(self.evaluator, EvaluatorSoftmax))
        folded = set()
        prod_tails = {}
        if self._epilogue:
            for prod, tails in self._epilogue:
                prod_tails[id(prod)] = tails
                folded.update(id(t) for t in tails)
        for i, f in enumerate(units):
            if id(f) in folded:
                continue        # applied by its producer's epilogue
            layer_rng = (jax.random.fold_in(rng, base + i)
                         if rng is not None else None)
            p = params.get(f.name, {})
            if f is last and use_logits:
                return f.logits(p, x)
            x = f.apply(p, x, train=train, rng=layer_rng)
            tails = prod_tails.get(id(f))
            if tails:
                x = apply_epilogue(x, tails, train=train)
            # the HEAD output feeds the evaluator (which upcasts to
            # f32 itself) — only INTERLAYER activations store bf16
            head = f is last or (tails and tails[-1] is last)
            if self._bf16_acts and not head \
                    and x.dtype == jnp.float32:
                x = x.astype(jnp.bfloat16)
        return x

    def _forward_pure(self, params, x, train: bool, rng):
        """Compose the forward chain; softmax head yields logits for the
        fused stable cross-entropy."""
        if self._pp is not None:
            return self._forward_pure_pp(params, x, train, rng)
        if self._pp_hetero is not None:
            return self._forward_pure_pp_hetero(params, x, train, rng)
        return self._apply_chain(self.forwards, params, x, train, rng, 0)

    def _forward_pure_pp(self, params, x, train: bool, rng):
        """Pipelined forward: pre-chain replicated → gpipe over the
        stage-grouped block (ppermute ring inside shard_map; jax.grad
        derives the reverse schedule) → post-chain replicated. Dropout
        inside the block runs rng-less (deterministic) — per-layer rng
        streams do not thread through the stage scan."""
        import jax
        from jax.sharding import PartitionSpec as P
        from ..parallel.pipeline import gpipe, microbatch, unmicrobatch
        from ..parallel.sharding import PP_BLOCK
        pp = self._pp
        x = self._apply_chain(pp["pre"], params, x, train, rng, 0)
        mesh = pp["mesh"]
        n_stages, n_micro = pp["n_stages"], pp["n_micro"]
        layers_per_stage = len(pp["names"]) // n_stages
        staged = jax.tree_util.tree_map(
            lambda a: a.reshape((n_stages, layers_per_stage)
                                + a.shape[1:]),
            params[PP_BLOCK])
        block_apply = pp["block"][0].apply

        def stage_fn(stage_params, h):
            # stage_params leaves: (layers_per_stage, …) — this stage's
            # slice; scan composes its layers
            def body(h, layer_p):
                return block_apply(layer_p, h, train=train, rng=None), None
            h, _ = jax.lax.scan(body, h, stage_params)
            return h

        bspec = (P(None, "data") if "data" in mesh.axis_names else P())
        xs = microbatch(x, n_micro)
        y = gpipe(stage_fn, staged, xs, mesh, batch_spec=bspec)
        x = unmicrobatch(y)
        return self._apply_chain(pp["post"], params, x, train, rng, 1000)

    def _forward_pure_pp_hetero(self, params, x, train: bool, rng):
        """Heterogeneous pipelined forward: the staged chain runs under
        gpipe_hetero (lax.switch selects each device's stage; activations
        hop the ppermute ring as padded flat buffers), the head runs
        replicated after. Dropout inside stages is rng-less, as in the
        uniform schedule."""
        from jax.sharding import PartitionSpec as P
        from ..parallel.pipeline import (gpipe_hetero, microbatch,
                                         unmicrobatch)
        pp = self._pp_hetero
        mesh = pp["mesh"]

        def make_stage(units):
            def stage_fn(stage_params, h):
                for f in units:
                    h = f.apply(stage_params.get(f.name, {}), h,
                                train=train, rng=None)
                return h
            return stage_fn

        stage_fns = [make_stage(us) for us in pp["stages"]]
        stage_params = [
            {f.name: params.get(f.name, {})
             for f in us if f.PARAMETERIZED}
            for us in pp["stages"]]
        bspec = (P(None, "data") if "data" in mesh.axis_names else P())
        xs = microbatch(x, pp["n_micro"])
        y = gpipe_hetero(stage_fns, stage_params, xs, mesh,
                         batch_spec=bspec)
        x = unmicrobatch(y)
        return self._apply_chain(pp["post"], params, x, train, rng, 1000)

    def _gather(self, dataset, indices):
        import jax.numpy as jnp
        return jnp.take(dataset, indices, axis=0)

    def _amp_cast(self, tree):
        """bf16 view of a float32 pytree (mixed_precision): autodiff
        through the cast returns float32 grads for the f32 masters."""
        import jax
        import jax.numpy as jnp

        def cast(a):
            return (a.astype(jnp.bfloat16)
                    if hasattr(a, "dtype") and a.dtype == jnp.float32
                    else a)
        return jax.tree_util.tree_map(cast, tree)

    def _amp_cast_params(self, params):
        """``_amp_cast`` of the parameters, but for the leaves a unit
        names in ``AMP_FLOAT32`` (a router's scores, a decay's rate, the
        matrices a unit casts itself a block at a time): those reach the
        unit in float32."""
        keep = {f.name: f.AMP_FLOAT32 for f in self.forwards
                if getattr(f, "AMP_FLOAT32", ())}
        if not keep:
            return self._amp_cast(params)
        return {name: ({k: v if k in keep[name] else self._amp_cast(v)
                        for k, v in p.items()}
                       if name in keep else self._amp_cast(p))
                for name, p in params.items()}

    def _forward_taps(self, p, batch, rng):
        """The training forward pass and what its units counted on the
        way (telemetry/steptaps.py): (out, {tap key: scalar}), the second
        empty where no unit of a plain chain counts anything."""
        import jax
        from ..telemetry import steptaps

        def fwd(pp, bb):
            if not self._step_taps:
                return self._forward_pure(pp, bb, True, rng), {}
            with steptaps.collecting() as got:
                out = self._forward_pure(pp, bb, True, rng)
            return out, dict(got)
        return jax.checkpoint(fwd)(p, batch) if self.remat else fwd(p, batch)

    def _target_for(self, batch, labels, targets, indices):
        if self.target_mode == "labels":
            return self._gather(labels, indices)
        if self.target_mode == "input":
            return batch
        if self.target_mode == "targets":
            if getattr(self.loader, "targets_by_label", False):
                # per-label template TABLE: row → label → template,
                # composed gathers (the table is n_labels rows, stored
                # once — never materialized per dataset row)
                return self._gather(targets,
                                    self._gather(labels, indices))
            return self._gather(targets, indices)
        raise Bug("bad target_mode %r" % self.target_mode)

    def _train_step_fn(self, params, opt_state, accum, dataset, labels,
                       targets, indices, mask, lr_scale, rng):
        import jax
        batch = self._gather(dataset, indices)
        # loader-supplied on-device augmentation (e.g. random mirror/crop
        # fused into the step — loader/image.py device_augmentation)
        aug = getattr(self.loader, "device_augment_fn", None)
        if aug is not None:
            batch = aug(batch, jax.random.fold_in(rng, 0x417))
        tgt = self._target_for(batch, labels, targets, indices)
        if self.mixed_precision:
            batch = self._amp_cast(batch)

        # the scopes name the step's device work in a profiler capture
        # (`veles_tpu trace self-time`; trace-time only). jax marks the
        # operations of the backward pass itself, as
        # transpose(jvp(forward)), which the reader calls "backward"
        def loss_fn(p):
            with jax.named_scope("forward"):
                if self.mixed_precision:
                    p = self._amp_cast_params(p)
                out, taps = self._forward_taps(p, batch, rng)
            with jax.named_scope("loss"):
                return self.evaluator.loss(out, tgt, mask), (out, taps)

        (loss, (out, taps)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        valid = mask.sum() > 0  # all-padded plan rows must not decay params
        new_params, new_opt = self._apply_updates(params, grads,
                                                  opt_state, lr_scale,
                                                  valid)
        with jax.named_scope("accumulate"):
            metrics = self.evaluator.metrics_fn(out, tgt, mask)
            metrics["sum_loss"] = loss * self.evaluator.sum_loss_weight(
                out, mask)
            metrics.update(taps)
        if self._tensormon is not None:
            # auxiliary tensor taps (telemetry/tensormon.py): pure
            # scalars over values this step already computed — extra
            # accumulator outputs, zero extra dispatches or host syncs
            from ..telemetry import tensormon
            metrics.update(tensormon.step_stats(
                params, new_params, grads, loss, out,
                self._tensormon["sat_threshold"]))
        with jax.named_scope("accumulate"):
            accum = jax.tree_util.tree_map(
                lambda a, m: a + m, accum,
                {k: metrics[k] for k in accum})
        return new_params, new_opt, accum, loss

    def _apply_updates(self, params, grads, opt_state, lr_scale, valid):
        """One copy of the optimizer application (per-unit GD rules,
        all-padded-row gating, sparsity masks), shared by the direct
        and gradient-accumulating steps."""
        import jax
        import jax.numpy as jnp
        new_params, new_opt = {}, {}
        for name, p in params.items():
            gd = self._gd_for[name]
            with jax.named_scope("optimizer"), jax.named_scope(name):
                up_p, up_s = gd.update(p, grads[name], opt_state[name],
                                       lr_scale)
                new_params[name] = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(valid, new, old), up_p, p)
                new_opt[name] = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(valid, new, old), up_s,
                    opt_state[name])
        for name, masks in self.param_masks.items():
            if name in new_params:
                for k, m in masks.items():
                    # cast: the product must keep the param dtype or the
                    # scan carry structure would change
                    new_params[name][k] = (new_params[name][k]
                                           * m.astype(new_params[name][k].dtype))
        return new_params, new_opt

    def _train_step_accum_fn(self, params, opt_state, accum, dataset,
                             labels, targets, indices, mask, lr_scale,
                             rng):
        """Gradient accumulation (``grad_accumulation=G``): the
        minibatch splits into G sequential chunks; the forward/backward
        runs per chunk (activation memory ∝ mb/G) and ONE optimizer
        step applies the valid-count-weighted mean of the chunk
        gradients — exactly the full-minibatch gradient up to reduction
        order (chunk losses are valid-masked means, so chunk grads are
        recombined with w_c/Σw weights). Dropout streams fold per
        chunk, so rng-using nets match the direct step only in
        distribution."""
        import jax
        import jax.numpy as jnp
        ga = self.grad_accumulation
        # the monitor's aux entries accumulate from the FINAL aggregate
        # (mean gradient + the one applied update), not per chunk —
        # split them out so the chunk scan carries the classic key set
        mon_zero = {k: v for k, v in accum.items()
                    if k.startswith("mon_")}
        accum = {k: v for k, v in accum.items()
                 if not k.startswith("mon_")}
        batch = self._gather(dataset, indices)
        aug = getattr(self.loader, "device_augment_fn", None)
        if aug is not None:
            batch = aug(batch, jax.random.fold_in(rng, 0x417))
        tgt = self._target_for(batch, labels, targets, indices)
        if self.mixed_precision:
            batch = self._amp_cast(batch)
        mb = batch.shape[0]

        def chunk(x):
            return x.reshape((ga, mb // ga) + x.shape[1:])

        total = jnp.maximum(mask.sum().astype(jnp.float32), 1.0)

        def body(carry, xs):
            g_sum, l_sum, a = carry
            b_i, t_i, m_i, ci = xs

            def loss_fn(p):
                if self.mixed_precision:
                    p = self._amp_cast_params(p)
                chunk_rng = jax.random.fold_in(rng, ci)
                if self.remat:
                    out = jax.checkpoint(
                        lambda pp, bb: self._forward_pure(
                            pp, bb, True, chunk_rng))(p, b_i)
                else:
                    out = self._forward_pure(p, b_i, True, chunk_rng)
                return self.evaluator.loss(out, t_i, m_i), out

            (loss, out), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            w = m_i.sum().astype(jnp.float32)
            g_sum = jax.tree_util.tree_map(
                lambda s, gg: s + gg.astype(jnp.float32) * w, g_sum, g)
            metrics = self.evaluator.metrics_fn(out, t_i, m_i)
            metrics["sum_loss"] = loss * self.evaluator.sum_loss_weight(
                out, m_i)
            a = jax.tree_util.tree_map(
                lambda av, m: av + m, a, {k: metrics[k] for k in a})
            return (g_sum, l_sum + loss * w, a), None

        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g_sum, l_sum, accum), _ = jax.lax.scan(
            body, (zero_g, jnp.float32(0.0), accum),
            (chunk(batch), chunk(tgt), chunk(mask),
             jnp.arange(ga)))
        grads = jax.tree_util.tree_map(
            lambda s, p: (s / total).astype(p.dtype), g_sum, params)
        valid = mask.sum() > 0
        new_params, new_opt = self._apply_updates(params, grads,
                                                  opt_state, lr_scale,
                                                  valid)
        if mon_zero:
            from ..telemetry import tensormon
            stats = tensormon.step_stats(
                params, new_params, grads, l_sum / total, None,
                self._tensormon["sat_threshold"])
            accum = dict(accum)
            accum.update({k: mon_zero[k] + stats[k] for k in mon_zero})
        return new_params, new_opt, accum, l_sum / total

    def _train_plan_fn(self, params, opt_state, accum, dataset, labels,
                       targets, idx_plan, mask_plan, lr_scale, rng):
        """lax.scan over a (K, mb) index plan: K optimizer steps in ONE
        dispatch. The TPU-era answer to per-unit dispatch overhead —
        sequential dependence between steps is real (param updates), so
        scan, not vmap."""
        import jax

        def body(carry, xs):
            p, o, a = carry
            idx, msk, i = xs
            step_rng = jax.random.fold_in(rng, i)
            p, o, a, loss = self._step_impl(
                p, o, a, dataset, labels, targets, idx, msk, lr_scale,
                step_rng)
            return (p, o, a), loss
        import jax.numpy as jnp
        steps = jnp.arange(idx_plan.shape[0])
        (params, opt_state, accum), losses = jax.lax.scan(
            body, (params, opt_state, accum), (idx_plan, mask_plan, steps))
        return params, opt_state, accum, losses[-1]

    def _eval_step_fn(self, params, accum, dataset, labels, targets,
                      indices, mask):
        import jax
        batch = self._gather(dataset, indices)
        ev = getattr(self.loader, "device_eval_fn", None)
        if ev is not None:
            batch = ev(batch)       # deterministic center crop
        tgt = self._target_for(batch, labels, targets, indices)
        if self.mixed_precision:
            batch = self._amp_cast(batch)
            params = self._amp_cast_params(params)
        out = self._forward_pure(params, batch, False, None)
        metrics = self.evaluator.metrics_fn(out, tgt, mask)
        metrics["sum_loss"] = (self.evaluator.loss(out, tgt, mask)
                               * self.evaluator.sum_loss_weight(out,
                                                                mask))
        return jax.tree_util.tree_map(
            lambda a, m: a + m, accum, {k: metrics[k] for k in accum})

    def _eval_plan_fn(self, params, accum, dataset, labels, targets,
                      idx_plan, mask_plan):
        import jax

        def body(a, xs):
            idx, msk = xs
            return self._eval_step_fn(params, a, dataset, labels, targets,
                                      idx, msk), None
        accum, _ = jax.lax.scan(body, accum, (idx_plan, mask_plan))
        return accum

    def _make_zero_accum(self, mon: bool = False):
        """``mon=True`` (train contexts with tensormon enabled) adds
        the monitor's auxiliary accumulator entries — eval accums and
        monitoring-off runs carry exactly the classic key set."""
        import jax.numpy as jnp
        from .evaluator import EvaluatorSoftmaxSeq
        zeros = {"n_samples": jnp.zeros((), jnp.float32),
                 "sum_loss": jnp.zeros((), jnp.float32)}
        if isinstance(self.evaluator, (EvaluatorSoftmax,
                                       EvaluatorSoftmaxSeq)):
            zeros["n_err"] = jnp.zeros((), jnp.float32)
        else:
            zeros["sum_sq"] = jnp.zeros((), jnp.float32)
        if mon and self._tensormon is not None:
            from ..telemetry import tensormon
            zeros.update(tensormon.zero_stats(sorted(self.params)))
        if mon:
            zeros.update({k: jnp.zeros((), jnp.float32)
                          for k in self._step_taps})
        return zeros

    # -- execution -----------------------------------------------------------
    def _inputs(self):
        loader = self.loader
        sh = self._shardings
        repl = sh["repl"] if sh else None
        batch = sh["batch"] if sh else None
        ds_sh = repl
        if sh is not None and getattr(loader, "shard_dataset", False):
            mesh = repl.mesh
            if "data" in mesh.axis_names and mesh.shape["data"] > 1:
                n_data = mesh.shape["data"]
                n_rows = loader.original_data.shape[0]
                if n_rows % n_data:
                    # the stored array is what shards, not the (possibly
                    # train_ratio-subsetted) logical sample count
                    raise Bug(
                        "shard_dataset: dataset of %d rows not "
                        "divisible by data-axis size %d"
                        % (n_rows, n_data))
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                ds_sh = NamedSharding(mesh, P("data"))
            elif mesh.devices.size > 1 and \
                    not getattr(self, "_warned_shard_dataset", False):
                self._warned_shard_dataset = True   # once, not per step
                self.warning(
                    "%s: shard_dataset=True but the mesh has no 'data' "
                    "axis (>1) — dataset stays fully replicated on "
                    "every chip", loader.name)
        dataset = loader.original_data.device_view(sharding=ds_sh)
        labels = (loader.original_labels.device_view(sharding=ds_sh)
                  if loader.original_labels else None)
        targets = getattr(loader, "original_targets", None)
        # a label-indexed table has n_labels rows, not n_rows — row
        # sharding over 'data' would be wrong AND wasteful (it is tiny:
        # replicate it)
        tgt_sh = (repl if getattr(loader, "targets_by_label", False)
                  else ds_sh)
        targets = (targets.device_view(sharding=tgt_sh)
                   if targets is not None and targets else dataset)
        if labels is None:
            labels = self._dummy_labels(dataset)
        if batch is not None and loader.plan_steps > 1 \
                and "data" in batch.mesh.axis_names:
            # plans are (K, mb): shard the minibatch axis, not the scan axis
            from jax.sharding import NamedSharding, PartitionSpec as P
            batch = NamedSharding(batch.mesh, P(None, "data"))
        indices = loader.minibatch_indices.device_view(sharding=batch)
        mask = loader.minibatch_mask.device_view(sharding=batch)
        return dataset, labels, targets, indices, mask

    def _dummy_labels(self, dataset):
        import jax.numpy as jnp
        return jnp.zeros((dataset.shape[0],), jnp.int32)

    def _epoch_block_fn(self, params, opt_state, dataset, labels,
                        targets, xs_template_keys, xs, rng):
        """H whole epochs in one program: lax.scan over epochs; each
        epoch runs the eval plans (test, validation) then the train
        plan, in the classic loop's offset order. Per-epoch metric
        accums come back stacked (H,) for the Decision to replay."""
        import jax

        def one_epoch(carry, per_epoch):
            p, o = carry
            e_rng = jax.random.fold_in(rng, per_epoch["e"])
            outs = {}
            for cls in (TEST, VALID):
                key = "c%d" % cls
                if key + "_idx" not in xs_template_keys:
                    continue
                acc = self._eval_plan_fn(
                    p, self._make_zero_accum(), dataset, labels,
                    targets, per_epoch[key + "_idx"],
                    per_epoch[key + "_mask"])
                outs[cls] = acc
            if getattr(self, "_fused_fc_active", False):
                # whole-epoch Pallas SGD kernel (ops/fused_fc.py):
                # weights AND the SGD delta recurrence stay VMEM-
                # resident for all K steps; both are returned so
                # opt_state continues the identical trajectory.
                import jax.numpy as jnp
                from ..ops.fused_fc import fused_fc_sgd_epoch
                ff = self._fused_fc
                names = ff["names"]
                plan = per_epoch["c%d_idx" % TRAIN]
                ws, bs, vws, vbs, loss_sum, err = fused_fc_sgd_epoch(
                    [p[n]["weights"] for n in names],
                    [p[n]["bias"] for n in names],
                    [o[n]["weights"] for n in names],
                    [o[n]["bias"] for n in names],
                    dataset, labels, plan,
                    per_epoch["lr"] * ff["lr"],
                    act_a=ff["act_a"], act_b=ff["act_b"],
                    lr_bias_ratio=ff["lr_bias_ratio"],
                    wd=ff["wd"], wd_bias=ff["wd_bias"],
                    momentum=ff["momentum"], interpret=ff["interpret"])
                p, o = dict(p), dict(o)
                for i2, n2 in enumerate(names):
                    p[n2] = {"weights": ws[i2], "bias": bs[i2]}
                    o[n2] = {"weights": vws[i2], "bias": vbs[i2]}
                n = jnp.float32(plan.shape[0] * plan.shape[1])
                outs[TRAIN] = {"n_samples": n, "sum_loss": loss_sum,
                               "n_err": err}
                # the general path reports the LAST batch's mean loss;
                # the kernel returns the epoch sum — report the epoch
                # mean (same scale, logging-only)
                return (p, o), (outs, loss_sum / n)
            p, o, acc_tr, loss = self._train_plan_fn(
                p, o, self._make_zero_accum(mon=True), dataset, labels,
                targets,
                per_epoch["c%d_idx" % TRAIN],
                per_epoch["c%d_mask" % TRAIN],
                per_epoch["lr"], e_rng)
            outs[TRAIN] = acc_tr
            return (p, o), (outs, loss)

        (params, opt_state), (stacked, losses) = jax.lax.scan(
            one_epoch, (params, opt_state), xs)
        return params, opt_state, stacked, losses[-1]

    def _run_epoch_block(self) -> None:
        import jax
        import numpy as _np
        from ..telemetry.counters import inc
        from ..telemetry.spans import span
        loader = self.loader
        dataset, labels, targets, _, _ = self._inputs()
        sh = self._shardings
        plan_sh = None
        if sh is not None and "data" in sh["repl"].mesh.axis_names:
            from jax.sharding import NamedSharding, PartitionSpec as P
            plan_sh = NamedSharding(sh["repl"].mesh,
                                    P(None, None, "data"))
        # the loader may have clamped the FINAL block below H
        # (block_epochs_cap); slice the host plans to what was served —
        # the tail block traces/compiles once at its own scan length
        h = loader.block_length or loader.block_epochs
        xs = {"e": _np.arange(h, dtype=_np.int32)}
        for cls, (idx, mask) in sorted(loader.block_plans.items()):
            if cls != TRAIN:
                # eval plans never change (only the TRAIN tail of the
                # shuffle permutes per epoch): upload once per scan
                # length, reuse the device copies across blocks
                cached = self._eval_plan_dev.get((cls, h))
                if cached is None:
                    idx_h = idx.map_read()[:h]
                    mask_h = mask.map_read()[:h]
                    inc("veles_h2d_bytes_total",
                        idx_h.nbytes + mask_h.nbytes)
                    cached = (jax.device_put(idx_h, plan_sh),
                              jax.device_put(mask_h, plan_sh))
                    self._eval_plan_dev[(cls, h)] = cached
                xs["c%d_idx" % cls], xs["c%d_mask" % cls] = cached
                continue
            idx_h, mask_h = idx.map_read()[:h], mask.map_read()[:h]
            inc("veles_h2d_bytes_total", idx_h.nbytes + mask_h.nbytes)
            xs["c%d_idx" % cls] = jax.device_put(idx_h, plan_sh)
            xs["c%d_mask" % cls] = jax.device_put(mask_h, plan_sh)
        # per-epoch LR scales from the schedule, host-evaluated exactly
        # as the classic loop would have (epoch k trains at schedule(k))
        lr_adjust = getattr(self.workflow, "lr_adjust", None)
        decision = getattr(self.workflow, "decision", None)
        e0 = decision.epoch_number if decision is not None else 0
        if lr_adjust is not None:
            scales = [float(lr_adjust.schedule(e0 + i)) for i in range(h)]
        else:
            scales = [float(self.lr_scale)] * h
        xs["lr"] = _np.asarray(scales, dtype=_np.float32)
        keys = frozenset(xs)

        # fused kernel assumes whole minibatches: any padded plan row
        # (partial tail batch) falls back to the masked general path.
        # The flag keys the jit cache — flipping it must not reuse the
        # other variant's trace.
        self._fused_fc_active = (
            self._fused_fc is not None
            and all(float(m.map_read()[:h].min()) >= 1.0
                    for cls, (i_, m) in loader.block_plans.items()
                    if cls == TRAIN))

        def fn(params, opt_state, dataset, labels, targets, xs, rng):
            return self._epoch_block_fn(params, opt_state, dataset,
                                        labels, targets, keys, xs, rng)

        jitted = self.jit(
            "epoch_block_fused" if self._fused_fc_active
            else "epoch_block", fn, donate_argnums=(0, 1))
        with span("train_step.epoch_block", unit=self.name, epochs=h,
                  fused_fc=bool(self._fused_fc_active)):
            self.params, self.opt_state, stacked, self.last_loss = \
                jitted(self.params, self.opt_state, dataset, labels,
                       targets, xs, self._rng.jax_key())
        # stays on device until the Decision drains: the host must NOT
        # block here, or consecutive blocks lose their async overlap
        self._block_metrics = (stacked, h)

    def drain_epoch_blocks(self) -> List[Dict[int, Dict[str, float]]]:
        """Per-epoch metric dicts since the last drain: H entries after
        a block dispatch, one entry in the classic per-epoch mode.
        When tensormon is on, the monitor's auxiliary entries ride this
        SAME drain (zero extra host syncs), are stripped before the
        Decision sees the dicts, and the NaN sentinel may raise
        :class:`~veles_tpu.telemetry.tensormon.ModelHealthError` here —
        on the scheduler path, exactly where a crashed dispatch would
        have surfaced."""
        if self._block_metrics is not None:
            import jax
            from ..telemetry.counters import inc
            stacked, h = self._block_metrics
            self._block_metrics = None
            host = jax.device_get(stacked)
            inc("veles_d2h_bytes_total",
                sum(a.nbytes for a in jax.tree_util.tree_leaves(host)))
            entries = [
                {cls: {k: float(v[e]) for k, v in acc.items()}
                 for cls, acc in host.items()}
                for e in range(h)]
        else:
            entries = [self.drain_epoch_metrics()]
        if self._tensormon is not None:
            from ..telemetry import tensormon
            for mon in tensormon.extract_mon(entries, TRAIN):
                tensormon.monitor.observe(self, mon)
        if self._step_taps:
            from ..telemetry import steptaps
            steptaps.publish(steptaps.extract(entries, TRAIN))
        return entries

    def cost_report(self):
        """Telemetry cost of every program this unit has dispatched
        (``AcceleratedUnit.program_cost`` per jit key), with the
        analytic fused-FC cost merged in when the Pallas kernel is
        active — the custom call is opaque to XLA's HLO cost model, so
        the kernel's FLOPs/bytes come from ``ops.fused_fc.
        analytic_cost``. Returns ``{"key", "cost", "costs"}`` (primary
        key + its cost, plus per-key costs so sections that mix
        programs — classic mode runs 'train' AND 'eval' per epoch —
        bill each dispatch at its own program's cost) or None before
        the first dispatch. This is what bench.py's measured-MFU rows
        read."""
        costs = {}
        for key in ("epoch_block_fused", "epoch_block", "train",
                    "eval"):
            if key not in self._jit_arg_shapes:
                continue
            cost = self.program_cost(key)
            if cost is None:
                continue
            if key == "epoch_block_fused" and self._fused_fc is not None:
                from ..ops import fused_fc as _ff
                names = self._fused_fc["names"]
                shapes = [self.params[n]["weights"].shape
                          for n in names]
                loader = self.loader
                h = loader.block_length or loader.block_epochs
                per_epoch = _ff.analytic_cost(
                    shapes, loader.max_minibatch_size,
                    loader.plan_steps)
                cost = cost + per_epoch.scaled(h)
            costs[key] = cost
        if not costs:
            return None
        primary = next(iter(costs))
        return {"key": primary, "cost": costs[primary], "costs": costs}

    def xla_run(self) -> None:
        import jax
        if self.loader.block_epochs > 1:
            if self.evaluation_mode:
                raise Bug("epochs_per_dispatch>1 requires training mode")
            return self._run_epoch_block()
        cls = self.loader.minibatch_class
        accum = self._accum.get(cls)
        if accum is None:
            # fresh zeros per class: accum buffers are donated to the step
            accum = self._accum[cls] = self._make_zero_accum(
                mon=(cls == TRAIN and not self.evaluation_mode))
        dataset, labels, targets, indices, mask = self._inputs()
        planned = self.loader.plan_steps > 1
        if cls == TRAIN and not self.evaluation_mode:
            fn = self.jit("train",
                          self._train_plan_fn if planned
                          else self._step_impl,
                          donate_argnums=(0, 1, 2))
            self.params, self.opt_state, self._accum[cls], self.last_loss \
                = fn(self.params, self.opt_state, accum, dataset, labels,
                     targets, indices, mask,
                     numpy.float32(self.lr_scale), self._rng.jax_key())
        else:
            fn = self.jit("eval",
                          self._eval_plan_fn if planned
                          else self._eval_step_fn, donate_argnums=(1,))
            self._accum[cls] = fn(self.params, accum, dataset, labels,
                                  targets, indices, mask)

    def numpy_run(self) -> None:
        # the fused step IS jax; on the numpy device it runs un-jitted on
        # host arrays (oracle path exercised by tests via forwards'
        # numpy_apply separately)
        self.xla_run()

    # -- epoch drain (Decision pulls these) ----------------------------------
    def drain_epoch_metrics(self) -> Dict[int, Dict[str, float]]:
        import jax
        from ..telemetry.counters import inc
        out = {}
        drained = 0
        for cls, accum in self._accum.items():
            host = jax.device_get(accum)
            drained += sum(a.nbytes
                           for a in jax.tree_util.tree_leaves(host))
            out[cls] = {k: float(v) for k, v in host.items()}
        if drained:
            inc("veles_d2h_bytes_total", drained)
        self._accum.clear()
        return out

    # -- checkpoint/pickle support -------------------------------------------
    def sync_params_to_arrays(self) -> None:
        """Copy the canonical device params back into the forwards' host
        Arrays (so snapshots and host-side units observe trained weights).
        Host copies, not buffer refs: the step donates its param buffers on
        the next dispatch, which would leave the Arrays dangling."""
        from ..parallel.distributed import fetch_global
        from ..parallel.sharding import PP_BLOCK
        pp_names = self._pp["names"] if self._pp is not None else []
        # fetch_global, not device_get: fsdp/tensor params on a multi-
        # process mesh span non-addressable devices and must all-gather
        # (every rank reaches here — see fetch_global's collective note)
        host = fetch_global(self.params)
        stacked = host.get(PP_BLOCK, {}) if pp_names else {}
        for f in self.forwards:
            if not f.PARAMETERIZED:
                continue
            arrays = f.param_arrays()
            if f.name in pp_names:
                i = pp_names.index(f.name)
                for k in arrays:
                    arrays[k].reset(numpy.array(stacked[k][i]))
                continue
            for k, v in host.get(f.name, {}).items():
                arrays[k].reset(numpy.array(v))

    def stop(self) -> None:
        if self.params:
            # workflow stop fires on every rank in the same order
            from ..parallel.distributed import lockstep
            with lockstep():
                self.sync_params_to_arrays()

    # -- checkpoint protocol -------------------------------------------------
    def on_snapshot(self) -> None:
        if self.params:
            self.sync_params_to_arrays()

    def state_dict(self):
        import jax
        from ..parallel.distributed import fetch_global
        opt = fetch_global(self.opt_state)
        if self._pp is not None:
            # snapshots stay per-layer so a checkpoint moves freely
            # between pipeline topologies (resume-with-different-mesh
            # guarantee, SURVEY.md §5.4). Works for any state structure:
            # per-param buffers unstack along the layer axis, scalars
            # (e.g. Adam's shared step counter) copy to every layer.
            from ..parallel.sharding import PP_BLOCK
            blk = opt.pop(PP_BLOCK)
            for i, n in enumerate(self._pp["names"]):
                opt[n] = jax.tree_util.tree_map(
                    lambda v, _i=i: v[_i] if numpy.ndim(v) else v, blk)
        return {"opt_state": opt, "lr_scale": float(self.lr_scale)}

    def load_state_dict(self, sd) -> None:
        """Called after the forwards restored their Arrays (apply order =
        unit construction order): rebuild the canonical device pytree."""
        import jax
        self.params = {
            f.name: {k: v.device_view() for k, v in
                     f.param_arrays().items()}
            for f in self.forwards if f.PARAMETERIZED}
        self.opt_state = {k: v for k, v in sd["opt_state"].items()}
        # a restored state may not cover every current param (resuming
        # a base snapshot into a lora_rank config): grow it with fresh
        # zero state for the new keys; restored leaves win
        for name, p in self.params.items():
            if name in self.opt_state and name in self._gd_for:
                self.opt_state[name] = self._gd_for[name].extend_state(
                    self.opt_state[name], p)
        if self._pp is not None:
            # restack the per-layer snapshot into the pipeline block;
            # scalar leaves (shared counters) take the first layer's
            import jax.numpy as jnp
            from ..parallel.sharding import PP_BLOCK
            names = self._pp["names"]
            self.params[PP_BLOCK] = {
                k: jnp.stack([self.params[n][k] for n in names])
                for k in self.params[names[0]]}
            self.opt_state[PP_BLOCK] = jax.tree_util.tree_map(
                lambda *ls: (jnp.stack([numpy.asarray(x) for x in ls])
                             if numpy.ndim(ls[0]) else ls[0]),
                *[self.opt_state[n] for n in names])
            for n in names:
                del self.params[n]
                del self.opt_state[n]
        if self._shardings is not None:
            from ..parallel.sharding import (param_shardings,
                                             state_shardings)
            pspec = param_shardings(self.params, self.device.mesh)
            sspec = state_shardings(self.opt_state, self.params, pspec,
                                    self.device.mesh)
            self.params = jax.tree_util.tree_map(
                jax.device_put, self.params, pspec)
            self.opt_state = jax.tree_util.tree_map(
                jax.device_put, self.opt_state, sspec)
        # the step re-takes device ownership (buffers will be donated)
        for f in self.forwards:
            for arr in f.param_arrays().values():
                arr.detach_devmem()
        # restore the schedule scale so the first resumed dispatch trains
        # at the snapshot's learning rate (identical-continuation guarantee)
        if "lr_scale" in sd:
            try:
                self.lr_scale = float(sd["lr_scale"])
            except AttributeError:
                pass  # linked read-only alias; LearningRateAdjust rules
        self._accum.clear()

    def __getstate__(self):
        self.sync_params_to_arrays()
        d = super().__getstate__()
        for k in ("params", "opt_state", "_accum", "_zero_accum",
                  "last_loss", "_pp", "_pp_hetero", "_block_metrics",
                  "_eval_plan_dev"):
            d[k] = ({} if k in ("params", "opt_state", "_accum",
                                "_eval_plan_dev") else None)
        d["param_masks"] = {
            n: {k: numpy.asarray(m) for k, m in ms.items()}
            for n, ms in self.param_masks.items()}
        return d
