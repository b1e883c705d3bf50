"""Whole-epoch fused FC training kernel (Pallas).

The MNIST-784 headline config (784 → hidden tanh → softmax, plain SGD,
reference topology `manualrst_veles_algorithms.rst:31`) is sequential-
SGD-bound, not FLOP-bound: `docs/perf.md` measures the per-step cost at
~36 µs — the TPU `lax.scan` step floor for these shapes, dominated by
per-step weight round trips through HBM and loop overhead, with the MXU
under 1 % busy. This kernel runs an ENTIRE epoch of SGD steps as ONE
Pallas grid with the weights (and momentum state) resident in VMEM
scratch for all K steps: no HBM weight traffic between steps, no
scan-step machinery — the only per-step HBM reads are the minibatch
block (pipelined by Mosaic's double buffering) while forward, backward
and update run back-to-back on the same core-resident parameters.

Scope (checked by ``TrainStep._setup_fused_fc``): a chain of dense
tanh layers ending in a softmax + cross-entropy head, Znicz SGD with
momentum and coupled L2 weight decay, whole minibatches. The TPU-first
point is the *shape* of the solution — the reference could never fuse
its per-unit OpenCL dispatch chain (`veles/znicz/all2all.py` +
`gd.py` kernels) into one residency-preserving program; on TPU one
kernel IS the epoch.

Update rule, exactly the general path's (nn_units.py GradientDescent):
``delta = lr·(g + wd·p) + mu·delta_prev; p -= delta`` — the delta
recurrence (with lr folded in, like the scan path's opt_state) rides
in VMEM and is returned, so resuming or switching engines mid-training
continues the identical trajectory.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

LANE = 128
SUB = 8
NEG = -1e30


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    want = ((size + mult - 1) // mult) * mult
    if want == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, want - size)
    return jnp.pad(x, pads)


def _kernel(refs, *, n_layers: int, mb: int, nout: int, steps: int,
            act_a: float, act_b: float, lr_bias_ratio: float,
            wd: float, wd_bias: float, momentum: float,
            precision=None):
    """One grid step = one SGD minibatch step, all state in VMEM.

    refs layout (built by fused_fc_sgd_epoch):
      [lr, x, y,
       w_0..w_{L-1}, b_0.., vw_0.., vb_0..,          (inputs)
       wo_0.., bo_0.., vwo_0.., vbo_0.., acc,        (outputs)
       ws_0.., bs_0.., vws_0.., vbs_0.., acc_s]      (scratch)
    acc[0, 0] = summed CE loss, acc[0, 1] = error count — over the
    REAL (unpadded) rows of the epoch.
    """
    from jax.experimental import pallas as pl

    L = n_layers
    it = iter(refs)
    lr_ref, x_ref, y_ref = next(it), next(it), next(it)
    w_in = [next(it) for _ in range(L)]
    b_in = [next(it) for _ in range(L)]
    vw_in = [next(it) for _ in range(L)]
    vb_in = [next(it) for _ in range(L)]
    w_out = [next(it) for _ in range(L)]
    b_out = [next(it) for _ in range(L)]
    vw_out = [next(it) for _ in range(L)]
    vb_out = [next(it) for _ in range(L)]
    acc_ref = next(it)
    w_s = [next(it) for _ in range(L)]
    b_s = [next(it) for _ in range(L)]
    vw_s = [next(it) for _ in range(L)]
    vb_s = [next(it) for _ in range(L)]
    acc_s = next(it)

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _load():
        for dst, src in zip(w_s + b_s + vw_s + vb_s,
                            w_in + b_in + vw_in + vb_in):
            dst[:] = src[:]
        acc_s[:] = jnp.zeros_like(acc_s)

    x = x_ref[0]                       # (mb_p, fin_p) f32
    y = y_ref[0]                       # (mb_p, nout_p) one-hot, pad=0
    mb_p = x.shape[0]
    nout_p = y.shape[1]
    lr = lr_ref[0, 0]

    # masks for the zero-padded rows (minibatch → sublane multiple) and
    # class lanes (nout → lane multiple): pad rows must not contribute
    # gradients, pad lanes must not receive softmax mass
    row = jax.lax.broadcasted_iota(jnp.int32, (mb_p, 1), 0)
    rmask = (row < mb).astype(jnp.float32)                 # (mb_p, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (mb_p, nout_p), 1)
    lane_bias = jnp.where(lane < nout, 0.0, NEG)

    def dot(a, bmat):
        return jax.lax.dot_general(
            a, bmat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    # forward: tanh chain, logits head; acts[li] is layer li's INPUT
    # (so acts[li] for li >= 1 is also layer li-1's tanh output — the
    # backward reads both roles from the one list)
    acts = [x]
    h = x
    for li in range(L - 1):
        pre = dot(h, w_s[li][:]) + b_s[li][:1, :]
        # Znicz LeCun-scaled tanh: y = A*tanh(B*a) (all2all.py A, B)
        h = act_a * jnp.tanh(act_b * pre)
        acts.append(h)
    logits = dot(h, w_s[L - 1][:]) + b_s[L - 1][:1, :] + lane_bias

    m = logits.max(axis=1, keepdims=True)
    e = jnp.exp(logits - m)
    s = e.sum(axis=1, keepdims=True)
    p = e / s
    logp = logits - m - jnp.log(s)

    # metrics over real rows (y is all-zero on pad rows already).
    # Error rule MATCHES EvaluatorSoftmax: strict argmax, ties to the
    # LOWEST class index (jnp.argmax).
    loss = -(y * logp).sum()
    is_max = logits >= logits.max(axis=1, keepdims=True)
    big = jnp.int32(nout_p)
    pred = jnp.where(is_max, lane, big).min(axis=1, keepdims=True)
    label_idx = (y * lane.astype(jnp.float32)).sum(
        axis=1, keepdims=True).astype(jnp.int32)
    correct = pred == label_idx
    err = (rmask * (1.0 - correct.astype(jnp.float32))).sum()
    r0 = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 0)
    c0 = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 1)
    acc_s[:] = acc_s[:] + jnp.where(
        (r0 == 0) & (c0 == 0), loss,
        jnp.where((r0 == 0) & (c0 == 1), err, 0.0))

    # backward (mean CE over the real minibatch), then the Znicz SGD
    # delta recurrence, all in-place on the VMEM state
    d_out = (p - y) * rmask / mb                  # d loss / d logits

    def tdot(a, bmat, contract_rows):
        # contract_rows: a^T @ b (rows) vs a @ b^T (cols)
        dims = (((0,), (0,)), ((), ())) if contract_rows \
            else (((1,), (1,)), ((), ()))
        return jax.lax.dot_general(a, bmat, dims,
                                   preferred_element_type=jnp.float32,
                                   precision=precision)

    for li in range(L - 1, -1, -1):
        a_in = acts[li]
        dw = tdot(a_in, d_out, True)              # (in_p, out_p)
        db = d_out.sum(axis=0, keepdims=True)
        if li > 0:
            d_h = tdot(d_out, w_s[li][:], False)  # (mb_p, in_p)
            hh = acts[li]                         # layer li-1's tanh out
            # dh/da of A*tanh(B*a) expressed in h: A*B - (B/A)*h^2
            d_out = d_h * (act_a * act_b - (act_b / act_a) * hh * hh)
        dlt_w = lr * (dw + wd * w_s[li][:]) + momentum * vw_s[li][:]
        dlt_b = (lr * lr_bias_ratio
                 * (jnp.broadcast_to(db, b_s[li].shape)
                    + wd_bias * b_s[li][:])
                 + momentum * vb_s[li][:])
        w_s[li][:] = w_s[li][:] - dlt_w
        b_s[li][:] = b_s[li][:] - dlt_b
        vw_s[li][:] = dlt_w
        vb_s[li][:] = dlt_b

    @pl.when(i == steps - 1)
    def _store():
        for dst, src in zip(w_out + b_out + vw_out + vb_out,
                            w_s + b_s + vw_s + vb_s):
            dst[:] = src[:]
        acc_ref[:] = acc_s[:]


def analytic_cost(layer_shapes: Sequence, mb: int, steps: int):
    """Telemetry fallback cost of ONE fused epoch
    (veles_tpu/telemetry/cost.py): the Pallas custom call is opaque to
    XLA's HLO cost model, so the kernel's owner publishes the analytic
    model. ``layer_shapes``: (n_in, n_out) per dense layer. FLOPs per
    SGD step: forward 2·mb·Σ(in·out), backward 2× forward (dW and dx
    matmuls), plus the delta-recurrence update (~4 per parameter).
    Bytes: the minibatch stream is the only per-step HBM traffic (the
    residency-preserving point of the kernel); weights+momentum cross
    HBM exactly twice per epoch (load, store)."""
    from ..telemetry.cost import Cost
    mm = sum(int(i) * int(o) for i, o in layer_shapes)
    params = mm + sum(int(o) for _, o in layer_shapes)
    flops = steps * (3 * 2 * mb * mm + 4 * params)
    d0 = int(layer_shapes[0][0])
    stream = steps * mb * (d0 + 1) * 4            # f32 batch + labels
    bytes_accessed = stream + 2 * 2 * params * 4  # w+momentum, in+out

    def padded(n, m=LANE):
        return ((n + m - 1) // m) * m
    state = sum(2 * 4 * (padded(i) * padded(o) + SUB * padded(o))
                for i, o in layer_shapes)
    x_bytes = 4 * padded(mb, SUB) * padded(d0)
    return Cost(flops, bytes_accessed, state + 3 * x_bytes,
                source="analytic")


def fused_fc_sgd_epoch(weights: Sequence, biases: Sequence,
                       vel_w: Sequence, vel_b: Sequence,
                       dataset, labels, plan, lr,
                       n_classes: Optional[int] = None,
                       act_a: float = 1.0, act_b: float = 1.0,
                       lr_bias_ratio: float = 1.0,
                       wd: float = 0.0, wd_bias: float = 0.0,
                       momentum: float = 0.0,
                       interpret: bool = False,
                       precision: Optional[str] = None):
    """One SGD epoch of an L-layer tanh chain + softmax-CE head as a
    single Pallas program with VMEM-resident weights AND momentum
    state.

    - weights[i] (d_i, d_{i+1}), biases[i] (d_{i+1},) — f32
    - vel_w/vel_b: the delta recurrence state (same shapes; the scan
      path's SGD opt_state). Pass zeros for a fresh run.
    - dataset (N, d_0) f32, labels (N,) int32
    - plan (K, mb) int32: the epoch's shuffled minibatch indices (same
      contract as TrainStep's plan serving)
    - lr: scalar learning rate for weights (traced OK — per-epoch
      schedules); the bias lr is ``lr * lr_bias_ratio`` (static
      ratio, so schedules scale both together like the scan path)
    - precision: dot precision for every matmul in the kernel. None
      (default) = the backend default — single-pass bf16 multiplies on
      the MXU, matching the scan path's own default-precision dots.
      'highest' = exact f32 multiplies; used by the chip parity gate to
      compare the kernel against an equally-exact oracle so algorithm
      bugs aren't hidden under (or mistaken for) bf16 rounding
      (measured on TPU v5 lite: default-vs-f32 drift is ~1.2e-3 after
      one step, ~2.6e-3 after a 12-step momentum epoch —
      docs/fused_fc_precision_probe.json)

    Returns ``(weights', biases', vel_w', vel_b', loss_sum,
    err_count)``.

    Note: the epoch-sized gather+pad below costs ~2× the minibatch-
    stream HBM traffic (~224 MB ≈ 0.6 ms/epoch at HBM speed for the
    MNIST headline vs a ~20 ms epoch) — the contiguous input stream it
    buys Mosaic's pipeline is worth far more than a scalar-prefetch
    redesign.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L = len(weights)
    assert len(biases) == len(vel_w) == len(vel_b) == L and L >= 1
    k_steps, mb = plan.shape
    nout = weights[-1].shape[1] if n_classes is None else int(n_classes)

    f32 = jnp.float32
    xg = dataset.astype(f32)[plan]                  # (K, mb, d0)
    yg = jax.nn.one_hot(labels[plan], nout, dtype=f32)
    xg = _pad_to(_pad_to(xg, 1, SUB), 2, LANE)      # (K, mb_p, d0_p)
    yg = _pad_to(_pad_to(yg, 1, SUB), 2, LANE)
    mb_p, fin_p = xg.shape[1], xg.shape[2]
    nout_p = yg.shape[2]

    wp = [_pad_to(_pad_to(w.astype(f32), 0, LANE), 1, LANE)
          for w in weights]
    vwp = [_pad_to(_pad_to(v.astype(f32), 0, LANE), 1, LANE)
           for v in vel_w]
    bp, vbp = [], []
    for b, v in zip(biases, vel_b):
        row = _pad_to(b.astype(f32)[None, :], 1, LANE)
        bp.append(jnp.broadcast_to(row, (SUB, row.shape[1])))
        vrow = _pad_to(v.astype(f32)[None, :], 1, LANE)
        vbp.append(jnp.broadcast_to(vrow, (SUB, vrow.shape[1])))
    lr2 = jnp.full((1, 1), lr, f32)

    def kernel(*refs):
        _kernel(refs, n_layers=L, mb=mb, nout=nout, steps=k_steps,
                act_a=float(act_a), act_b=float(act_b),
                lr_bias_ratio=float(lr_bias_ratio), wd=float(wd),
                wd_bias=float(wd_bias), momentum=float(momentum),
                precision=precision)

    vm = pltpu.VMEM

    def fix(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape),
                            memory_space=vm)

    mat_specs = [fix(w.shape) for w in wp]
    bias_specs = [fix(b.shape) for b in bp]
    in_specs = ([pl.BlockSpec((1, 1), lambda i: (0, 0),
                              memory_space=pltpu.SMEM),
                 pl.BlockSpec((1, mb_p, fin_p), lambda i: (i, 0, 0),
                              memory_space=vm),
                 pl.BlockSpec((1, mb_p, nout_p), lambda i: (i, 0, 0),
                              memory_space=vm)]
                + mat_specs + bias_specs + mat_specs + bias_specs)
    out_specs = (mat_specs + bias_specs + mat_specs + bias_specs
                 + [fix((SUB, LANE))])
    out_shape = ([jax.ShapeDtypeStruct(w.shape, f32) for w in wp]
                 + [jax.ShapeDtypeStruct(b.shape, f32) for b in bp]
                 + [jax.ShapeDtypeStruct(w.shape, f32) for w in wp]
                 + [jax.ShapeDtypeStruct(b.shape, f32) for b in bp]
                 + [jax.ShapeDtypeStruct((SUB, LANE), f32)])
    scratch = ([pltpu.VMEM(w.shape, f32) for w in wp]
               + [pltpu.VMEM(b.shape, f32) for b in bp]
               + [pltpu.VMEM(w.shape, f32) for w in wp]
               + [pltpu.VMEM(b.shape, f32) for b in bp]
               + [pltpu.VMEM((SUB, LANE), f32)])
    outs = pl.pallas_call(
        kernel,
        grid=(k_steps,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        # one sequential dimension: every step reads+writes the same
        # VMEM-resident weights
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="veles_fused_fc",
    )(lr2, xg, yg, *wp, *bp, *vwp, *vbp)

    w_o = outs[:L]
    b_o = outs[L:2 * L]
    vw_o = outs[2 * L:3 * L]
    vb_o = outs[3 * L:4 * L]
    acc = outs[4 * L]
    dims = [w.shape for w in weights]
    w_f = [w_o[i][:dims[i][0], :dims[i][1]] for i in range(L)]
    b_f = [b_o[i][0, :dims[i][1]] for i in range(L)]
    vw_f = [vw_o[i][:dims[i][0], :dims[i][1]] for i in range(L)]
    vb_f = [vb_o[i][0, :dims[i][1]] for i in range(L)]
    return w_f, b_f, vw_f, vb_f, acc[0, 0], acc[0, 1]


# -- fused scale-bias-activation epilogues -----------------------------------
#
# The Znicz layer vocabulary allows standalone elementwise units after a
# matmul-bearing forward (``activation_tanh``/``activation_str``/
# ``activation_mul`` … — the cifar sample's topology). Inside the fused
# train step XLA fuses them for free, but on the standalone forward
# path (inference graphs, ``extract_forward_workflow``) every unit is
# its OWN jitted program: a [conv, activation] pair costs two device
# dispatches per minibatch where one consumer-fused program suffices.
# The epilogue plan folds each run of eligible elementwise tail units
# into the preceding matmul producer's program — the tail units then
# skip their dispatch entirely (removed, not renamed: the dispatch
# counter lock in tests/test_devtime.py). Opt-in via
# ``root.common.engine.fused_epilogue``; OFF is bit-identical to a
# build without the feature, ON applies the same ops in the same order
# inside one program. Composes with TensorMonitor taps: the taps read
# the post-epilogue head output, so monitoring never forces the
# unfused path (test-locked).


def epilogue_eligible(unit) -> bool:
    """True for forward units whose whole work is an rng-free,
    shape-preserving elementwise map — the scale (``activation_mul``)
    / activation vocabulary. Only these may fold into the producing
    matmul's program without changing semantics."""
    from ..nn.activation import ActivationForward
    return isinstance(unit, ActivationForward)


def plan_epilogues(forwards):
    """``[(producer, [tail units…]), …]`` — each maximal run of
    eligible elementwise units directly following a parameterized
    (matmul-bearing) forward, in chain order. Pure planning: no unit
    state is touched (the train step consumes the plan per trace;
    :func:`install_epilogues` materializes it for standalone runs)."""
    plan = []
    producer = None
    for f in forwards:
        if producer is not None and epilogue_eligible(f):
            if not plan or plan[-1][0] is not producer:
                plan.append((producer, []))
            plan[-1][1].append(f)
            continue
        producer = f if getattr(f, "PARAMETERIZED", False) else None
    return plan


def apply_epilogue(y, tails, train: bool = False):
    """Fold the elementwise tail into the matmul consumer: apply each
    planned tail unit's pure map to ``y`` inside the SAME traced
    program, in chain order — exactly the ops the unfused path runs,
    so on/off is bit-identical while the tail units' separate
    dispatches disappear."""
    for t in tails:
        y = t.apply({}, y, train=train, rng=None)
    return y


def install_epilogues(forwards, force: bool = False):
    """Materialize the epilogue plan on a standalone forward chain:
    producers get ``_epilogue_tails`` (their ``xla_run`` dispatches
    ONE program computing matmul + every tail, assigning EVERY
    stage's output array), tails get ``_epilogue_folded`` (their
    ``xla_run`` becomes a no-op — the removed dispatches). Gated on
    ``root.common.engine.fused_epilogue`` unless ``force``; returns
    the installed plan ``{producer name: [tail names]}`` (empty =
    nothing folded). Idempotent AND reversible: any previous plan on
    these units clears first — including each producer's cached
    ``apply_epilogue`` jitted closure, which would otherwise keep
    serving a stale tails list — so re-calling with the knob off
    restores the exact unfused dispatch layout. The numpy oracle path
    is untouched — tails still run there, keeping the oracle
    equivalence checks unfused."""
    from ..config import root
    for f in forwards:
        if getattr(f, "_epilogue_tails", None) is not None \
                or getattr(f, "_epilogue_folded", False):
            f._epilogue_tails = None
            f._epilogue_folded = False
            f._jit_cache.pop("apply_epilogue", None)
            f._jit_fns.pop("apply_epilogue", None)
    if not force and not root.common.engine.get("fused_epilogue",
                                                False):
        return {}
    installed = {}
    for producer, tails in plan_epilogues(forwards):
        producer._epilogue_tails = list(tails)
        for t in tails:
            t._epilogue_folded = True
        installed[producer.name] = [t.name for t in tails]
    return installed


def fused_fc_oracle(weights, biases, vel_w, vel_b, dataset, labels,
                    plan, lr, n_classes: Optional[int] = None,
                    act_a: float = 1.0, act_b: float = 1.0,
                    lr_bias_ratio: float = 1.0, wd: float = 0.0,
                    wd_bias: float = 0.0, momentum: float = 0.0):
    """jnp reference (lax.scan of per-step SGD) — the equivalence
    oracle for the kernel; same plan, same math, per-step HBM
    weights."""
    L = len(weights)
    nout = weights[-1].shape[1] if n_classes is None else int(n_classes)
    mb = plan.shape[1]
    lr_bias = lr * lr_bias_ratio
    f32 = jnp.float32

    def step(carry, idx):
        ws, bs, vws, vbs, loss, err = carry
        x = dataset.astype(f32)[idx]
        y = jax.nn.one_hot(labels[idx], nout, dtype=f32)
        acts = [x]
        h = x
        for li in range(L - 1):
            h = act_a * jnp.tanh(act_b * (h @ ws[li] + bs[li]))
            acts.append(h)
        logits = h @ ws[L - 1] + bs[L - 1]
        logp = jax.nn.log_softmax(logits)
        p = jnp.exp(logp)
        loss = loss - (y * logp).sum()
        err = err + (jnp.argmax(logits, 1) != labels[idx]).sum()
        d_out = (p - y) / mb
        n_ws, n_bs, n_vws, n_vbs = list(ws), list(bs), list(vws), \
            list(vbs)
        for li in range(L - 1, -1, -1):
            dw = acts[li].T @ d_out
            db = d_out.sum(0)
            if li > 0:
                d_h = d_out @ ws[li].T
                hh = acts[li]
                d_out = d_h * (act_a * act_b
                               - (act_b / act_a) * hh * hh)
            dlt_w = lr * (dw + wd * ws[li]) + momentum * vws[li]
            dlt_b = lr_bias * (db + wd_bias * bs[li]) \
                + momentum * vbs[li]
            n_ws[li] = ws[li] - dlt_w
            n_bs[li] = bs[li] - dlt_b
            n_vws[li] = dlt_w
            n_vbs[li] = dlt_b
        return (tuple(n_ws), tuple(n_bs), tuple(n_vws), tuple(n_vbs),
                loss, err), None

    init = (tuple(w.astype(f32) for w in weights),
            tuple(b.astype(f32) for b in biases),
            tuple(v.astype(f32) for v in vel_w),
            tuple(v.astype(f32) for v in vel_b),
            jnp.float32(0.0), jnp.int32(0))
    (ws, bs, vws, vbs, loss, err), _ = jax.lax.scan(step, init, plan)
    return (list(ws), list(bs), list(vws), list(vbs), loss,
            err.astype(f32))
