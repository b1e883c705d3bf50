"""Pipeline parallelism: GPipe-style microbatched stage pipeline.

New capability vs the reference (SURVEY.md §2.4 row "Model-parallel /
pipeline — absent; new capability"). The standard TPU formulation (the
scaling-book recipe): each device on the ``pipeline`` mesh axis holds one
stage's parameters; microbatches ripple through, activations hopping
stage-to-stage with ``ppermute`` inside ``shard_map``; the schedule runs
``M + n_stages - 1`` ticks (fill + drain). Differentiable end to end —
``jax.grad`` through the scan/ppermute yields the reverse schedule
automatically, so the fused train step can wrap a pipelined forward like
any other pure function.

Two schedules live here. :func:`gpipe` handles the uniform-stage case
(every stage maps an activation of shape S to shape S — e.g. a stack of
residual blocks), the memory-scaling formulation: stacked stage params
are *sharded* over the axis. :func:`gpipe_hetero` handles
shape-changing chains (conv → pool → dense) with per-stage
``lax.switch`` and a padded flat wire — compute overlap without the
memory scaling (params replicated; see its docstring for the trade).
``plan_pipeline`` / ``plan_pipeline_hetero`` stage-group a workflow's
forward chain so ``{"pipeline": N}`` is a StandardWorkflow/TrainStep
capability, not a standalone demo.
"""

from __future__ import annotations

from typing import Any, Callable, List, Tuple


def _ring_schedule(step_of, x_all, m, n, axis, wire0, out_of_wire,
                   out_shape):
    """The one copy of the GPipe tick loop both schedules share.

    ``m + n - 1`` ticks (fill + drain). Each tick: stage 0 injects
    microbatch t (garbage after the fill phase — those lanes never
    reach a collected slot), every device applies its stage via
    ``step_of(idx, buf, inject) -> y`` (wire-shaped), the LAST stage
    decodes and collects microbatch ``t - (n-1)`` via
    ``out_of_wire(y)``, and the wire hops the ``ppermute`` ring.
    Returns (m, *out_shape) outputs — only the last stage holds real
    values; the closing psum replicates them (other stages contribute
    zeros)."""
    import jax
    import jax.numpy as jnp

    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        buf, outputs = carry
        inject = x_all[jnp.clip(t, 0, m - 1)]
        y = step_of(idx, buf, inject)
        out_slot = t - (n - 1)
        collect = jnp.logical_and(idx == n - 1, out_slot >= 0)
        outputs = jax.lax.cond(
            collect,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, out_of_wire(y), jnp.maximum(out_slot, 0), 0),
            lambda o: o, outputs)
        buf = jax.lax.ppermute(y, axis, perm)
        return (buf, outputs), None

    outputs0 = jnp.zeros((m,) + out_shape, x_all.dtype)
    (_, outputs), _ = jax.lax.scan(tick, (wire0, outputs0),
                                   jnp.arange(m + n - 1))
    outputs = jnp.where(idx == n - 1, outputs, 0.0)
    return jax.lax.psum(outputs, axis)


def gpipe(fn: Callable[[Any, Any], Any], stage_params: Any, xs: Any,
          mesh, axis: str = "pipeline", batch_spec=None):
    """Run ``y_m = fn_{n-1}(…fn_0(x_m))`` for M microbatches.

    - ``fn(params_slice, x)`` — one stage; same activation shape in/out.
    - ``stage_params`` — pytree whose leaves have a leading ``n_stages``
      axis (sharded over ``axis``; each device sees its slice with the
      leading axis of size 1).
    - ``xs`` — (M, mb, …) microbatches; ``batch_spec`` is their
      PartitionSpec (e.g. ``P(None, "data")`` when the minibatch axis is
      data-sharded in the surrounding SPMD program; default replicated).

    Returns (M, mb, …) outputs, sharded like ``batch_spec``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if batch_spec is None:
        batch_spec = P()
    n = mesh.shape[axis]
    m = xs.shape[0]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.ndim == 0 or leaf.shape[0] != n:
            raise ValueError(
                "stage_params leaves need a leading axis of exactly %d "
                "pipeline stages, got shape %s (a multiple would shard "
                "silently and drop stages)" % (n, leaf.shape))

    def local(params, x_all):
        # params leaves: (1, …) — this stage's slice; the wire carries
        # the (unpadded) activation itself: every hop has the same shape
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)

        def step_of(idx, buf, inject):
            return fn(my_params, jnp.where(idx == 0, inject, buf))

        return _ring_schedule(step_of, x_all, m, n, axis,
                              jnp.zeros_like(x_all[0]), lambda y: y,
                              x_all.shape[1:])

    params_spec = jax.tree_util.tree_map(
        lambda _: P(axis), stage_params)
    fn_sharded = jax.shard_map(
        local, mesh=mesh, in_specs=(params_spec, batch_spec),
        out_specs=batch_spec, check_vma=False)
    return fn_sharded(stage_params, xs)


def gpipe_hetero(stage_fns: List[Callable[[Any, Any], Any]],
                 stage_params: List[Any], xs: Any, mesh,
                 axis: str = "pipeline", batch_spec=None):
    """GPipe schedule over *heterogeneous* stages (shape-changing chain).

    Where :func:`gpipe` demands identical shape-preserving stages (and
    in return shards the stacked parameters over the axis — the
    memory-scaling formulation), this variant accepts one arbitrary
    ``fn_i(params_i, x) -> y`` per stage: each device selects its own
    stage with ``lax.switch`` on ``axis_index``, and the inter-stage
    activations — whose shapes differ per hop — ride the ``ppermute``
    ring as a flat buffer padded to the widest hop. That makes
    AlexNet/ImagenetAE-shaped chains (conv → pool → … → dense)
    pipelineable, which the uniform planner refuses.

    The trade, stated plainly: ``stage_params`` is a *list of per-stage
    pytrees replicated on every device* (SPMD cannot scatter
    differently-shaped arrays along one mesh axis), so heterogeneous
    pipelining buys compute overlap, not parameter-memory scaling. For
    the conv-era nets this targets, parameters are tiny next to
    activations, which is why the trade is acceptable. The backward
    ride comes free: ``lax.switch`` transposes to the executed branch
    only, so each device contributes exactly its stage's parameter
    cotangents, and shard_map's replicated-input transpose psums them.

    - ``xs`` — (M, mb, *in_shape) microbatches; ``batch_spec`` as in
      :func:`gpipe` (dim 1 may be data-sharded).
    - every stage must preserve dtype (checked at trace time); AMP
      casts happen outside.
    Returns (M, mb, *out_shape) outputs from the final stage.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if batch_spec is None:
        batch_spec = P()
    n = mesh.shape[axis]
    if len(stage_fns) != n or len(stage_params) != n:
        raise ValueError("need exactly %d stage fns/params, got %d/%d"
                         % (n, len(stage_fns), len(stage_params)))
    m = xs.shape[0]

    def local(all_params, x_all):
        # trace the shape chain on the LOCAL microbatch shape (dim 1 may
        # be data-sharded, so shapes must be derived inside shard_map)
        shapes = [x_all.shape[1:]]
        for fn, p in zip(stage_fns, all_params):
            out = jax.eval_shape(
                fn, p, jax.ShapeDtypeStruct(shapes[-1], x_all.dtype))
            if out.dtype != x_all.dtype:
                raise ValueError(
                    "pipeline stages must preserve dtype: stage yields "
                    "%s from %s input" % (out.dtype, x_all.dtype))
            shapes.append(out.shape)
        sizes = [int(np.prod(s)) for s in shapes]
        wire = max(sizes)

        def make_branch(i):
            def branch(buf, inject):
                x = (inject if i == 0
                     else buf[:sizes[i]].reshape(shapes[i]))
                y = stage_fns[i](all_params[i], x)
                y = y.reshape(-1)
                return jnp.pad(y, (0, wire - y.size))
            return branch

        branches = [make_branch(i) for i in range(n)]

        def step_of(idx, buf, inject):
            return jax.lax.switch(idx, branches, buf, inject)

        return _ring_schedule(
            step_of, x_all, m, n, axis,
            jnp.zeros((wire,), x_all.dtype),
            lambda y: y[:sizes[n]].reshape(shapes[n]), shapes[n])

    params_spec = jax.tree_util.tree_map(lambda _: P(), stage_params)
    fn_sharded = jax.shard_map(
        local, mesh=mesh, in_specs=(params_spec, batch_spec),
        out_specs=batch_spec, check_vma=False)
    return fn_sharded(stage_params, xs)


def stage_cost(f) -> float:
    """Rough per-sample FLOP proxy for stage balancing: 2 × weight
    elements × output spatial positions for conv-likes (input positions
    for deconv), 2 × weight elements for dense, output size for
    unparameterized plumbing (pool/activation — bandwidth, not FLOPs,
    but enough to keep them from looking free)."""
    import numpy as np
    kind = type(f).__name__
    w = None
    if getattr(f, "PARAMETERIZED", False):
        w = f.param_arrays().get("weights")
    out_size = (int(np.prod(f.output.shape[1:]))
                if getattr(f, "output", None) else 1)
    if w is None:
        return float(out_size)
    if "Deconv" in kind and getattr(f, "input", None):
        _, ih, iw = f.input.shape[:3]
        return 2.0 * ih * iw * w.mem.size
    if "Conv" in kind and getattr(f, "output", None):
        _, oh, ow = f.output.shape[:3]
        return 2.0 * oh * ow * w.mem.size
    return 2.0 * float(w.mem.size)


def plan_pipeline_hetero(forwards: List[Any], n_stages: int
                         ) -> List[List[Any]]:
    """Split a heterogeneous forward chain into ``n_stages`` contiguous
    groups minimizing the max per-stage cost (classic linear-partition
    DP over :func:`stage_cost`) — the balance decides the pipeline's
    steady-state tick time. Every stage gets >= 1 unit; raises when the
    chain is shorter than the axis."""
    if len(forwards) < n_stages:
        raise ValueError(
            "pipeline axis of size %d needs >= %d forward units to "
            "stage; chain has %d. Drop the 'pipeline' mesh axis or "
            "shrink it." % (n_stages, n_stages, len(forwards)))
    costs = [stage_cost(f) for f in forwards]
    k = len(costs)
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def span(i, j):           # cost of units [i, j)
        return prefix[j] - prefix[i]

    INF = float("inf")
    # best[s][j] = minimal max-stage-cost splitting first j units into s
    best = [[INF] * (k + 1) for _ in range(n_stages + 1)]
    cut = [[0] * (k + 1) for _ in range(n_stages + 1)]
    best[0][0] = 0.0
    for s in range(1, n_stages + 1):
        for j in range(s, k + 1):
            for i in range(s - 1, j):
                v = max(best[s - 1][i], span(i, j))
                if v < best[s][j]:
                    best[s][j] = v
                    cut[s][j] = i
    bounds = [k]
    for s in range(n_stages, 0, -1):
        bounds.append(cut[s][bounds[-1]])
    bounds.reverse()
    return [list(forwards[bounds[s]:bounds[s + 1]])
            for s in range(n_stages)]


def plan_pipeline(forwards: List[Any], n_stages: int
                  ) -> Tuple[List[Any], List[Any], List[Any]]:
    """Stage-group a forward chain for ``{"pipeline": N}``.

    Returns ``(pre, block, post)``: the longest contiguous run of
    *identical, shape-preserving, parameterized* forwards (same class,
    same parameter signature, same GD hyper-parameters, activation shape
    in == out), trimmed to a multiple of ``n_stages``; everything before/
    after runs replicated outside the pipelined region. Raises ValueError
    when no viable run exists — pipelining a heterogeneous chain would
    silently serialize, which is worse than failing loudly.
    """
    def signature(f):
        if not getattr(f, "PARAMETERIZED", False):
            return None
        if f.input is None or not f.input or not f.output:
            return None
        if tuple(f.input.shape) != tuple(f.output.shape):
            return None  # stages must be shape-preserving
        params = tuple(sorted(
            (k, tuple(v.shape), str(v.dtype))
            for k, v in f.param_arrays().items()))
        gd = tuple(sorted(getattr(f, "gd_config", {}).items()))
        # semantic config must match too: the grouped block runs every
        # layer through block[0].apply, so e.g. rope=True/False or
        # causal differences would silently apply block 0's setting to
        # all stages. The export key list IS the inference-defining
        # config inventory — reuse it.
        from ..export.package import _EXPORT_KEYS
        cfg = tuple((k, repr(getattr(f, k))) for k in _EXPORT_KEYS
                    if hasattr(f, k))
        return (type(f).__name__, params, gd, cfg)

    sigs = [signature(f) for f in forwards]
    best = (0, 0)  # (length, start)
    i = 0
    while i < len(sigs):
        if sigs[i] is None:
            i += 1
            continue
        j = i
        while j < len(sigs) and sigs[j] == sigs[i]:
            j += 1
        if j - i > best[0]:
            best = (j - i, i)
        i = j
    length, start = best
    usable = (length // n_stages) * n_stages
    if usable < n_stages or usable == 0:
        raise ValueError(
            "pipeline axis of size %d needs >= %d contiguous identical "
            "shape-preserving parameterized layers; longest run is %d. "
            "Stack repeated blocks (e.g. N x all2all_tanh of equal width) "
            "or drop the 'pipeline' mesh axis." % (n_stages, n_stages,
                                                   length))
    block = list(forwards[start:start + usable])
    pre = list(forwards[:start])
    post = list(forwards[start + usable:])
    return pre, block, post


def microbatch(x, n_micro: int):
    """(B, …) → (M, B/M, …); B must divide."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (b, n_micro))
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def unmicrobatch(y):
    return y.reshape((-1,) + y.shape[2:])
