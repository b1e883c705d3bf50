"""Sparse experts with a shared one: the feed-forward of a hybrid block.

The router scores every token over ALL the experts of the layer (softmax
in float32), keeps the ``top_k`` largest and divides their weights by
their sum; or (``route_sigmoid``) scores them by a sigmoid, chooses by the
score plus a learned correction an expert and weighs by the score alone.
The combine weights may be scaled (``routed_scale`` s: DeepSeek-V3's
``routed_scaling_factor``); the shared expert may be left out, and its
gate too (then it is added whole). The layer is told which experts it
holds (``held``: their ids; all of them by default) and computes

    y = sum over the chosen experts held here of
            s w_e down_e(silu(gate_e x) * up_e x)
        + sigmoid(x . s_mix) * shared(x)      (or shared(x), ungated)

What the chosen experts that are NOT held would add is left out: this is
one chip's part of the layer's result, and the parts of all the chips
(the shared expert counted once) add up to the whole (the share test in
``tests/test_hybrid.py``). No token is dropped whatever the load: there
is no capacity factor.

The held assignments are sorted by expert and cut into blocks of
``block`` rows of one expert each (an expert's last block is padded
with rows of weight nought; in training ``block_rows_for`` sizes a block
to an expert's expected rows); one loop runs over the blocks that hold
anything, so the work follows the number of held assignments and no
(tokens, experts, capacity) tensor exists. The loop gathers a block's
token rows, multiplies them through that expert's three matrices and adds
the weighted result to the tokens' rows; the backward pass
(``jax.custom_vjp``) is the same loop over the same blocks with the
expert's hidden activations recomputed.
"""

from __future__ import annotations

import functools
import math

#: rows of one expert that one step of the loop multiplies, at the least
BLOCK_ROWS = 256
#: bucket bounds (tokens) of the histogram of the fullest held expert's
#: load a layer a step (telemetry/counters.py HISTOGRAMS)
PEAK_LOAD = "veles_moe_peak_load_tokens"
ASSIGNED = "veles_moe_assignments_total"
HELD = "veles_moe_assignments_held_total"


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def route(x, router, top_k):
    """(N, D) tokens -> ((N, k) expert ids, (N, k) float32 weights that
    sum to one a token). The scores are float32 whatever ``x`` is."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def route_sigmoid(x, router, bias, top_k):
    """As :func:`route` with sigmoid scores: the ``top_k`` largest of
    ``sigmoid(x . router) + bias`` are chosen (``bias`` (E,): the
    selection's correction, which no weight carries) and weigh by their
    sigmoid alone over the sum of the chosen."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def plan_blocks(idx, local_of, n_held, block):
    """The held assignments sorted by expert and cut into blocks.
    ``idx`` (N, k) expert ids; ``local_of`` (E,) the place of an expert
    among the held ones or -1. Returns a dict of int32 arrays: ``order``
    (A,) assignment numbers, the held ones first and grouped by expert;
    ``counts`` (n_held,); per block its ``expert``, the ``first`` row it
    takes of that expert's group and where the group ``starts`` in
    ``order``; and ``n_blocks``, how many blocks hold anything."""
    import jax.numpy as jnp
    local = jnp.take(local_of, idx.reshape(-1))
    key = jnp.where(local >= 0, local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    blocks = (counts + block - 1) // block
    ends = jnp.cumsum(blocks)
    starts = jnp.cumsum(counts) - counts
    max_blocks = key.shape[0] // block + n_held
    b = jnp.arange(max_blocks, dtype=jnp.int32)
    expert = jnp.minimum(jnp.sum(b[:, None] >= ends[None, :], axis=1),
                         n_held - 1).astype(jnp.int32)
    first = (b - (ends - blocks)[expert]) * block
    return {"order": order, "counts": counts, "expert": expert,
            "first": first.astype(jnp.int32),
            "starts": starts.astype(jnp.int32)[expert],
            "n_blocks": ends[-1].astype(jnp.int32)}


def block_rows_for(tokens, top_k, n_experts):
    """Rows of a training block: the power of two nearest the rows that an
    expert of the router's ``n_experts`` expects a step (``tokens x top_k
    / n_experts``), at least ``BLOCK_ROWS``. A block reads its expert's
    three matrices and writes their gradients' slices whatever its rows,
    so an expert's expected rows take about one block."""
    expected = max(tokens * top_k / n_experts, 1.0)
    return max(BLOCK_ROWS, 1 << int(round(math.log2(expected))))


def _block_rows(plan, b, block, top_k, weights):
    """Block ``b``: its expert, its rows' tokens, assignments and combine
    weights (nought on the padding rows)."""
    import jax.numpy as jnp
    e = plan["expert"][b]
    pos = plan["first"][b] + jnp.arange(block, dtype=jnp.int32)
    valid = pos < plan["counts"][e]
    src = jnp.minimum(plan["starts"][b] + pos, plan["order"].shape[0] - 1)
    assign = plan["order"][src]
    w = jnp.where(valid, weights[assign], 0.0)
    return e, assign // top_k, assign, w, valid


def _expert_hidden(xb, wg, wu, dt):
    import jax.numpy as jnp
    f32 = jnp.float32
    a = jnp.dot(xb, wg.astype(dt), preferred_element_type=f32)
    u = jnp.dot(xb, wu.astype(dt), preferred_element_type=f32)
    return a, u


def grouped_experts(x, weights, e_gate, e_up, e_down, plan, block, top_k):
    """(N, D) tokens and (A,) combine weights -> (N, D) float32: the sum
    over each token's held assignments of weight x expert(token)."""
    return _grouped()(x, weights, e_gate, e_up, e_down, plan, block, top_k)


@functools.lru_cache(maxsize=None)
def _grouped():
    """The loop with its own backward pass; built at the first call, as
    this package imports jax nowhere at its top."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
    def grouped(x, weights, e_gate, e_up, e_down, plan, block, top_k):
        return _grouped_fwd(x, weights, e_gate, e_up, e_down, plan, block,
                            top_k)[0]
    grouped.defvjp(_grouped_fwd, _grouped_bwd)
    return grouped


def _grouped_fwd(x, weights, e_gate, e_up, e_down, plan, block, top_k):
    import jax
    import jax.numpy as jnp
    f32, dt = jnp.float32, x.dtype

    def body(b, out):
        e, tok, _, w, _ = _block_rows(plan, b, block, top_k, weights)
        xb = jnp.take(x, tok, axis=0)
        a, u = _expert_hidden(xb, e_gate[e], e_up[e], dt)
        h = (_silu(a) * u).astype(dt)
        y = jnp.dot(h, e_down[e].astype(dt), preferred_element_type=f32)
        return out.at[tok].add(y * w[:, None])

    out = jax.lax.fori_loop(0, plan["n_blocks"], body,
                            jnp.zeros(x.shape, f32))
    return out, (x, weights, e_gate, e_up, e_down, plan)


def _grouped_bwd(block, top_k, res, dout):
    import jax
    import jax.numpy as jnp
    from jax import named_scope
    x, weights, e_gate, e_up, e_down, plan = res
    f32, dt = jnp.float32, x.dtype
    dout = dout.astype(f32)

    def body(b, carry):
        dx, dw, dg, du, dd = carry
        e, tok, assign, w, valid = _block_rows(plan, b, block, top_k,
                                               weights)
        xb = jnp.take(x, tok, axis=0)
        dob = jnp.take(dout, tok, axis=0)
        wd = e_down[e].astype(dt)
        a, u = _expert_hidden(xb, e_gate[e], e_up[e], dt)
        sig = jax.nn.sigmoid(a)
        sa = a * sig
        h = (sa * u).astype(dt)
        y = jnp.dot(h, wd, preferred_element_type=f32)
        dw = dw.at[assign].add(jnp.where(valid, jnp.sum(y * dob, axis=-1),
                                         0.0))
        dy = (dob * w[:, None]).astype(dt)
        dd = dd.at[e].add(jnp.dot(h.T, dy, preferred_element_type=f32))
        dh = jnp.dot(dy, wd.T, preferred_element_type=f32)
        da = (dh * u * (sig + sa * (1.0 - sig))).astype(dt)
        db = (dh * sa).astype(dt)
        dg = dg.at[e].add(jnp.dot(xb.T, da, preferred_element_type=f32))
        du = du.at[e].add(jnp.dot(xb.T, db, preferred_element_type=f32))
        dxb = (jnp.dot(da, e_gate[e].astype(dt).T,
                       preferred_element_type=f32)
               + jnp.dot(db, e_up[e].astype(dt).T,
                         preferred_element_type=f32))
        return dx.at[tok].add(dxb), dw, dg, du, dd

    # the backward pass's operations keep the forward's scope name
    with named_scope("experts"):
        dx, dw, dg, du, dd = jax.lax.fori_loop(
            0, plan["n_blocks"], body,
            (jnp.zeros(x.shape, f32), jnp.zeros(weights.shape, f32),
             jnp.zeros(e_gate.shape, f32), jnp.zeros(e_up.shape, f32),
             jnp.zeros(e_down.shape, f32)))
    return (dx.astype(x.dtype), dw.astype(weights.dtype),
            dg.astype(e_gate.dtype), du.astype(e_up.dtype),
            dd.astype(e_down.dtype), None)


def shared_expert(p, x, precision, gated=True):
    import jax
    import jax.numpy as jnp
    if gated:
        # one number a token decides how much of the shared expert it
        # gets: summed and squashed in float32
        mix = jax.nn.sigmoid(jnp.dot(
            x, p["s_mix"], precision=precision,
            preferred_element_type=jnp.float32))[..., None]
    h = (_silu(jnp.dot(x, p["s_gate"], precision=precision))
         * jnp.dot(x, p["s_up"], precision=precision))
    y = jnp.dot(h, p["s_down"], precision=precision).astype(jnp.float32)
    return mix * y if gated else y


TOUCHED = "veles_moe_experts_touched_total"


def tap_keys():
    """The accumulator keys of ``load_taps``: two counters, and the
    histogram's sum and one key a bucket (the last is +Inf)."""
    from ..telemetry import steptaps
    from ..telemetry.counters import histogram_buckets
    buckets = len(histogram_buckets(PEAK_LOAD)) + 1
    return ([steptaps.counter_key(ASSIGNED), steptaps.counter_key(HELD),
             steptaps.histogram_key(PEAK_LOAD, "sum")]
            + [steptaps.histogram_key(PEAK_LOAD, i) for i in range(buckets)])


def load_taps(counts, n_assigned, touched=False):
    """What the layer counts of one step: assignments made, assignments
    held, and the fullest held expert's load in its histogram bucket;
    with ``touched`` (a served step, whose collector takes any key) also
    the held experts that got a row."""
    import jax.numpy as jnp
    from ..telemetry import steptaps
    from ..telemetry.counters import histogram_buckets
    f32 = jnp.float32
    peak = jnp.max(counts).astype(f32)
    # bisect_left over the bounds, as HistogramRegistry.observe
    bucket = jnp.sum(peak > jnp.asarray(histogram_buckets(PEAK_LOAD), f32))
    assigned, held, total, *in_bucket = tap_keys()
    steptaps.emit(assigned, jnp.asarray(n_assigned, f32))
    steptaps.emit(held, jnp.sum(counts).astype(f32))
    steptaps.emit(total, peak)
    for i, key in enumerate(in_bucket):
        steptaps.emit(key, (bucket == i).astype(f32))
    if touched:
        steptaps.emit(steptaps.counter_key(TOUCHED),
                      jnp.sum(counts > 0).astype(f32))


def sparse_experts(p, x, *, top_k, local_of, n_held, precision, scope,
                   block=BLOCK_ROWS, router="softmax", shared=True,
                   live=None, routed_scale=1.0, shared_gate=True):
    """(B, T, D) -> (B, T, D) on the leaves ``router`` (D, E), ``e_gate``,
    ``e_up`` (held, D, F), ``e_down`` (held, F, D), with ``shared``
    ``s_gate``, ``s_up``, ``s_down`` and, with ``shared_gate``, ``s_mix``,
    with ``router="sigmoid"`` ``router_bias`` (E,). The combine weights
    are multiplied by ``routed_scale``. ``scope(part)`` opens a
    part's scope. ``live`` (B x T,) bool, a served program's: the rows
    that are somebody's; the others are assigned to no expert, and the
    layer counts the live rows' assignments alone."""
    import jax.numpy as jnp
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    with scope("router"):
        if router == "sigmoid":
            idx, w = route_sigmoid(x2, p["router"], p["router_bias"],
                                   top_k)
        else:
            idx, w = route(x2, p["router"], top_k)
        if routed_scale != 1.0:
            w = w * routed_scale
    with scope("dispatch"):
        local_of = jnp.asarray(local_of, jnp.int32)
        n_assigned = idx.size
        if live is not None:
            # a row that is nobody's chooses an expert past the router's
            # width, which nobody holds
            idx = jnp.where(live[:, None], idx, local_of.shape[0])
            local_of = jnp.concatenate(
                [local_of, jnp.full((1,), -1, jnp.int32)])
            n_assigned = jnp.sum(live) * top_k
        plan = plan_blocks(idx, local_of, n_held, block)
        load_taps(plan["counts"], n_assigned, touched=live is not None)
    with scope("experts"):
        y = grouped_experts(x2, w.reshape(-1), p["e_gate"], p["e_up"],
                            p["e_down"], plan, block, top_k)
    s = None
    if shared:
        with scope("shared_expert"):
            s = shared_expert(p, x2, precision, gated=shared_gate)
    with scope("combine"):
        return (y if s is None else y + s).astype(x.dtype).reshape(shape)
