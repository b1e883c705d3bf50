"""The window/full sparse decoder's module: what ``mimo-v2.5`` and
``tiny-mimo`` name under ``reference`` (the contract is in ``modules.py``).
It holds the program's layer list for such a configuration, the weights and
tokens from the seed, and the plain reference of what was served. It serves
only: no ``train_reference``, so a ``train`` cell on these configurations
stops with one plain line.

The model, as published (MiMo-V2): a token embedding; pre-norm blocks
``h = x + attention(rmsnorm x)``, ``out = h + ffn(rmsnorm h)``. Block ``l``
is a ``window`` or a ``full`` layer by ``hybrid_layer_pattern[l]`` (1 or 0)
and has ``experts`` or a ``dense`` SwiGLU by ``moe_layer_freq[l]``; the
lists are the published ones, whole, and the ``num_hidden_layers`` first
entries are the layers held. Attention: 64 query heads of 192, KV heads of
192 for the keys and 128 for the values (4 in a full layer, 8 in a window
layer), the values times ``attention_value_scale``; half-split rotary on
the first ``rotary_dims`` features of each q and k head at base
``rope_theta`` (full) or ``swa_rope_theta`` (window); scores
``q.k / sqrt(192)`` in float32, causal; a window layer sees the keys j
with ``i - sliding_window < j <= i`` and has one learned scalar ``b_h`` a
head that joins the softmax's denominator alone:
``p_ij = exp(s_ij) / (sum_j exp(s_ij) + exp(b_h))``. Experts:
``s = sigmoid(g R)`` in float32 over all ``router_width`` experts, the
``num_experts_per_tok`` largest of ``s + c`` chosen (``c`` the selection's
correction), weighed by their ``s`` over the sum of the chosen; no shared
expert. An untied head. Departures and assumptions are in the
configuration files.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"`` on the
seed's weights as the program holds them (``weights_dtype``: rounded to
bfloat16 first, where the configuration serves that): no cache, no ring,
no kernel, nothing imported from the program or from the other modules.
One full forward pass over prompt + served tokens, a layer at a time: a
layer's weights are made from the layer's own key, used and dropped, so
the float32 copy of one layer is all that is held. Attention runs in
blocks of ``QUERY_BLOCK`` queries (a window layer's block reads the keys
that its queries can see and no others), the experts as a dense masked
sum over the experts held: every held expert on every token under a
(tokens, held) weight that is nought where the token did not choose it.

One chip's share of a deployment: ``n_routed_experts`` is the number of
experts HELD (ids ``experts_held_first`` and the following),
``router_width`` the router's. What the chosen experts that are not held
would add is left out, here as in the program.

``pick`` names what is put in the program's place for a study: a
precision (every product's operands rounded to it first: float8 scaled per
tensor; what the configuration keeps in float32, the router's scores, the
softmax and its sink, the control keeps too), or a planted fault
(``FAULTS``: the sink left out; the window one page short).
"""

import functools

import jax
import jax.numpy as jnp
import numpy

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
#: planted faults a study may put in the program's place, by name
FAULTS = ("no_sink", "window_short", "no_value_scale", "no_router_bias",
          "one_rope_base")
PAGE = 16


def layer_kinds(cfg):
    """[("full" | "window", "dense" | "experts")] of the layers held."""
    n = cfg["num_hidden_layers"]
    return [("window" if w else "full", "experts" if e else "dense")
            for w, e in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n])]


def held_ids(cfg):
    first = cfg.get("experts_held_first", 0)
    return list(range(first, first + cfg["n_routed_experts"]))


def attention_of(cfg, kind):
    """A layer's attention sizes by its kind."""
    swa = kind == "window"
    return dict(
        h=cfg["swa_num_attention_heads" if swa else "num_attention_heads"],
        kv=cfg["swa_num_key_value_heads" if swa else "num_key_value_heads"],
        dk=cfg["swa_head_dim" if swa else "head_dim"],
        dv=cfg["swa_v_head_dim" if swa else "v_head_dim"],
        base=float(cfg["swa_rope_theta" if swa else "rope_theta"]),
        window=cfg["sliding_window"] if swa else 0,
        sink=bool(cfg["add_swa_attention_sink_bias" if swa
                      else "add_full_attention_sink_bias"]))


def layer_list(cfg, lr=None):
    """The configuration as the program's layer list."""
    opt = {} if lr is None else {"solver": "adam", "learning_rate": lr}
    std = cfg["initializer_range"]
    layers = [dict(opt, type="embedding", vocab_size=cfg["vocab_size"],
                   dim=cfg["hidden_size"], stddev=std, name="embed")]
    for i, (kind, ffn) in enumerate(layer_kinds(cfg)):
        a = attention_of(cfg, kind)
        layers.append(dict(
            opt, type="hybrid_block", name="blk%d" % i, mixer="softmax",
            n_heads=a["h"], n_kv_heads=a["kv"], head_dim=a["dk"],
            v_head_dim=a["dv"], rope_base=a["base"],
            rotary_factor=cfg["rotary_dims"] / a["dk"], window=a["window"],
            sink=a["sink"], value_scale=cfg["attention_value_scale"],
            ffn=ffn, dense_hidden=cfg["intermediate_size"],
            router=cfg["scoring_func"], shared_expert=False,
            n_experts=cfg["router_width"], experts_held=held_ids(cfg),
            top_k=cfg["num_experts_per_tok"],
            expert_hidden=cfg["moe_intermediate_size"],
            eps=cfg["layernorm_epsilon"], weights_stddev=std))
    return layers + [dict(opt, type="lm_head", vocab_size=cfg["vocab_size"],
                          weights_stddev=std, name="head")]


def seed_key(seed):
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def unit_names(cfg):
    return (["embed"] + ["blk%d" % i for i in range(cfg["num_hidden_layers"])]
            + ["head"])


def unit_shapes(cfg, unit):
    """{leaf: (shape, kind)} of one unit in the program's naming; kind is
    "normal", "zeros" (the norms' ``w`` of ``1 + w``, the head's bias)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if unit == "embed":
        return {"table": ((v, d), "normal")}
    if unit == "head":
        return {"weights": ((d, v), "normal"), "bias": ((v,), "zeros")}
    kind, ffn = layer_kinds(cfg)[int(unit[3:])]
    a = attention_of(cfg, kind)
    shapes = {"ln1_w": ((d,), "zeros"), "ln2_w": ((d,), "zeros"),
              "wq": ((d, a["h"] * a["dk"]), "normal"),
              "wk": ((d, a["kv"] * a["dk"]), "normal"),
              "wv": ((d, a["kv"] * a["dv"]), "normal"),
              "wo": ((a["h"] * a["dv"], d), "normal")}
    if a["sink"]:
        shapes["sink"] = ((a["h"],), "normal")
    if ffn == "dense":
        f = cfg["intermediate_size"]
        shapes.update({"d_gate": ((d, f), "normal"),
                       "d_up": ((d, f), "normal"),
                       "d_down": ((f, d), "normal")})
        return shapes
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    shapes.update({
        "router": ((d, cfg["router_width"]), "normal"),
        "router_bias": ((cfg["router_width"],), "normal"),
        "e_gate": ((held, d, f), "normal"), "e_up": ((held, d, f), "normal"),
        "e_down": ((held, f, d), "normal")})
    return shapes


def _static(cfg):
    """The configuration as a jit's static argument."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, list)) and k not in (
            "reduced", "departures", "deployment", "source", "name")))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_unit(cfg_items, unit, key):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(cfg.get("weights_dtype", "float32"))
    key = jax.random.fold_in(key, unit_names(cfg).index(unit))
    out = {}
    for n, (name, (shape, kind)) in enumerate(
            sorted(unit_shapes(cfg, unit).items())):
        out[name] = (
            jnp.zeros(shape, dtype) if kind == "zeros" else
            (cfg["initializer_range"] * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)
             ).astype(dtype))
    return out


def make_unit(cfg, seed, unit):
    """One unit's leaves on the device from the seed, in the type the
    program holds them: a key a unit, so that the reference can make a
    layer at a time."""
    return _make_unit(_static(cfg), unit, seed_key(seed))


def make_weights(cfg, seed):
    """All weights on the device from the seed, unit by unit."""
    return {u: make_unit(cfg, seed, u) for u in unit_names(cfg)}


def make_tokens(seed, rows, seq_len, vocab):
    """(rows, seq_len + 1) token ids from the seed, on the host."""
    rng = numpy.random.default_rng([int(seed), 0x70C5])
    return rng.integers(0, vocab, (rows, seq_len + 1), dtype=numpy.int32)


def _round(x, quant):
    """``x`` rounded to the control's precision and back."""
    if quant is None:
        return x
    if quant == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "float8_e4m3fn":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError("unknown control precision %r" % (quant,))


def _mm(a, b, quant):
    return jnp.matmul(_round(a, quant), _round(b, quant), precision=HIGHEST)


def _rmsnorm(x, w, eps):
    """``x / rms(x) * (1 + w)``: the published ``x / rms(x) * weight`` with
    the weight written as one plus a leaf that starts at nought."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _rope(x, base, rotary):
    """Half-split rotary on the first ``rotary`` features of each head of
    x (T, H, D): feature j of the first half turns with j + rotary / 2."""
    t = x.shape[0]
    half = rotary // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rotary:]], axis=-1)


def _attention(p, a_in, a, cfg, quant, fault):
    """(T, D) -> (T, D): one layer's attention, blocks of queries."""
    t = a_in.shape[0]
    h, kv, dk, dv = a["h"], a["kv"], a["dk"], a["dv"]
    scale = 1.0 if fault == "no_value_scale" else cfg["attention_value_scale"]
    base = (float(cfg["rope_theta"]) if fault == "one_rope_base"
            else a["base"])
    window = a["window"]
    if window and fault == "window_short":
        window -= min(PAGE, window // 2)
    q = _rope(_mm(a_in, p["wq"], quant).reshape(t, h, dk), base,
              cfg["rotary_dims"])
    k = _rope(_mm(a_in, p["wk"], quant).reshape(t, kv, dk), base,
              cfg["rotary_dims"])
    v = scale * _mm(a_in, p["wv"], quant).reshape(t, kv, dv)
    g = h // kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    outs = []
    for start in range(0, t, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, t)
        lo = max(0, start - window + 1) if window else 0
        i = jnp.arange(start, end)[:, None]
        j = jnp.arange(lo, end)[None, :]
        seen = j <= i
        if window:
            seen = seen & (j > i - window)
        s = jnp.einsum("qhd,khd->hqk", _round(q[start:end], quant),
                       _round(k[lo:end], quant), precision=HIGHEST
                       ) / numpy.float32(numpy.sqrt(dk))
        s = jnp.where(seen[None], s, -jnp.inf)
        if a["sink"] and fault != "no_sink":
            sink = p["sink"][:, None, None]
            m = jnp.maximum(s.max(axis=-1, keepdims=True), sink)
            e = jnp.exp(s - m)
            w = e / (e.sum(axis=-1, keepdims=True) + jnp.exp(sink - m))
        else:
            w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _round(w, quant),
                               _round(v[lo:end], quant), precision=HIGHEST))
    o = jnp.concatenate(outs, axis=0).reshape(t, h * dv)
    return _mm(o, p["wo"], quant)


def _experts(p, g_in, cfg, quant, fault):
    """(T, D) -> (T, D): the held experts' part of the sparse layer."""
    scores = jax.nn.sigmoid(jnp.matmul(g_in, p["router"], precision=HIGHEST))
    chosen_by = scores if fault == "no_router_bias" else (
        scores + p["router_bias"])
    _, idx = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # (T, router_width): a chosen expert's weight, nought elsewhere
    dense = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(w)
    held = dense[:, jnp.asarray(held_ids(cfg))]

    def one(y, expert):
        wg, wu, wd, col = expert
        a = _mm(g_in, wg, quant)
        hid = a * jax.nn.sigmoid(a) * _mm(g_in, wu, quant)
        return y + col[:, None] * _mm(hid, wd, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(g_in),
                        (p["e_gate"], p["e_up"], p["e_down"], held.T))
    return y


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def _layer(cfg_items, i, p, x, quant, fault):
    """Block ``i`` over (T, D) float32."""
    cfg = dict(cfg_items)
    kind, ffn = layer_kinds(cfg)[i]
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    eps = cfg["layernorm_epsilon"]
    x = x + _attention(p, _rmsnorm(x, p["ln1_w"], eps),
                       attention_of(cfg, kind), cfg, quant, fault)
    g_in = _rmsnorm(x, p["ln2_w"], eps)
    if ffn == "dense":
        a = _mm(g_in, p["d_gate"], quant)
        return x + _mm(a * jax.nn.sigmoid(a) * _mm(g_in, p["d_up"], quant),
                       p["d_down"], quant)
    return x + _experts(p, g_in, cfg, quant, fault)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(p, x, quant):
    return (_mm(x, p["weights"].astype(jnp.float32), quant)
            + p["bias"].astype(jnp.float32))


def logits_fn(cfg, seed, tokens, pick=None, weights=None):
    """(T,) ids to (T, V) float32 logits, a layer at a time. ``weights``:
    a tree to use in place of the seed's (the tests')."""
    quant = pick if pick not in FAULTS else None
    fault = pick if pick in FAULTS else None
    static = _static(cfg)

    def unit(name):
        return weights[name] if weights is not None else make_unit(
            cfg, seed, name)

    x = jnp.take(unit("embed")["table"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(static, i, unit("blk%d" % i), x, quant, fault)
    return _head(unit("head"), x, quant)


def served_gaps(cfg, seed, prompt, served, pad=None, pick=None):
    """For each served token, the reference's best logit at that position
    minus its logit of the token that was served, from one forward pass
    over prompt + served padded to ``pad`` positions; with ``pick`` the gap
    of the token which that precision, or that planted fault, puts first.
    The seed's weights are made inside the call, a layer at a time, and
    held by nobody. Also returns the first choices."""
    seq = list(prompt) + list(served)
    n = len(seq)
    if pad:
        if n > pad:
            raise ValueError("request of %d positions, pad %d" % (n, pad))
        seq = seq + [0] * (pad - n)
    tokens = jnp.asarray(seq, jnp.int32)
    logits = logits_fn(cfg, seed, tokens[:-1])
    first = jnp.argmax(logits if pick is None else logits_fn(
        cfg, seed, tokens[:-1], pick), axis=-1)
    chosen = tokens[1:] if pick is None else first
    gaps = (jnp.max(logits, axis=-1)
            - jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0])
    lo, hi = len(prompt) - 1, n - 1
    return numpy.asarray(gaps)[lo:hi], numpy.asarray(first)[lo:hi]
