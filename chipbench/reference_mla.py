"""The latent-attention sparse decoder's module: what ``moonlight-16b-a3b``
and ``tiny-mla`` name under ``reference`` (the contract is in
``modules.py``). It holds the program's layer list for such a
configuration, the weights and tokens from the seed, and the plain
reference of training. It trains only: no ``served_gaps``, so a ``serve``
cell on these configurations stops with one plain line.

The model, as published (DeepSeek-V3's architecture, Moonlight's sizes): a
token embedding; pre-norm blocks ``h = x + mla(norm1 x)``, ``out = h +
ffn(norm2 h)``; a final RMS norm and an untied head. Every layer mixes by
latent attention (MLA) without query compression (``q_lora_rank`` null):

    q = x W_q, each head's [q_nope | q_pe]
    [c_kv | k_pe] = x W_kva;  c_kv = RMSNorm(c_kv)
    each head's [k_nope | v] = c_kv W_kvb
    k = [k_nope | rope(k_pe)] with the one k_pe head shared by all heads
    o = softmax(q k^T / sqrt(qk_nope + qk_rope), causal) v;  out = o W_o

with rotary on ``q_pe`` and ``k_pe`` alone. The first
``first_k_dense_replace`` layers' feed-forward is a dense SwiGLU; the
others' is ``n_routed_experts`` experts chosen by ``sigmoid(x W_r)`` plus
the selection's correction ``b`` (``noaux_tc``; ``n_group`` 1, so no
group step), weighed by their scores over the chosen ones' sum times
``routed_scaling_factor``, beside the ``n_shared_experts`` shared ones as
one ungated SwiGLU. The departures are in the configuration files.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no
kernel, nothing imported from the program or from the other modules. MLA
is decompressed per head; attention goes by blocks of ``QUERY_BLOCK``
queries, each over every key under the causal mask, so no (heads, T, T)
score tensor exists; the experts are a loop over the experts held, each
on every token under a dense (tokens, experts) weight that is nought
where the token did not choose the expert; the loss over the vocabulary
in blocks of positions; and a training step block by block, each unit's
leaves updated as soon as their gradient is there.

One chip's share of a deployment: ``n_routed_experts`` is the number of
experts HELD (ids ``experts_held_first`` and the following),
``router_width`` the router's. The router scores all of them, the chosen
experts that are not held add nothing, here as in the program, and
nothing stands in for them.

``quant`` names the control's precision: every matrix product rounds its
operands to that dtype first (float8 scaled per tensor); what the
configuration keeps in float32 (the router's scores) the control keeps
too.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

HIGHEST = jax.lax.Precision.HIGHEST
BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
QUERY_BLOCK = 1024
LOSS_BLOCK = 1024


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def held_ids(cfg):
    first = cfg.get("experts_held_first", 0)
    return list(range(first, first + cfg["n_routed_experts"]))


def shared_hidden(cfg):
    return cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def layer_list(cfg, lr=None):
    """The configuration as the program's layer list; with ``lr`` every
    layer trains under Adam at that rate."""
    opt = {} if lr is None else {"solver": "adam", "learning_rate": lr}
    std, eps = cfg["initializer_range"], cfg["rms_norm_eps"]
    block = {"type": "hybrid_block", "mixer": "latent",
             "n_heads": cfg["num_attention_heads"],
             "kv_rank": cfg["kv_lora_rank"],
             "qk_nope_dim": cfg["qk_nope_head_dim"],
             "qk_rope_dim": cfg["qk_rope_head_dim"],
             "v_head_dim": cfg["v_head_dim"], "rope_base": cfg["rope_theta"],
             "eps": eps, "weights_stddev": std}
    experts = {"ffn": "experts", "router": cfg["scoring_func"],
               "n_experts": cfg["router_width"],
               "experts_held": held_ids(cfg),
               "top_k": cfg["num_experts_per_tok"],
               "expert_hidden": cfg["moe_intermediate_size"],
               "shared_hidden": shared_hidden(cfg), "shared_gate": False,
               "routed_scale": cfg["routed_scaling_factor"]}
    dense = {"ffn": "dense", "dense_hidden": cfg["intermediate_size"]}
    return ([dict(opt, type="embedding", vocab_size=cfg["vocab_size"],
                  dim=cfg["hidden_size"], stddev=std, name="embed")]
            + [dict(opt, name="blk%d" % i, **block,
                    **(dense if is_dense(cfg, i) else experts))
               for i in range(cfg["num_hidden_layers"])]
            + [dict(opt, type="final_norm", eps=eps, name="norm"),
               dict(opt, type="lm_head", vocab_size=cfg["vocab_size"],
                    weights_stddev=std, name="head")])


def seed_key(seed):
    """A key from any whole number: seeds may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def block_shapes(cfg, i):
    """{leaf: (shape, kind)} of block ``i`` in the program's naming."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    shapes = {"ln1_w": ((d,), "zeros"), "ln2_w": ((d,), "zeros"),
              "wq": ((d, h * (dn + dr)), "normal"),
              "wkv_a": ((d, r + dr), "normal"), "kv_norm": ((r,), "zeros"),
              "wkv_b": ((r, h * (dn + dv)), "normal"),
              "wo": ((h * dv, d), "normal")}
    if is_dense(cfg, i):
        f = cfg["intermediate_size"]
        shapes.update({"d_gate": ((d, f), "normal"),
                       "d_up": ((d, f), "normal"),
                       "d_down": ((f, d), "normal")})
        return shapes
    f, sf, held = (cfg["moe_intermediate_size"], shared_hidden(cfg),
                   cfg["n_routed_experts"])
    shapes.update({
        "router": ((d, cfg["router_width"]), "normal"),
        "router_bias": ((cfg["router_width"],), "normal"),
        "e_gate": ((held, d, f), "normal"), "e_up": ((held, d, f), "normal"),
        "e_down": ((held, f, d), "normal"),
        "s_gate": ((d, sf), "normal"), "s_up": ((d, sf), "normal"),
        "s_down": ((sf, d), "normal")})
    return shapes


def leaf_shapes(cfg):
    """{unit: {leaf: (shape, kind)}} in the program's own naming."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": {"table": ((v, d), "normal")}}
    for i in range(cfg["num_hidden_layers"]):
        shapes["blk%d" % i] = block_shapes(cfg, i)
    shapes["norm"] = {"w": ((d,), "ones")}
    shapes["head"] = {"weights": ((d, v), "normal"), "bias": ((v,), "zeros")}
    return shapes


def _make_weights(cfg_items, key, dtype):
    cfg = dict(cfg_items)
    out, n = {}, 0
    for unit, leaves in leaf_shapes(cfg).items():
        out[unit] = {}
        for name, (shape, kind) in leaves.items():
            n += 1
            if kind == "normal":
                w = cfg["initializer_range"] * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
            else:
                w = jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                             jnp.float32)
            out[unit][name] = w.astype(dtype)
    return out


_make_weights_jit = jax.jit(_make_weights, static_argnums=(0, 2))


def _static(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float)) and v is not None))


def make_weights(cfg, seed, dtype=jnp.float32):
    """All weights on the device in one jitted call from the seed."""
    return _make_weights_jit(_static(cfg), seed_key(seed),
                             jnp.dtype(dtype).name)


def make_tokens(seed, rows, seq_len, vocab):
    """(rows, seq_len + 1) token ids from the seed, on the host: inputs
    are columns [:-1], next-token targets columns [1:]. Rows all differ."""
    rng = numpy.random.default_rng([int(seed), 0x3A1A])
    return rng.integers(0, vocab, (rows, seq_len + 1), dtype=numpy.int32)


# -- the model ---------------------------------------------------------------

def _round(x, quant):
    """``x`` rounded to the control's precision and back; the gradient
    passes straight through."""
    if quant is None:
        return x
    if quant == "bfloat16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "float8_e4m3fn":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError("unknown control precision %r" % (quant,))
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, quant):
    return jnp.matmul(_round(a, quant), _round(b, quant), precision=HIGHEST)


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(x, w, eps):
    """Zero-centred RMS norm: the weight is nought at the start."""
    return _rms(x, eps) * (1.0 + w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """Half-split rotary on every feature of x (B, T, H, R): feature j
    turns with j + R / 2 at the angle position x theta^(-2j / R)."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, quant):
    """Causal attention of q, k (B, T, H, Dk) over v (B, T, H, Dv), by
    blocks of queries: a block's (H, QUERY_BLOCK, T) scores are held for
    that block alone."""
    b, t, h, dk = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    keys = jnp.arange(t)
    scale = 1.0 / numpy.sqrt(dk).astype("float32")

    @jax.checkpoint
    def part(qs):
        qi, start = qs
        s = jnp.einsum("bqhd,bkhd->bhqk", _round(qi, quant),
                       _round(k, quant), precision=HIGHEST) * scale
        seen = (start + jnp.arange(block))[:, None] >= keys[None, :]
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round(w, quant),
                          _round(v, quant), precision=HIGHEST)
    qb = jnp.moveaxis(q.reshape(b, t // block, block, h, dk), 1, 0)
    o = jax.lax.map(part, (qb, jnp.arange(0, t, block)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, h * v.shape[-1])


def _rope_key(k_pe, h, theta):
    """The one rope key head (B, T, R), rotated and shared by all ``h``
    heads: (B, T, h, R)."""
    return jnp.repeat(_rope(k_pe[..., None, :], theta), h, axis=2)


def _mla(p, x, cfg, quant):
    b, t, _ = x.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, theta = cfg["v_head_dim"], cfg["rope_theta"]
    q = _mm(x, p["wq"], quant).reshape(b, t, h, dn + dr)
    c = _mm(x, p["wkv_a"], quant)
    c_kv = _norm(c[..., :r], p["kv_norm"], cfg["rms_norm_eps"])
    kv = _mm(c_kv, p["wkv_b"], quant).reshape(b, t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :dn], _rope_key(c[..., r:], h, theta)], -1)
    return _mm(_attend(q, k, kv[..., dn:], quant), p["wo"], quant)


def route(p, x, cfg):
    """(N, D) -> the dense (N, E) combine weights: sigmoid scores over all
    the experts in float32, the ``num_experts_per_tok`` largest of score
    plus correction chosen, their scores divided by their sum and times
    ``routed_scaling_factor``, nought elsewhere."""
    k = cfg["num_experts_per_tok"]
    scores = _sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    _, idx = jax.lax.top_k(scores + p["router_bias"], k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * cfg[
        "routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(_silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def _experts(p, x, cfg, quant):
    """Sparse experts with the ungated shared ones on x (N, D): every held
    expert on every token, weighted by the dense combine weight."""
    weights = route(p, x, cfg)[:, jnp.asarray(held_ids(cfg))]

    @jax.checkpoint
    def one(acc, ew):
        wg, wu, wd, w = ew
        return acc + w[:, None] * _swiglu(x, wg, wu, wd, quant), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["e_gate"], p["e_up"], p["e_down"], weights.T))
    return y + _swiglu(x, p["s_gate"], p["s_up"], p["s_down"], quant)


def _block(p, x, cfg, dense, quant):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(p, _norm(x, p["ln1_w"], eps), cfg, quant)
    f = _norm(x, p["ln2_w"], eps)
    if dense:
        return x + _swiglu(f, p["d_gate"], p["d_up"], p["d_down"], quant)
    return x + _experts(p, f.reshape(-1, f.shape[-1]), cfg, quant).reshape(
        f.shape)


def tail_loss(tail, x, targets, eps, quant=None, keep=None):
    """Mean next-token negative log-likelihood over the first ``keep``
    positions (all of them by default) of x (B, T, d) under the final
    norm and the head (``tail``: {"norm", "head"}), the logits made
    ``LOSS_BLOCK`` positions at a time."""
    n = targets.size
    block = LOSS_BLOCK if n % LOSS_BLOCK == 0 else n
    counted = jnp.arange(n) < (n if keep is None else keep)
    head = tail["head"]

    @jax.checkpoint
    def part(xtc):
        xi, ti, ci = xtc
        xi = _rms(xi, eps) * tail["norm"]["w"]
        logp = jax.nn.log_softmax(
            _mm(xi, head["weights"], quant) + head["bias"], axis=-1)
        return -jnp.sum(jnp.where(
            ci, jnp.take_along_axis(logp, ti[:, None], -1)[:, 0], 0.0))
    sums = jax.lax.map(part, (x.reshape(-1, block, x.shape[-1]),
                              targets.reshape(-1, block),
                              counted.reshape(-1, block)))
    return jnp.sum(sums) / jnp.sum(counted)


def loss_fn(params, tokens, targets, cfg, quant=None, keep=None):
    """The whole model's loss in one expression (the tests hold the
    block-by-block step below against its gradient)."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(params["blk%d" % i], x, cfg, is_dense(cfg, i), quant)
    return tail_loss({"norm": params["norm"], "head": params["head"]}, x,
                     targets, cfg["rms_norm_eps"], quant, keep)


# -- training: three Adam steps and what is compared of them -----------------

def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


leaf_norms = jax.jit(_leaf_norms)


def _adam(p, m, v, g, lr, t):
    """One Adam update of some leaves; also the gradient's norms."""
    tm = jax.tree_util.tree_map
    m = tm(lambda a, b: BETA1 * a + (1 - BETA1) * b, m, g)
    v = tm(lambda a, b: BETA2 * a + (1 - BETA2) * b * b, v, g)
    tf = t.astype(jnp.float32)
    p = tm(lambda w, mm, vv: w - lr * (mm / (1 - BETA1 ** tf)) / (
        jnp.sqrt(vv / (1 - BETA2 ** tf)) + ADAM_EPS), p, m, v)
    return p, m, v, _leaf_norms(g)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_forward(p, x, cfg_items, dense, quant):
    return _block(p, x, dict(cfg_items), dense, quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8),
                   donate_argnums=(0, 1, 2))
def _block_step(p, m, v, x, dy, cfg_items, dense, quant, lr, t):
    """Backward through one block from its input ``x`` and the gradient
    ``dy`` of its output, and its leaves' update."""
    _, vjp = jax.vjp(lambda p_, x_: _block(p_, x_, dict(cfg_items), dense,
                                           quant), p, x)
    g, dx = vjp(dy)
    return _adam(p, m, v, g, lr, t) + (dx,)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8),
                   donate_argnums=(0, 1, 2))
def _tail_step(p, m, v, x, targets, eps, quant, keep, lr, t):
    loss, (g, dx) = jax.value_and_grad(tail_loss, argnums=(0, 1))(
        p, x, targets, eps, quant, keep)
    return _adam(p, m, v, g, lr, t) + (dx, loss)


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def _embed_step(p, m, v, tokens, dx, lr, t):
    g = {"table": jnp.zeros_like(p["table"]).at[tokens].add(dx)}
    return _adam(p, m, v, g, lr, t)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _delta_norms(params, cfg_items, key, dtype):
    """Per-leaf norm of (params - the seed's weights); the start is made
    again inside the call, leaf by leaf, and never held whole."""
    start = _make_weights(cfg_items, key, dtype)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), params, start)


def delta_norms(params, cfg, seed):
    return _delta_norms(params, _static(cfg), seed_key(seed), "float32")


def train_reference(cfg, seed, batches, lr, quant=None, keep_share=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.
    ``batches`` are (rows, T + 1) id arrays as the program was fed them.
    A step goes forward block by block, then back from the head, and each
    unit's leaves get their Adam update as soon as their gradient is
    there: parameters and both moments are held, a whole gradient never.
    ``keep_share`` plants a fault: the loss is the mean over that leading
    share of the batch's positions only. Returns ``{"loss": [..], "grad1":
    {unit: {leaf: norm}}, "delta": {unit: {leaf: norm}}}`` as host
    numbers."""
    items, lr, eps = _static(cfg), float(lr), float(cfg["rms_norm_eps"])
    params = make_weights(cfg, seed)
    zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
    m, v = zeros(params), zeros(params)
    blocks = [("blk%d" % i, is_dense(cfg, i))
              for i in range(cfg["num_hidden_layers"])]
    tail = ("norm", "head")
    losses, grad1 = [], None
    for i, rows in enumerate(batches):
        rows, t, norms = jnp.asarray(rows), jnp.int32(i + 1), {}
        tokens, targets = rows[:, :-1], rows[:, 1:]
        keep = None if keep_share is None else int(targets.size * keep_share)
        xs = [jnp.take(params["embed"]["table"], tokens, axis=0)]
        for u, dense in blocks:
            xs.append(_block_forward(params[u], xs[-1], items, dense, quant))
        p, mm, vv, g, dx, loss = _tail_step(
            *({u: tree[u] for u in tail} for tree in (params, m, v)),
            xs.pop(), targets, eps, quant, keep, lr, t)
        for u in tail:
            params[u], m[u], v[u], norms[u] = p[u], mm[u], vv[u], g[u]
        for u, dense in reversed(blocks):
            params[u], m[u], v[u], norms[u], dx = _block_step(
                params[u], m[u], v[u], xs.pop(), dx, items, dense, quant,
                lr, t)
        params["embed"], m["embed"], v["embed"], norms["embed"] = \
            _embed_step(params["embed"], m["embed"], v["embed"], tokens, dx,
                        lr, t)
        losses.append(float(loss))
        if i == 0:
            grad1 = jax.device_get(norms)
    del m, v
    delta = jax.device_get(delta_norms(params, cfg, seed))
    return {"loss": losses, "grad1": floats(grad1), "delta": floats(delta)}


def floats(tree):
    return {u: {k: float(x) for k, x in leaves.items()}
            for u, leaves in tree.items()}
