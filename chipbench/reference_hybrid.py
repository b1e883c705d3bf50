"""The sparse linear-attention hybrid's module: what ``qwen3-next-80b-a3b``
and ``tiny-hybrid`` name under ``reference`` (the contract is in
``modules.py``). It holds the program's layer list for such a
configuration, the weights and tokens from the seed, and the plain
reference of training. It trains only: no ``served_gaps``, so a ``serve``
cell on these configurations stops with one plain line.

The model, as published (Qwen3-Next): a token embedding; pre-norm blocks
``h = x + mixer(norm1 x)``, ``out = h + experts(norm2 h)`` with
zero-centred RMS norms ``x / rms(x) (1 + w)``; layer ``i`` mixes by gated
softmax attention where ``(i + 1) % full_attention_interval == 0`` and by
the gated delta rule otherwise; every block's feed-forward is sparse
experts with a shared one; an untied head. The departures are in the
configuration files: no final norm before the head, a head bias, no
multi-token-prediction module, no auxiliary router loss.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no
kernel, no chunked form, nothing imported from the program or from the
other modules. The delta rule is the recurrence itself, one ``lax.scan``
step a token, rematerialised by segments of ``SEGMENT`` tokens so that its
backward pass keeps one state a segment and not one a token (8,192 states
of 32 x 128 x 128 floats would be 17 GB). Attention is computed one query
head at a time, the experts as a loop over the experts held, each on every
token under a dense (tokens, experts) weight that is nought where the
token did not choose the expert, the loss over the vocabulary in blocks of
positions, and a training step block by block, each unit's leaves updated
as soon as their gradient is there.

One chip's share of a deployment: ``num_experts`` is the number of experts
HELD (ids ``experts_held_first`` and the following), ``num_routed_experts``
the router's width. The router scores all of them, the chosen experts that
are not held add nothing, here as in the program, and nothing stands in
for them. The loss is therefore that of the partial layer, and every
gradient is that loss's: the held experts are where the loss can be
lowered, so training pulls the routing towards them (a cell's learning
rate is set so that a run's steps do not: ``PERF.md``, PR 34).

``quant`` names the control's precision: every matrix product, and the
recurrence's q, k and v, round their operands to that dtype first (float8
scaled per tensor); what the configuration keeps in float32 (the router's
scores, the decay) the control keeps too.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

HIGHEST = jax.lax.Precision.HIGHEST
BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
L2_EPS = 1e-6
SEGMENT = 128
LOSS_BLOCK = 1024


def is_attention(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def held_ids(cfg):
    first = cfg.get("experts_held_first", 0)
    return list(range(first, first + cfg["num_experts"]))


def layer_list(cfg, lr=None):
    """The configuration as the program's layer list; with ``lr`` every
    layer trains under Adam at that rate."""
    opt = {} if lr is None else {"solver": "adam", "learning_rate": lr}
    std = cfg["initializer_range"]
    block = {"type": "hybrid_block",
             "n_heads": cfg["num_attention_heads"],
             "n_kv_heads": cfg["num_key_value_heads"],
             "head_dim": cfg["head_dim"], "rope_base": cfg["rope_theta"],
             "rotary_factor": cfg["partial_rotary_factor"],
             "n_key_heads": cfg["linear_num_key_heads"],
             "n_value_heads": cfg["linear_num_value_heads"],
             "key_dim": cfg["linear_key_head_dim"],
             "value_dim": cfg["linear_value_head_dim"],
             "conv_taps": cfg["linear_conv_kernel_dim"],
             "n_experts": cfg["num_routed_experts"],
             "experts_held": held_ids(cfg),
             "top_k": cfg["num_experts_per_tok"],
             "expert_hidden": cfg["moe_intermediate_size"],
             "shared_hidden": cfg["shared_expert_intermediate_size"],
             "eps": cfg["rms_norm_eps"], "weights_stddev": std}
    return ([dict(opt, type="embedding", vocab_size=cfg["vocab_size"],
                  dim=cfg["hidden_size"], stddev=std, name="embed")]
            + [dict(opt, name="blk%d" % i, mixer="attention" if
                    is_attention(cfg, i) else "delta_rule", **block)
               for i in range(cfg["num_hidden_layers"])]
            + [dict(opt, type="lm_head", vocab_size=cfg["vocab_size"],
                    weights_stddev=std, name="head")])


def seed_key(seed):
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def block_shapes(cfg, i):
    """{leaf: (shape, kind)} of block ``i`` in the program's naming."""
    d = cfg["hidden_size"]
    f, sf = cfg["moe_intermediate_size"], cfg[
        "shared_expert_intermediate_size"]
    held = cfg["num_experts"]
    shapes = {"ln1_w": ((d,), "zeros"), "ln2_w": ((d,), "zeros")}
    if is_attention(cfg, i):
        hd = cfg["head_dim"]
        q_d = cfg["num_attention_heads"] * hd
        kv_d = cfg["num_key_value_heads"] * hd
        shapes.update({
            "wq": ((d, 2 * q_d), "normal"), "wk": ((d, kv_d), "normal"),
            "wv": ((d, kv_d), "normal"), "wo": ((q_d, d), "normal"),
            "q_norm": ((hd,), "zeros"), "k_norm": ((hd,), "zeros")})
    else:
        n_v = cfg["linear_num_value_heads"]
        kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
        vd = n_v * cfg["linear_value_head_dim"]
        shapes.update({
            "in_qkvz": ((d, 2 * kd + 2 * vd), "normal"),
            "in_ba": ((d, 2 * n_v), "normal"),
            "conv": ((2 * kd + vd, cfg["linear_conv_kernel_dim"]),
                     "normal"),
            "A_log": ((n_v,), "log_uniform16"),
            "dt_bias": ((n_v,), "ones"),
            "gnorm_w": ((cfg["linear_value_head_dim"],), "ones"),
            "out_proj": ((vd, d), "normal")})
    shapes.update({
        "router": ((d, cfg["num_routed_experts"]), "normal"),
        "e_gate": ((held, d, f), "normal"), "e_up": ((held, d, f), "normal"),
        "e_down": ((held, f, d), "normal"),
        "s_gate": ((d, sf), "normal"), "s_up": ((d, sf), "normal"),
        "s_down": ((sf, d), "normal"), "s_mix": ((d,), "normal")})
    return shapes


def leaf_shapes(cfg):
    """{unit: {leaf: (shape, kind)}} in the program's own naming."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": {"table": ((v, d), "normal")}}
    for i in range(cfg["num_hidden_layers"]):
        shapes["blk%d" % i] = block_shapes(cfg, i)
    shapes["head"] = {"weights": ((d, v), "normal"), "bias": ((v,), "zeros")}
    return shapes


def _make_weights(cfg_items, key, dtype):
    cfg = dict(cfg_items)
    out, n = {}, 0
    for unit, leaves in leaf_shapes(cfg).items():
        out[unit] = {}
        for name, (shape, kind) in leaves.items():
            n += 1
            k = jax.random.fold_in(key, n)
            if kind == "normal":
                w = cfg["initializer_range"] * jax.random.normal(
                    k, shape, jnp.float32)
            elif kind == "log_uniform16":
                # the decay's rate starts as the log of uniform(0, 16),
                # kept off nought
                w = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               1e-3, 16.0))
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
    rng = numpy.random.default_rng([int(seed), 0x70C5])
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


def _norm(x, w, eps):
    """Zero-centred RMS norm: the weight is nought at the start."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _rope_part(x, theta, rotary):
    """Half-split rotary on the first ``rotary`` features of each head of
    x (B, T, H, Dh): feature j turns with j + rotary / 2; the rest of the
    head is left as it is."""
    t = x.shape[1]
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rotary:]], -1)


def _attend(q, k, v, quant):
    """Causal attention of q (B, T, H, Dh) over k and v (B, T, KV, Dh),
    one query head at a time: the (T, T) scores are held for one head."""
    b, t, h, hd = q.shape
    group = h // k.shape[2]
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def head(qkv):
        qi, ki, vi = qkv
        s = jnp.einsum("bqd,bkd->bqk", _round(qi, quant), _round(ki, quant),
                       precision=HIGHEST) / numpy.sqrt(hd).astype("float32")
        w = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", _round(w, quant), _round(vi, quant),
                          precision=HIGHEST)
    o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                           jnp.repeat(jnp.moveaxis(k, 2, 0), group, axis=0),
                           jnp.repeat(jnp.moveaxis(v, 2, 0), group, axis=0)))
    return jnp.moveaxis(o, 0, 2).reshape(b, t, h * hd)


def _attention(p, x, cfg, quant):
    b, t, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    rotary = int(round(hd * cfg["partial_rotary_factor"]))
    qg = _mm(x, p["wq"], quant).reshape(b, t, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    q = _rope_part(_norm(q, p["q_norm"], eps), theta, rotary)
    k = _rope_part(_norm(_mm(x, p["wk"], quant).reshape(b, t, kv, hd),
                         p["k_norm"], eps), theta, rotary)
    v = _mm(x, p["wv"], quant).reshape(b, t, kv, hd)
    o = _attend(q, k, v, quant) * _sigmoid(gate).reshape(b, t, h * hd)
    return _mm(o, p["wo"], quant)


def _segment(t):
    return max(s for s in range(1, min(SEGMENT, t) + 1) if t % s == 0)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token. q, k: (B, T, H, K); v:
    (B, T, H, V); g, beta: (B, T, H). Per head the state S (K, V) goes
    ``S <- exp(g_t) S``, ``d_t = beta_t (v_t - S^T k_t)``,
    ``S <- S + k_t d_t^T`` and the output is ``o_t = S^T q_t``."""
    b, t, h, dk = q.shape
    seg = _segment(t)

    def token(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None, None]
        d = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=HIGHEST))
        s = s + kt[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=HIGHEST)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    def split(a):       # (B, T, ...) -> (T / seg, seg, B, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // seg, seg) + a.shape[1:])
    _, o = jax.lax.scan(segment, jnp.zeros((b, h, dk, v.shape[-1]),
                                           jnp.float32),
                        tuple(split(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _delta_layer(p, x, cfg, quant):
    b, t, _ = x.shape
    n_k, n_v = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd = n_k * dk, n_v * dv
    taps = cfg["linear_conv_kernel_dim"]
    qkvz = _mm(x, p["in_qkvz"], quant)
    ba = _mm(x, p["in_ba"], quant)
    qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    # causal depthwise convolution: tap j weighs the token taps - 1 - j back
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = _silu(sum(padded[:, j:j + t] * p["conv"][:, j]
                    for j in range(taps)))

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    q = unit(qkv[..., :kd].reshape(b, t, n_k, dk)) / numpy.sqrt(dk).astype(
        "float32")
    k = unit(qkv[..., kd:2 * kd].reshape(b, t, n_k, dk))
    # key head j serves value heads j * n_v / n_k and the following
    q, k = (jnp.repeat(a, n_v // n_k, axis=2) for a in (q, k))
    v = qkv[..., 2 * kd:].reshape(b, t, n_v, dv)
    beta = _sigmoid(ba[..., :n_v])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., n_v:] + p["dt_bias"])
    o = delta_rule(_round(q, quant), _round(k, quant), _round(v, quant),
                   g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + cfg["rms_norm_eps"])
    o = p["gnorm_w"] * o * _silu(z.reshape(b, t, n_v, dv))
    return _mm(o.reshape(b, t, vd), p["out_proj"], quant)


def route(p, x, cfg):
    """(N, D) -> the dense (N, E) combine weights: softmax over all the
    experts in float32, the ``num_experts_per_tok`` largest kept and
    divided by their sum, nought elsewhere."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.matmul(x, p["router"], precision=HIGHEST),
                           axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def _experts(p, x, cfg, quant):
    """Sparse experts with the shared one on x (N, D): every held expert
    on every token, weighted by the dense combine weight."""
    weights = route(p, x, cfg)[:, jnp.asarray(held_ids(cfg))]

    @jax.checkpoint
    def one(acc, ew):
        wg, wu, wd, w = ew
        h = _silu(_mm(x, wg, quant)) * _mm(x, wu, quant)
        return acc + w[:, None] * _mm(h, wd, quant), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["e_gate"], p["e_up"], p["e_down"], weights.T))
    shared = _mm(_silu(_mm(x, p["s_gate"], quant)) * _mm(x, p["s_up"], quant),
                 p["s_down"], quant)
    mix = _sigmoid(jnp.matmul(_round(x, quant), _round(p["s_mix"], quant),
                              precision=HIGHEST))
    return y + mix[:, None] * shared


def _block(p, x, cfg, attention, quant):
    eps = cfg["rms_norm_eps"]
    a = _norm(x, p["ln1_w"], eps)
    x = x + (_attention(p, a, cfg, quant) if attention
             else _delta_layer(p, a, cfg, quant))
    f = _norm(x, p["ln2_w"], eps)
    return x + _experts(p, f.reshape(-1, f.shape[-1]), cfg, quant).reshape(
        f.shape)


def head_loss(head, x, targets, quant=None, keep=None):
    """Mean next-token negative log-likelihood over the first ``keep``
    positions (all of them by default) of x (B, T, d) under the head, the
    logits made ``LOSS_BLOCK`` positions at a time."""
    n = targets.size
    block = LOSS_BLOCK if n % LOSS_BLOCK == 0 else n
    counted = jnp.arange(n) < (n if keep is None else keep)

    @jax.checkpoint
    def part(xtc):
        xi, ti, ci = xtc
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
        x = _block(params["blk%d" % i], x, cfg, is_attention(cfg, i), quant)
    return head_loss(params["head"], x, targets, quant, keep)


# -- training: three Adam steps and what is compared of them -----------------

def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


leaf_norms = jax.jit(_leaf_norms)


def _adam(p, m, v, g, lr, t):
    """One Adam update of one unit's leaves; also the gradient's norms."""
    tm = jax.tree_util.tree_map
    m = tm(lambda a, b: BETA1 * a + (1 - BETA1) * b, m, g)
    v = tm(lambda a, b: BETA2 * a + (1 - BETA2) * b * b, v, g)
    tf = t.astype(jnp.float32)
    p = tm(lambda w, mm, vv: w - lr * (mm / (1 - BETA1 ** tf)) / (
        jnp.sqrt(vv / (1 - BETA2 ** tf)) + ADAM_EPS), p, m, v)
    return p, m, v, _leaf_norms(g)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_forward(p, x, cfg_items, attention, quant):
    return _block(p, x, dict(cfg_items), attention, quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8),
                   donate_argnums=(0, 1, 2))
def _block_step(p, m, v, x, dy, cfg_items, attention, quant, lr, t):
    """Backward through one block from its input ``x`` and the gradient
    ``dy`` of its output, and its leaves' update."""
    _, vjp = jax.vjp(lambda p_, x_: _block(p_, x_, dict(cfg_items),
                                           attention, quant), p, x)
    g, dx = vjp(dy)
    return _adam(p, m, v, g, lr, t) + (dx,)


@functools.partial(jax.jit, static_argnums=(5, 6, 7), donate_argnums=(0, 1, 2))
def _head_step(p, m, v, x, targets, quant, keep, lr, t):
    loss, (g, dx) = jax.value_and_grad(head_loss, argnums=(0, 1))(
        p, x, targets, quant, keep)
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
    items, lr = _static(cfg), float(lr)
    params = make_weights(cfg, seed)
    zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))
    m, v = zeros(params), zeros(params)
    blocks = [("blk%d" % i, is_attention(cfg, i))
              for i in range(cfg["num_hidden_layers"])]
    losses, grad1 = [], None
    for i, rows in enumerate(batches):
        rows, t, norms = jnp.asarray(rows), jnp.int32(i + 1), {}
        tokens, targets = rows[:, :-1], rows[:, 1:]
        keep = None if keep_share is None else int(targets.size * keep_share)
        xs = [jnp.take(params["embed"]["table"], tokens, axis=0)]
        for u, attention in blocks:
            xs.append(_block_forward(params[u], xs[-1], items, attention,
                                     quant))
        params["head"], m["head"], v["head"], norms["head"], dx, loss = \
            _head_step(params["head"], m["head"], v["head"], xs.pop(),
                       targets, quant, keep, lr, t)
        for u, attention in reversed(blocks):
            params[u], m[u], v[u], norms[u], dx = _block_step(
                params[u], m[u], v[u], xs.pop(), dx, items, attention,
                quant, lr, t)
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
