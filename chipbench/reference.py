"""The plain reference of both configurations, and the weights.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no
kernel, no cache, nothing imported from the program. It is computed in
pieces so that it fits wherever the program fits: attention one group of
query heads at a time, the loss over the vocabulary in blocks of
positions, and a training step layer by layer, each layer's leaves updated
as soon as their gradient is there.
The model is the published one (token embedding, pre-norm blocks of
RMSNorm, grouped-query attention with half-split RoPE and an optional
sliding window, SwiGLU, an untied head) with the departures the
configuration files list: no final RMSNorm, and a bias on the head.

``make_weights`` is the benchmark's own recipe from ``--seed`` (normal,
the published ``initializer_range``; gains one, bias zero). The program
is handed the same arrays, so both sides start from equal weights and
neither takes anything the other has made.

``quant`` names the control's precision: every matrix product rounds
both operands to that dtype first (float8 scaled per tensor), which is
the step below the bfloat16 products the configurations state.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

HIGHEST = jax.lax.Precision.HIGHEST
BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8


def seed_key(seed):
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    # the "rbg" generator: the device's own, many times faster on a TPU
    # than threefry for the billions of normals a run draws
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def leaf_shapes(cfg):
    """{unit: {leaf: (shape, kind)}} in the program's own naming."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q_d, kv_d = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    v = cfg["vocab_size"]
    shapes = {"embed": {"table": ((v, d), "normal")}}
    for i in range(cfg["num_hidden_layers"]):
        shapes["blk%d" % i] = {
            "wq": ((d, q_d), "normal"), "wk": ((d, kv_d), "normal"),
            "wv": ((d, kv_d), "normal"), "wo": ((q_d, d), "normal"),
            "w1": ((d, f), "normal"), "w3": ((d, f), "normal"),
            "w2": ((f, d), "normal"),
            "ln1_g": ((d,), "ones"), "ln2_g": ((d,), "ones")}
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
    rng = numpy.random.default_rng([int(seed), 0x70C5])
    return rng.integers(0, vocab, (rows, seq_len + 1), dtype=numpy.int32)


# -- the model ---------------------------------------------------------------

def _round(x, quant):
    """``x`` rounded to the control's precision and back. The gradient
    passes straight through, so the backward products see the rounded
    operands and an unrounded cotangent: milder than a true low-precision
    backward pass, which makes the control harder to tell from the
    reference, not easier."""
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


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Half-split pairing on (B, T, H, Dh): feature j turns with j+half."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, cfg, quant):
    """Causal (and windowed) attention of q (B, T, H, Dh) over k and v
    (B, T, KV, Dh), the query heads of one key head at a time: the (T, T)
    scores are held for H / KV heads only."""
    b, t, h, hd = q.shape
    kv = k.shape[2]
    rel = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    mask = rel >= 0
    if cfg.get("sliding_window"):
        mask = mask & (rel < cfg["sliding_window"])

    @jax.checkpoint
    def group(qkv):
        qi, ki, vi = qkv
        s = jnp.einsum("bqgd,bkd->bgqk", _round(qi, quant), _round(ki, quant),
                       precision=HIGHEST) / numpy.sqrt(hd).astype("float32")
        w = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", _round(w, quant),
                          _round(vi, quant), precision=HIGHEST)
    o = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(b, t, kv, h // kv, hd), 2, 0),
        jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(o, 0, 2).reshape(b, t, h * hd)


def _block(p, x, cfg, quant):
    b, t, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    eps = cfg["rms_norm_eps"]
    a = _rms(x, p["ln1_g"], eps)
    q = _rope(_mm(a, p["wq"], quant).reshape(b, t, h, hd), cfg["rope_theta"])
    k = _rope(_mm(a, p["wk"], quant).reshape(b, t, kv, hd), cfg["rope_theta"])
    v = _mm(a, p["wv"], quant).reshape(b, t, kv, hd)
    x = x + _mm(_attend(q, k, v, cfg, quant), p["wo"], quant)
    f = _rms(x, p["ln2_g"], eps)
    gate = _mm(f, p["w1"], quant)
    return x + _mm(gate / (1.0 + jnp.exp(-gate)) * _mm(f, p["w3"], quant),
                   p["w2"], quant)


def logits_fn(params, tokens, cfg, quant=None):
    """(B, T) ids to (B, T, V) float32 logits, one block at a time."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(params["blk%d" % i], x, cfg, quant)
    return _mm(x, params["head"]["weights"], quant) + params["head"]["bias"]


LOSS_BLOCK = 1024


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
    layer-by-layer step below against its gradient)."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(params["blk%d" % i], x, cfg, quant)
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


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block_forward(p, x, cfg_items, quant):
    return _block(p, x, dict(cfg_items), quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7), donate_argnums=(0, 1, 2))
def _block_step(p, m, v, x, dy, cfg_items, quant, lr, t):
    """Backward through one block from its input ``x`` and the gradient
    ``dy`` of its output, and its leaves' update."""
    _, vjp = jax.vjp(lambda p_, x_: _block(p_, x_, dict(cfg_items), quant),
                     p, x)
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
    blocks = ["blk%d" % i for i in range(cfg["num_hidden_layers"])]
    losses, grad1 = [], None
    for i, rows in enumerate(batches):
        rows, t, norms = jnp.asarray(rows), jnp.int32(i + 1), {}
        tokens, targets = rows[:, :-1], rows[:, 1:]
        keep = None if keep_share is None else int(targets.size * keep_share)
        xs = [jnp.take(params["embed"]["table"], tokens, axis=0)]
        for u in blocks:
            xs.append(_block_forward(params[u], xs[-1], items, quant))
        params["head"], m["head"], v["head"], norms["head"], dx, loss = \
            _head_step(params["head"], m["head"], v["head"], xs.pop(),
                       targets, quant, keep, lr, t)
        for u in reversed(blocks):
            params[u], m[u], v[u], norms[u], dx = _block_step(
                params[u], m[u], v[u], xs.pop(), dx, items, quant, lr, t)
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


# -- serving: how far a served token lies below the reference's best ---------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _gaps(params, tokens, cfg_items, pick):
    cfg = dict(cfg_items)
    logits = logits_fn(params, tokens[None, :-1], cfg, None)[0]
    first = jnp.argmax(logits if pick is None else logits_fn(
        params, tokens[None, :-1], cfg, pick)[0], axis=-1)
    chosen = tokens[1:] if pick is None else first
    return (jnp.max(logits, axis=-1)
            - jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0], first)


def served_gaps(params, cfg, prompt, served, pad=None, pick=None):
    """For each served token, the reference's best logit at that position
    minus its logit of the token that was served: 0 where the reference
    would have chosen the same. One forward pass over prompt + served,
    padded at the end to ``pad`` positions (causal: what comes after a
    position does not reach it) so that every request is one shape.
    ``pick`` names the control's precision: the gap is then read for the
    token which that precision itself puts first at each position. Also
    returns those first choices (the reference's own without ``pick``)."""
    seq = list(prompt) + list(served)
    n = len(seq)
    if pad:
        if n > pad:
            raise ValueError("request of %d positions, pad %d" % (n, pad))
        seq = seq + [0] * (pad - n)
    gaps, chosen = _gaps(params, jnp.asarray(seq, jnp.int32), _static(cfg),
                         pick)
    lo, hi = len(prompt) - 1, n - 1
    return numpy.asarray(gaps)[lo:hi], numpy.asarray(chosen)[lo:hi]
