"""Gated delta rule: the linear-attention mixer of a hybrid block.

Per value head a (key, value) state ``S`` is decayed, corrected towards
the token's value along its key, and read with the query, token by token:

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

``recurrent_delta_rule`` is that recurrence as it stands (what the tests
hold the chunked form to, and the step a serving slot would take). ``chunked_delta_rule`` computes the same in
chunks of ``CHUNK`` tokens: inside a chunk the corrections solve a
unit-lower-triangular system, ``(I + A) D = beta (V - decay K S)`` with
``A_ij = beta_i (k_i . k_j) exp(g_i - g_j)`` for ``j < i``, whose inverse
is a product of ``log2(CHUNK)`` matrix factors (``A`` is nilpotent), and
the state moves once a chunk. Everything is matrix products and one
``lax.scan`` over the chunks, so ``jax.grad`` differentiates it like any
other unit; the mixer rematerialises it in the backward pass.

``delta_rule_mixer`` is the layer around it: one projection to q, k, v
and the output gate z, one to the per-head ``b`` and ``a``; a causal
depthwise convolution and SiLU over q, k, v; ``beta = sigmoid(b)`` and
``g = -exp(A_log) softplus(a + dt_bias)`` in float32; q and k normalised
to unit length over the head; the rule; an RMS norm over each head gated
by ``silu(z)``; the output projection.
"""

from __future__ import annotations

import numpy

#: tokens a chunk; the triangular system is CHUNK x CHUNK a head
CHUNK = 64
#: the triangular system's products (K K^T, the inverse's chain, the
#: inverse times V and K) keep float32 operands whole: an error in the
#: inverse compounds through the chain's ten factors
INVERSE_PRECISION = "highest"
L2_EPS = 1e-6


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def recurrent_delta_rule(q, k, v, g, beta):
    """The recurrence token by token. q, k: (B, T, H, K), already
    normalised and scaled; v: (B, T, H, V); g, beta: (B, T, H). Returns
    (B, T, H, V) float32 and the last state (B, H, K, V)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    b, _, h, dk = q.shape

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None, None]
        d = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=hi))
        s = s + kt[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=hi)

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    s, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _unit_lower_inverse(a, precision):
    """(I + A)^-1 for strictly lower triangular A (..., C, C), C a power
    of two: with P = -A, I + P + P^2 + ... + P^(C-1) as the product
    (I + P)(I + P^2)(I + P^4)..., since P^C = 0. Its backward pass is the
    inverse's own, ``dA = -T^T dT T^T``: two products, not the chain's
    twenty."""
    import jax
    import jax.numpy as jnp

    def chain(a):
        c = a.shape[-1]
        p = -a
        out = jnp.eye(c, dtype=a.dtype) + p
        for _ in range(int(numpy.log2(c)) - 1):
            p = jnp.matmul(p, p, precision=precision)
            out = out + jnp.matmul(out, p, precision=precision)
        return out

    def forward(a):
        t = chain(a)
        return t, t

    def backward(t, dt):
        tt = jnp.swapaxes(t, -1, -2)
        return (-jnp.matmul(jnp.matmul(tt, dt, precision=precision), tt,
                            precision=precision),)

    inverse = jax.custom_vjp(chain)
    inverse.defvjp(forward, backward)
    return inverse(a)


def remat(f):
    """``jax.checkpoint`` by hand: ``f``'s arguments are all that the
    forward pass keeps, and the backward pass runs ``f`` again. Written
    out because of the names: what ``jax.checkpoint`` recomputes is named
    ``transpose(jvp(forward))/jvp(forward)/checkpoint/...`` in a train
    step, one level too deep for a reader that cuts the name stack at two
    (its backward pass would read ``backward/forward``); here the scopes
    that ``f`` opens stand directly under the backward pass's mark.

    A stopgap for that reader and nothing else: once it cuts a train
    step's stack deeper (ROADMAP S0), this goes, ``jax.checkpoint`` comes
    back in ``delta_rule_mixer``, and ``nn/hybrid.py`` names its scopes
    block first (``blk2/delta_rule``) like ``TransformerBlock``."""
    import jax

    def backward(args, ct):
        # behind a barrier, or XLA merges the second run of ``f`` with the
        # first and keeps everything the first made (jax.checkpoint's
        # ``prevent_cse``)
        args, ct = jax.lax.optimization_barrier((args, ct))
        return jax.vjp(f, *args)[1](ct)
    g = jax.custom_vjp(f)
    g.defvjp(lambda *args: (f(*args), args), backward)
    return g


def chunked_delta_rule(q, k, v, g, beta, chunk=CHUNK, precision=None):
    """The same result as ``recurrent_delta_rule``'s first, by chunks.
    Shapes as there; T is padded to whole chunks with tokens that leave
    the state alone (k = 0, beta = 0, g = 0). ``precision`` is that of
    the products with the state and the values; the triangular inverse
    and what it multiplies are always at ``INVERSE_PRECISION``."""
    import jax
    import jax.numpy as jnp
    hi = INVERSE_PRECISION
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(a):      # (B, T, H, ...) -> (n, B, H, C, ...)
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)
    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                      # within the chunk
    rows = jnp.arange(chunk)
    lower = rows[:, None] >= rows[None, :]
    strict = rows[:, None] > rows[None, :]
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb, vb = k * beta[..., None], v * beta[..., None]
    a = jnp.where(strict, jnp.einsum("...ik,...jk->...ij", kb, k,
                                     precision=hi) * decay, 0.0)
    inv = _unit_lower_inverse(a, hi)
    u = jnp.matmul(inv, vb, precision=hi)
    w = jnp.matmul(inv, kb * jnp.exp(gc)[..., None], precision=hi)
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=precision) * decay
    q_in = q * jnp.exp(gc)[..., None]
    g_last = gc[..., -1:]
    k_tail = k * jnp.exp(g_last - gc)[..., None]
    s_decay = jnp.exp(g_last)[..., None]

    def step(s, xs):
        u_i, w_i, qk_i, q_i, kt_i, sd_i = xs
        v_new = u_i - jnp.matmul(w_i, s, precision=precision)
        o = (jnp.matmul(q_i, s, precision=precision)
             + jnp.matmul(qk_i, v_new, precision=precision))
        s = s * sd_i + jnp.einsum("...ck,...cv->...kv", kt_i, v_new,
                                  precision=precision)
        return s, o

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32),
                        (u, w, qk, q_in, k_tail, s_decay))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)    # (B, n, C, H, V)
    return o.reshape(b, n * chunk, h, dv)[:, :t]


def causal_conv(x, w):
    """Depthwise causal convolution over time: x (B, T, C), w (C, taps);
    ``y_t = sum_j w[:, j] x_(t - taps + 1 + j)``, nothing before t = 0."""
    import jax.numpy as jnp
    taps = w.shape[-1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(taps))


def l2_normalise(x):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def plain_scope(part):
    from jax import named_scope
    return named_scope(part)


def delta_rule_inputs(p, qkvz, ba, n_k, n_v, dk, dv, scope=plain_scope):
    """q, k, v, g, beta as the rule takes them and the gate z, from the
    two projections' outputs; float32. Key head j serves value heads
    ``j * n_v / n_k`` and the next ones."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, t, _ = qkvz.shape
    kd, vd = n_k * dk, n_v * dv
    with scope("conv"):
        qkv = _silu(causal_conv(qkvz[..., :2 * kd + vd].astype(f32),
                                p["conv"].astype(f32)))
    with scope("delta_prep"):
        z = qkvz[..., 2 * kd + vd:].astype(f32).reshape(b, t, n_v, dv)
        rep = n_v // n_k
        q = l2_normalise(qkv[..., :kd].reshape(b, t, n_k, dk)) * dk ** -0.5
        k = l2_normalise(qkv[..., kd:2 * kd].reshape(b, t, n_k, dk))
        q, k = (jnp.repeat(a, rep, axis=2) for a in (q, k))
        v = qkv[..., 2 * kd:].reshape(b, t, n_v, dv)
        ba = ba.astype(f32)
        beta = jax.nn.sigmoid(ba[..., :n_v])
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., n_v:] + p["dt_bias"].astype(f32))
    return q, k, v, g, beta, z


def gated_head_norm(o, z, w, eps):
    """``w * o / rms(o) * silu(z)`` over each head's features."""
    import jax
    import jax.numpy as jnp
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return w.astype(jnp.float32) * o * _silu(z)


def delta_rule_mixer(p, x, *, n_k, n_v, dk, dv, eps, precision,
                     scope=plain_scope):
    """(B, T, D) -> (B, T, D): the gated delta-rule layer on the leaves
    ``in_qkvz``, ``in_ba``, ``conv``, ``A_log``, ``dt_bias``, ``gnorm_w``
    and ``out_proj``. The part between the projections is rematerialised
    in the backward pass: what it keeps is the projections' outputs.
    ``scope(part)`` opens the named scope of one part of the layer."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    with scope("delta_in"):
        qkvz = jnp.dot(x, p["in_qkvz"], precision=precision)
        ba = jnp.dot(x, p["in_ba"], precision=precision)

    def core(small, qkvz, ba):
        q, k, v, g, beta, z = delta_rule_inputs(small, qkvz, ba, n_k, n_v,
                                                dk, dv, scope)
        with scope("delta_rule"):
            o = chunked_delta_rule(q, k, v, g, beta, precision=precision)
        with scope("delta_gate"):
            o = gated_head_norm(o, z, small["gnorm_w"], eps)
        return o.reshape(b, t, n_v * dv).astype(x.dtype)

    o = remat(core)({k: p[k] for k in ("conv", "A_log", "dt_bias",
                                       "gnorm_w")}, qkvz, ba)
    with scope("delta_out"):
        return jnp.dot(o, p["out_proj"], precision=precision)
