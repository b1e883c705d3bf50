"""The post-norm delta-rule hybrid's module: what ``olmo-hybrid-7b`` and
``tiny-olmo`` name under ``reference`` (the contract is in ``modules.py``).
It holds the program's layer list for such a configuration, the weights and
tokens from the seed, and the plain reference of what was served. It serves
only: no ``train_reference``, so a ``train`` cell on these configurations
stops with one plain line.

The model (Olmo-Hybrid): a token embedding; blocks normalised on their
sublayers' outputs and never on the way in (Olmo's "reordered" post-norm),
``h = x + rmsnorm(mixer(x))``, ``out = h + rmsnorm(swiglu(h))``, with the
plain ``x / rms(x) * w``; a final RMSNorm and an untied head. Block ``l`` is
a full or a linear-attention layer by ``layer_types[l]``; the list is the
published one, whole, and its ``num_hidden_layers`` first entries are the
layers held.

- Full layers: ``num_attention_heads`` heads of ``hidden / heads`` on as
  many KV heads; an RMSNorm over the whole q projection and one over the
  whole k projection before they are split into heads; no rotary (the
  row's ``rope_theta`` is null: no position enters but through the
  linear layers); causal softmax at ``1 / sqrt(head)``; the output
  projection.
- Linear layers, the gated delta rule (FLA's ``GatedDeltaNet`` at the row's
  widths): q and k of ``linear_num_key_heads`` x ``linear_key_head_dim``, v
  and the gate z of ``linear_num_value_heads`` x ``linear_value_head_dim``;
  a causal depthwise convolution of ``linear_conv_kernel_dim`` taps over
  each of q, k and v, then SiLU; q and k L2-normalised over the head and q
  times ``key_head ** -0.5``; ``g = -exp(A_log) softplus(a + dt_bias)``;
  ``beta = 2 sigmoid(b)`` where ``linear_allow_neg_eigval``; per value head
  the state S (key head x value head) in float32, token by token::

      S <- exp(g) S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q

  then an RMSNorm over each head times ``silu(z)``, and the output
  projection.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"`` on the
seed's weights as the program holds them (``weights_dtype``: rounded to
bfloat16 first): no cache, no kernel, no chunked form, nothing imported
from the program or from the other modules. One forward pass over prompt +
served tokens, a layer at a time (a layer's weights are made from its own
key, used and dropped): the recurrence runs token by token, attention in
blocks of ``QUERY_BLOCK`` queries.

``pick`` names what is put in the program's place for a study: a precision
(every product's operands rounded to it first, float8 scaled per tensor;
the recurrence on the state, which the configuration keeps in float32, the
control keeps too), or a planted fault (``FAULTS``): ``beta`` not doubled,
the norms before the sublayers, q and k normalised a head at a time, a
rotary on the full layers, no final norm.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
#: planted faults a study may put in the program's place, by name
FAULTS = ("beta_single", "pre_norm", "per_head_qk_norm", "rotary",
          "no_final_norm")
#: the base of the ``rotary`` fault's half-split rotary over the whole head
ROTARY_BASE = 10000.0
L2_EPS = 1e-6


def layer_kinds(cfg):
    """["full" | "linear"] of the layers held."""
    return ["full" if t == "full_attention" else "linear"
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def linear_of(cfg):
    """A linear layer's sizes: key and value heads and widths, taps, and
    the convolution's channels (q, k and v side by side)."""
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return dict(nk=nk, nv=nv, dk=dk, dv=dv,
                taps=cfg["linear_conv_kernel_dim"],
                channels=2 * nk * dk + nv * dv)


def layer_list(cfg, lr=None):
    """The configuration as the program's layer list."""
    opt = {} if lr is None else {"solver": "adam", "learning_rate": lr}
    std, d = cfg["initializer_range"], cfg["hidden_size"]
    eps = cfg["rms_norm_eps"]
    layers = [dict(opt, type="embedding", vocab_size=cfg["vocab_size"],
                   dim=d, stddev=std, name="embed")]
    for i, kind in enumerate(layer_kinds(cfg)):
        block = dict(opt, type="hybrid_block", name="blk%d" % i, norm="post",
                     ffn="dense", dense_hidden=cfg["intermediate_size"],
                     eps=eps, weights_stddev=std)
        if kind == "full":
            h = cfg["num_attention_heads"]
            block.update(mixer="softmax", n_heads=h,
                         n_kv_heads=cfg["num_key_value_heads"],
                         head_dim=d // h, rotary_factor=0.0, qk_norm=True)
        else:
            lin = linear_of(cfg)
            block.update(mixer="delta_rule", n_key_heads=lin["nk"],
                         n_value_heads=lin["nv"], key_dim=lin["dk"],
                         value_dim=lin["dv"], conv_taps=lin["taps"],
                         beta_scale=2.0 if cfg["linear_allow_neg_eigval"]
                         else 1.0)
        layers.append(block)
    return layers + [dict(opt, type="final_norm", eps=eps, name="norm"),
                     dict(opt, type="lm_head", vocab_size=cfg["vocab_size"],
                          weights_stddev=std, name="head")]


def seed_key(seed):
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def unit_names(cfg):
    return (["embed"] + ["blk%d" % i for i in range(cfg["num_hidden_layers"])]
            + ["norm", "head"])


def unit_shapes(cfg, unit):
    """{leaf: (shape, kind)} of one unit in the program's naming. Kinds:
    "normal" (at ``initializer_range``), "conv" (normal at
    ``1 / sqrt(taps)``), "ones" (a plain norm's weight), "zeros" (the
    head's bias), "a_log" (the log of uniform(1, 16)), "dt_bias" (the
    inverse softplus of a log-uniform step in [1e-3, 0.1])."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if unit == "embed":
        return {"table": ((v, d), "normal")}
    if unit == "norm":
        return {"w": ((d,), "ones")}
    if unit == "head":
        return {"weights": ((d, v), "normal"), "bias": ((v,), "zeros")}
    f = cfg["intermediate_size"]
    shapes = {"ln1_w": ((d,), "ones"), "ln2_w": ((d,), "ones"),
              "d_gate": ((d, f), "normal"), "d_up": ((d, f), "normal"),
              "d_down": ((f, d), "normal")}
    if layer_kinds(cfg)[int(unit[3:])] == "full":
        q = cfg["num_attention_heads"] * (d // cfg["num_attention_heads"])
        kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
        shapes.update({"wq": ((d, q), "normal"), "wk": ((d, kv), "normal"),
                       "wv": ((d, kv), "normal"), "wo": ((q, d), "normal"),
                       "q_norm": ((q,), "ones"), "k_norm": ((kv,), "ones")})
        return shapes
    lin = linear_of(cfg)
    nv = lin["nv"]
    shapes.update({
        "in_qkvz": ((d, 2 * lin["nk"] * lin["dk"] + 2 * nv * lin["dv"]),
                    "normal"),
        "in_ba": ((d, 2 * nv), "normal"),
        "conv": ((lin["channels"], lin["taps"]), "conv"),
        "A_log": ((nv,), "a_log"), "dt_bias": ((nv,), "dt_bias"),
        "gnorm_w": ((lin["dv"],), "ones"),
        "out_proj": ((nv * lin["dv"], d), "normal")})
    return shapes


def _static(cfg):
    """The configuration as a jit's static argument."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, list)) and k not in (
            "reduced", "departures", "deployment", "source", "name")))


def _draw(key, shape, kind, cfg):
    f32 = jnp.float32
    if kind == "normal":
        return cfg["initializer_range"] * jax.random.normal(key, shape, f32)
    if kind == "conv":
        return jax.random.normal(key, shape, f32) / numpy.sqrt(shape[-1])
    if kind == "ones":
        return jnp.ones(shape, f32)
    if kind == "zeros":
        return jnp.zeros(shape, f32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, numpy.log(1e-3),
                                        numpy.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_unit(cfg_items, unit, key):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(cfg.get("weights_dtype", "float32"))
    key = jax.random.fold_in(key, unit_names(cfg).index(unit))
    return {name: _draw(jax.random.fold_in(key, n), shape, kind, cfg
                        ).astype(dtype)
            for n, (name, (shape, kind)) in enumerate(
                sorted(unit_shapes(cfg, unit).items()))}


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
    rng = numpy.random.default_rng([int(seed), 0x01A0])
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
    """The plain ``x / rms(x) * w`` over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x):
    """The fault's half-split rotary over the whole head of x (T, H, D)."""
    t, half = x.shape[0], x.shape[-1] // 2
    inv = ROTARY_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _full(p, x, cfg, quant, fault):
    """(T, D) -> (T, D): a full layer's attention, blocks of queries."""
    t, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // h, cfg["rms_norm_eps"]
    q, k = _mm(x, p["wq"], quant), _mm(x, p["wk"], quant)
    v = _mm(x, p["wv"], quant).reshape(t, kv, hd)
    if fault == "per_head_qk_norm":
        q = _rmsnorm(q.reshape(t, h, hd), p["q_norm"].reshape(h, hd), eps)
        k = _rmsnorm(k.reshape(t, kv, hd), p["k_norm"].reshape(kv, hd), eps)
    else:
        q = _rmsnorm(q, p["q_norm"], eps).reshape(t, h, hd)
        k = _rmsnorm(k, p["k_norm"], eps).reshape(t, kv, hd)
    if fault == "rotary":
        q, k = _rope(q), _rope(k)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    outs = []
    for start in range(0, t, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, t)
        seen = (jnp.arange(end)[None, :]
                <= jnp.arange(start, end)[:, None])
        s = jnp.einsum("qhd,khd->hqk", _round(q[start:end], quant),
                       _round(k[:end], quant), precision=HIGHEST
                       ) / numpy.float32(numpy.sqrt(hd))
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _round(w, quant),
                               _round(v[:end], quant), precision=HIGHEST))
    return _mm(jnp.concatenate(outs, axis=0).reshape(t, h * hd), p["wo"],
               quant)


def _linear(p, x, cfg, quant, fault):
    """(T, D) -> (T, D): a gated delta-rule layer, token by token."""
    t = x.shape[0]
    lin = linear_of(cfg)
    nk, nv, dk, dv, taps = (lin[k] for k in ("nk", "nv", "dk", "dv", "taps"))
    kd, vd = nk * dk, nv * dv
    qkvz, ba = _mm(x, p["in_qkvz"], quant), _mm(x, p["in_ba"], quant)
    # the causal depthwise convolution over q, k and v, then SiLU
    pre = jnp.pad(qkvz[:, :2 * kd + vd], ((taps - 1, 0), (0, 0)))
    conv = sum(pre[j:j + t] * p["conv"][:, j] for j in range(taps))
    conv = _silu(conv)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q = unit(conv[:, :kd].reshape(t, nk, dk)) * dk ** -0.5
    k = unit(conv[:, kd:2 * kd].reshape(t, nk, dk))
    q, k = (jnp.repeat(a, nv // nk, axis=1) for a in (q, k))
    v = conv[:, 2 * kd:].reshape(t, nv, dv)
    z = qkvz[:, 2 * kd + vd:].reshape(t, nv, dv)
    doubled = cfg["linear_allow_neg_eigval"] and fault != "beta_single"
    beta = (2.0 if doubled else 1.0) * jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, nv:] + p["dt_bias"])

    def token(s, xs):
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[:, None, None] * s
        old = jnp.einsum("hkv,hk->hv", s, kt, precision=HIGHEST)
        s = s + jnp.einsum("hk,hv->hkv", kt, bt[:, None] * (vt - old),
                           precision=HIGHEST)
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = _rmsnorm(o, p["gnorm_w"], cfg["rms_norm_eps"]) * _silu(z)
    return _mm(o.reshape(t, vd), p["out_proj"], quant)


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def _layer(cfg_items, i, p, x, quant, fault):
    """Block ``i`` over (T, D) float32."""
    cfg = dict(cfg_items)
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    eps = cfg["rms_norm_eps"]
    mixer = _full if layer_kinds(cfg)[i] == "full" else _linear

    def swiglu(h):
        a = _mm(h, p["d_gate"], quant)
        return _mm(_silu(a) * _mm(h, p["d_up"], quant), p["d_down"], quant)

    if fault == "pre_norm":
        x = x + mixer(p, _rmsnorm(x, p["ln1_w"], eps), cfg, quant, fault)
        return x + swiglu(_rmsnorm(x, p["ln2_w"], eps))
    x = x + _rmsnorm(mixer(p, x, cfg, quant, fault), p["ln1_w"], eps)
    return x + _rmsnorm(swiglu(x), p["ln2_w"], eps)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(norm, p, x, eps, quant, fault):
    if fault != "no_final_norm":
        x = _rmsnorm(x, norm["w"].astype(jnp.float32), eps)
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
    return _head(unit("norm"), unit("head"), x, cfg["rms_norm_eps"], quant,
                 fault)


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
