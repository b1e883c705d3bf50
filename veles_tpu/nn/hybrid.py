"""HybridBlock: a residual block that is told its mixer and where its
norms stand.

    h   = x + mixer(norm1(x))              (norm="pre")
    out = h + ffn(norm2(h))

    h   = x + norm1(mixer(x))              (norm="post", Olmo's)
    out = h + norm2(ffn(h))

``mixer`` is ``"attention"`` (gated softmax attention: a sigmoid output
gate beside the query, RMS norms on q and k, rotary on a leading part of
the head, any head size the flash kernel takes), ``"delta_rule"`` (the
gated delta rule of ``nn/delta_rule.py``) or ``"softmax"`` (plain softmax
attention told, layer by layer, its key and its value width, its KV
heads, its rotary part and base, its window, a learned sink a head and a
scale on the values). The feed-forward is the sparse experts of
``nn/experts.py`` (``ffn="experts"``: told which experts it holds, its
router softmax or sigmoid, with or without a shared expert) or a dense
SwiGLU (``ffn="dense"``). Before the sublayers the norms are
zero-centred, ``x / rms(x) * (1 + w)`` with ``w`` nought at the start;
after them plain, ``x / rms(x) * w`` with ``w`` one at the start. The
softmax mixer may also normalise its whole q and its whole k projection
before they are split into heads (``qk_norm``) and may leave the rotary
out (``rotary_factor`` 0); the delta rule's ``beta`` may reach 2
(``beta_scale``). ``FinalNorm`` is the plain norm before the head.

One chip's share of a layer: with ``experts_held`` the unit holds some of
the ``n_experts`` that its router scores, and what the others would add
is left out. The loss is then the partial layer's and every gradient that
loss's.

Training: the unit has ``apply`` (traced by ``TrainStep`` and
differentiated by ``jax.grad`` like every unit). Serving: a block whose
mixer is ``"softmax"`` or ``"delta_rule"`` also states its own
``serve_prefill`` and its own one-position ``serve_step`` with a cache
argument, on the same ``_block`` as ``apply`` (one definition of the
equations); ``serving/engine.py`` asks ``cache_geometry`` what a slot
holds of the layer: pages of K and V rows; with a window, a ring of
``ring_positions`` rows addressed by position modulo its length; of a
delta-rule layer, its state and the convolution's tail, a row a slot,
whatever the context. The gated attention mixer keeps no per-slot state
yet and is not served.

Named scopes in ``apply`` go mechanism first, block second
(``delta_rule/blk2``, ``experts/blk0``): in a train step jax puts
``forward`` or the backward pass's mark in front, and a reader that cuts
the stack at two levels (``chipbench``'s ``scope_ms``) then sees
``forward/delta_rule`` and ``backward/delta_rule``, every block's
together; ``trace self-time`` at three levels splits them by block. That
order serves the reader and is the wrong way round for everything else (a
lower layer's name in front of its block's): it turns to
``blk2/delta_rule`` with ``delta_rule.remat``'s going, once the reader
cuts deeper (ROADMAP S0). The served programs have no such mark in front
and name the block first (``blk2/window_attn``), as the served
``TransformerBlock`` does.
"""

from __future__ import annotations

import contextlib
from typing import Dict

import numpy

from ..config import root
from ..memory import Array
from .. import prng
from .nn_units import ForwardBase, GradientDescentBase, matches
from .attention import attention_core, device_mesh
from .transformer import _rope


#: lanes of a TPU's vector registers: a cache row wider than this is
#: stored at the next multiple (``stored_width``)
LANES = 128


def stored_width(width):
    """The width a cache keeps rows of ``width`` features at: ``width``
    up to ``LANES``, beyond that the next multiple of ``LANES``, padded
    with noughts. A pool whose rows are 192 wide gets no tile-aligned
    layout on a TPU: XLA lays it out rows-minor and converts the whole
    pool to and fro in every step (12 ms of a 38 ms step, chip, PR 36)."""
    return width if width <= LANES else -(-width // LANES) * LANES


#: KV heads of a page a cache keeps as they are; beyond, a multiple of this
HEAD_TILE = 16


def stored_heads(heads):
    """The KV heads a page pool keeps rows of ``heads`` heads at: up to 8
    as they are, beyond that the next multiple of ``HEAD_TILE``, padded
    with noughts. A page of (16 positions, 30 heads, 128) gets a layout
    on a TPU that puts the positions second-minor (30 heads fill no tile
    of 16 rows): the step's gather wants the heads there, and XLA
    converts both pools whole, to and fro, in every step (four 755 MB
    copies at ``olmo-hybrid-7b``'s widths, compiled for a described
    v5e, PR 40). At 32 the layout is the rows' own."""
    if heads <= 8 or heads % HEAD_TILE == 0:
        return heads
    return -(-heads // HEAD_TILE) * HEAD_TILE


def _pad_heads(x, heads):
    """x (..., KV, D) padded with nought heads to ``heads``."""
    import jax.numpy as jnp
    pad = heads - x.shape[-2]
    return x if pad <= 0 else jnp.pad(
        x, ((0, 0),) * (x.ndim - 2) + ((0, pad), (0, 0)))


def _pad_last(x, width):
    import jax.numpy as jnp
    pad = width - x.shape[-1]
    return x if pad <= 0 else jnp.pad(
        x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def _scopes(*names):
    """Nested named scopes, outermost first, as one context."""
    from jax import named_scope
    stack = contextlib.ExitStack()
    for name in names:
        stack.enter_context(named_scope(name))
    return stack


#: what a served decode step counts of its delta-rule layers: live rows x
#: such layers, the states it read and wrote (a ``steptaps`` counter)
STATE_ROWS = "veles_serving_state_rows_total"


def rms_norm(x, w, eps):
    """``x / rms(x) * w`` in float32, returned in x's type."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def zero_centred_norm(x, w, eps):
    """``x / rms(x) * (1 + w)`` in float32, returned in x's type."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def partial_rope(np_mod, x, base, rotary_dims, positions=None):
    """Half-split rotary on the first ``rotary_dims`` features of each
    head of x (B, T, H, Dh); the others pass untouched. ``positions``
    (B, T): each row's own positions (a decode step); None is 0..T-1."""
    if rotary_dims >= x.shape[-1]:
        return _rope(np_mod, x, base, positions)
    return np_mod.concatenate(
        [_rope(np_mod, x[..., :rotary_dims], base, positions),
         x[..., rotary_dims:]], axis=-1)


def gated_attention_mixer(p, x, *, n_heads, n_kv_heads, head_dim,
                          rope_base, rotary_dims, eps, precision, mesh,
                          scope):
    """(B, T, D) -> (B, T, D) on ``wq`` (D, H x 2 Dh: each head's query
    and gate), ``wk``, ``wv`` (D, KV x Dh), ``wo`` (H x Dh, D),
    ``q_norm`` and ``k_norm`` (Dh)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    h, kv, hd = n_heads, n_kv_heads, head_dim
    with scope("attn_qkv"):
        qg = jnp.dot(x, p["wq"], precision=precision).reshape(
            b, t, h, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = jnp.dot(x, p["wk"], precision=precision).reshape(b, t, kv, hd)
        v = jnp.dot(x, p["wv"], precision=precision).reshape(b, t, kv, hd)
        q = zero_centred_norm(q, p["q_norm"], eps)
        k = zero_centred_norm(k, p["k_norm"], eps)
    with scope("rope"):
        # the tables are float32; q and k go on in the type they came in
        q = partial_rope(jnp, q.astype(jnp.float32), rope_base,
                         rotary_dims).astype(x.dtype)
        k = partial_rope(jnp, k.astype(jnp.float32), rope_base,
                         rotary_dims).astype(x.dtype)
    with scope("attn"):
        o = attention_core(q, k, v, causal=True, mesh=mesh, n_heads=h)
    with scope("attn_gate"):
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(x.dtype).reshape(
            b, t, h * hd)
    with scope("attn_out"):
        return jnp.dot(o, p["wo"], precision=precision)


def softmax_mixer(p, x, attend, *, n_heads, n_kv_heads, head_dim,
                  v_head_dim, rope_base, rotary_dims, value_scale,
                  precision, scope, attn_scope, positions=None,
                  qk_norm_eps=None):
    """(B, T, D) -> ((B, T, D), k, v) on ``wq`` (D, H x Dk), ``wk``
    (D, KV x Dk), ``wv`` (D, KV x Dv) and ``wo`` (H x Dv, D): the keys
    rotated at ``positions`` (none where ``rotary_dims`` is 0) and the
    values times ``value_scale``, as the cache holds them. With
    ``qk_norm_eps`` the whole q and the whole k projection are
    normalised (``rms_norm`` on ``q_norm`` (H x Dk) and ``k_norm``
    (KV x Dk)) before the split into heads. ``attend(q, k, v)`` is the
    softmax over whatever the caller may see (the sequence itself, or a
    slot's cache with the new row in it) and returns (B, T, H, Dv); it
    runs under the scope ``attn_scope``."""
    import jax.numpy as jnp
    b, t, _ = x.shape
    h, kv = n_heads, n_kv_heads
    with scope("attn_qkv"):
        def projected(name, heads):
            y = jnp.dot(x, p["w" + name], precision=precision)
            if qk_norm_eps is not None:
                y = rms_norm(y, p[name + "_norm"], qk_norm_eps)
            return y.reshape(b, t, heads, head_dim)
        q, k = projected("q", h), projected("k", kv)
        v = jnp.dot(x, p["wv"], precision=precision).reshape(
            b, t, kv, v_head_dim)
        if value_scale != 1.0:
            v = (v.astype(jnp.float32) * value_scale).astype(x.dtype)
    if rotary_dims:
        with scope("rope"):
            q = partial_rope(jnp, q.astype(jnp.float32), rope_base,
                             rotary_dims, positions).astype(x.dtype)
            k = partial_rope(jnp, k.astype(jnp.float32), rope_base,
                             rotary_dims, positions).astype(x.dtype)
    with scope(attn_scope):
        o = attend(q, k, v).reshape(b, t, h * v_head_dim)
    with scope("attn_out"):
        return jnp.dot(o, p["wo"], precision=precision), k, v


def dense_swiglu(p, x, precision):
    """``down(silu(gate x) * up x)`` on ``d_gate``, ``d_up`` (D, F) and
    ``d_down`` (F, D)."""
    import jax
    import jax.numpy as jnp
    a = jnp.dot(x, p["d_gate"], precision=precision)
    return jnp.dot(a * jax.nn.sigmoid(a)
                   * jnp.dot(x, p["d_up"], precision=precision),
                   p["d_down"], precision=precision)


class HybridBlock(ForwardBase):
    """(B, T, D) -> (B, T, D): ``mixer`` then the feed-forward, with the
    norms before them (``norm="pre"``) or after them (``"post"``)."""

    MAPPING = "hybrid_block"
    PARAMETERIZED = True
    hide_from_registry = False
    ATTENTION = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "sink")
    DELTA_RULE = ("in_qkvz", "in_ba", "conv", "A_log", "dt_bias",
                  "gnorm_w", "out_proj")
    EXPERTS = ("router", "router_bias", "e_gate", "e_up", "e_down",
               "s_gate", "s_up", "s_down", "s_mix")
    DENSE = ("d_gate", "d_up", "d_down")
    PARAM_NAMES = (("ln1_w", "ln2_w") + ATTENTION + DELTA_RULE + EXPERTS
                   + DENSE)
    #: leaves that ``--mixed-precision`` leaves in float32: the router
    #: (its softmax decides which experts a token reaches), the decay's
    #: two (``g`` is float32 as published), and the held experts'
    #: matrices, which the grouped loop casts a block at a time and whose
    #: gradients it sums in float32
    AMP_FLOAT32 = ("router", "A_log", "dt_bias", "e_gate", "e_up",
                   "e_down")
    #: rows of one expert that one step of the served loops multiplies: a
    #: decode step's rows choose a held expert a few times each, a
    #: prefill's some hundred times
    STEP_BLOCK_ROWS, PREFILL_BLOCK_ROWS = 64, 256

    def __init__(self, workflow, mixer="attention", n_heads=4,
                 n_kv_heads=None, head_dim=0, rope_base=10000.0,
                 rotary_factor=1.0, n_key_heads=2, n_value_heads=4,
                 key_dim=16, value_dim=16, conv_taps=4, n_experts=8,
                 experts_held=None, top_k=2, expert_hidden=0,
                 shared_hidden=0, eps=1e-6, v_head_dim=0, window=0,
                 sink=False, value_scale=1.0, ffn="experts",
                 dense_hidden=0, router="softmax", shared_expert=True,
                 norm="pre", qk_norm=False, beta_scale=1.0, **kwargs):
        super().__init__(workflow, **kwargs)
        if mixer not in ("attention", "delta_rule", "softmax"):
            raise ValueError("mixer must be 'attention', 'delta_rule' or "
                             "'softmax'")
        if ffn not in ("experts", "dense"):
            raise ValueError("ffn must be 'experts' or 'dense'")
        if router not in ("softmax", "sigmoid"):
            raise ValueError("router must be 'softmax' or 'sigmoid'")
        if norm not in ("pre", "post"):
            raise ValueError("norm must be 'pre' or 'post'")
        #: where the norms stand, whether the softmax mixer normalises its
        #: whole q and k projections, and what the delta rule's sigmoid
        #: ``beta`` is multiplied by (2: it may reach 2)
        self.norm_place = norm
        self.qk_norm = bool(qk_norm)
        self.beta_scale = float(beta_scale)
        # (``router`` and ``sink`` are leaves' names, and a leaf is an
        # attribute of its unit)
        self.mixer, self.ffn, self.router_kind = mixer, ffn, router
        self.n_heads = int(n_heads)
        self.n_kv_heads = int(n_kv_heads) if n_kv_heads else self.n_heads
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads %d not divisible by n_kv_heads %d"
                             % (self.n_heads, self.n_kv_heads))
        self.head_dim = int(head_dim)
        #: the softmax mixer's value width a head (0: the key width), its
        #: window in positions (0: every earlier key), whether each head
        #: has a learned sink, and what its values are multiplied by
        self.v_head_dim = int(v_head_dim)
        self.window = int(window or 0)
        self.has_sink = bool(sink)
        self.value_scale = float(value_scale)
        self.rope_base = float(rope_base)
        self.rotary_factor = float(rotary_factor)
        self.n_key_heads, self.n_value_heads = int(n_key_heads), int(
            n_value_heads)
        if self.n_value_heads % self.n_key_heads:
            raise ValueError("n_value_heads %d not divisible by "
                             "n_key_heads %d" % (self.n_value_heads,
                                                 self.n_key_heads))
        self.key_dim, self.value_dim = int(key_dim), int(value_dim)
        self.conv_taps = int(conv_taps)
        #: the router's width: every expert of the layer, held or not
        self.n_experts = int(n_experts)
        #: ids of the experts whose matrices this unit holds; the others'
        #: part of the result is some other chip's
        self.experts_held = tuple(
            range(self.n_experts) if experts_held is None
            else (int(e) for e in experts_held))
        if len(set(self.experts_held)) != len(self.experts_held) or any(
                not 0 <= e < self.n_experts for e in self.experts_held):
            raise ValueError("experts_held must be distinct ids under "
                             "n_experts")
        self.top_k = int(top_k)
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must lie in 1..n_experts")
        self.expert_hidden = int(expert_hidden)
        self.shared_hidden = int(shared_hidden)
        self.shared_expert = bool(shared_expert)
        self.dense_hidden = int(dense_hidden)
        self.eps = float(eps)
        self.mesh = None
        self.weights_stddev = kwargs.get("weights_stddev", None)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def step_taps(self):
        """The accumulator keys this unit's ``apply`` emits in a train
        step (telemetry/steptaps.py)."""
        from .experts import tap_keys
        return tap_keys() if self.ffn == "experts" else []

    def _widths(self, d):
        """(key width, value width, rotary features) of a head."""
        hd = self.head_dim or d // self.n_heads
        return hd, self.v_head_dim or hd, int(round(hd * self.rotary_factor))

    def leaf_shapes(self, d):
        """{leaf: (shape, how it starts)}: "normal", "zeros", "ones", or
        "log_uniform16" (the log of uniform(0, 16), ``A_log``)."""
        f = self.expert_hidden or 4 * d
        sf = self.shared_hidden or f
        held = len(self.experts_held)
        # a norm after a sublayer is plain, its weight one at the start
        ln = "ones" if self.norm_place == "post" else "zeros"
        shapes = {"ln1_w": ((d,), ln), "ln2_w": ((d,), ln)}
        if self.mixer == "attention":
            hd = self.head_dim or d // self.n_heads
            q_d, kv_d = self.n_heads * hd, self.n_kv_heads * hd
            shapes.update(wq=((d, 2 * q_d), "normal"),
                          wk=((d, kv_d), "normal"),
                          wv=((d, kv_d), "normal"),
                          wo=((q_d, d), "normal"),
                          q_norm=((hd,), "zeros"), k_norm=((hd,), "zeros"))
        elif self.mixer == "softmax":
            hd, vd, _ = self._widths(d)
            shapes.update(wq=((d, self.n_heads * hd), "normal"),
                          wk=((d, self.n_kv_heads * hd), "normal"),
                          wv=((d, self.n_kv_heads * vd), "normal"),
                          wo=((self.n_heads * vd, d), "normal"))
            if self.has_sink:
                shapes.update(sink=((self.n_heads,), "normal"))
            if self.qk_norm:
                shapes.update(q_norm=((self.n_heads * hd,), "ones"),
                              k_norm=((self.n_kv_heads * hd,), "ones"))
        else:
            kd = self.n_key_heads * self.key_dim
            vd = self.n_value_heads * self.value_dim
            shapes.update(
                in_qkvz=((d, 2 * kd + 2 * vd), "normal"),
                in_ba=((d, 2 * self.n_value_heads), "normal"),
                conv=((2 * kd + vd, self.conv_taps), "normal"),
                A_log=((self.n_value_heads,), "log_uniform16"),
                dt_bias=((self.n_value_heads,), "ones"),
                gnorm_w=((self.value_dim,), "ones"),
                out_proj=((vd, d), "normal"))
        if self.ffn == "dense":
            df = self.dense_hidden or 4 * d
            shapes.update(d_gate=((d, df), "normal"),
                          d_up=((d, df), "normal"),
                          d_down=((df, d), "normal"))
            return shapes
        shapes.update(
            router=((d, self.n_experts), "normal"),
            e_gate=((held, d, f), "normal"), e_up=((held, d, f), "normal"),
            e_down=((held, f, d), "normal"))
        if self.router_kind == "sigmoid":
            # the selection's correction (``noaux_tc``): a small normal,
            # so that a path which forgets it shows
            shapes.update(router_bias=((self.n_experts,), "normal"))
        if self.shared_expert:
            shapes.update(
                s_gate=((d, sf), "normal"), s_up=((d, sf), "normal"),
                s_down=((sf, d), "normal"), s_mix=((d,), "normal"))
        return shapes

    def create_params(self, rng: prng.RandomGenerator) -> Dict[str, Array]:
        d = self.input.shape[-1]
        stddev = self.weights_stddev or (1.0 / numpy.sqrt(d))
        dtype = root.common.engine.precision_type
        params = {}
        for name, (shape, kind) in self.leaf_shapes(d).items():
            full = "%s.%s" % (self.name, name)
            w = numpy.zeros(shape, dtype=dtype)
            if kind == "normal":
                prng.get(full).fill_normal(w, stddev)
            elif kind == "ones":
                w += 1
            elif kind == "log_uniform16":
                # away from nought: the log of a draw of 1e-30 is no start
                w[...] = numpy.log(numpy.maximum(
                    16.0 * prng.get(full).rand(*shape), 1e-3))
            params[name] = Array(w, name=full)
        return params

    def initialize(self, device=None, **kwargs):
        res = super().initialize(device=device, **kwargs)
        if res:
            return res
        self.mesh = device_mesh(device)
        return None

    def _scope(self, part):
        """The named scope of one part of the block in ``apply``:
        mechanism first, block second (this module's docstring says
        why)."""
        return _scopes(part, self.name)

    @property
    def attn_scope(self) -> str:
        """The named scope of a softmax layer's attention read."""
        return "window_attn" if self.window else "full_attn"

    def _serve_scope(self, part):
        """The same in a served program: block first, as the served
        ``TransformerBlock`` names its parts."""
        return _scopes(self.name, part)

    def _block(self, params, x, scope, attend=None, positions=None,
               live=None, block_rows=None, carry=None):
        """The block's equations, once: ``apply`` and the two served
        forms differ in what ``attend`` sees, in the rows' positions, in
        which rows are ``live`` (the others reach no expert and leave a
        delta-rule state alone), in the delta rule's ``carry`` (a slot's
        state and tail, or (None, None) for a prompt) and in the scopes'
        order. Returns (out, k, v); k and v are the softmax mixer's rows
        or, served, the delta rule's state and tail, else None."""
        from ..ops import matmul_precision
        from .attention import blocked_causal_attention
        from .delta_rule import delta_rule_mixer
        from .experts import BLOCK_ROWS, sparse_experts
        prec = matmul_precision()
        post = self.norm_place == "post"
        k = v = None
        with scope("norm1"):
            a_in = x if post else zero_centred_norm(
                x, params["ln1_w"], self.eps)
        if self.mixer == "attention":
            hd = self.head_dim or x.shape[-1] // self.n_heads
            mixed = gated_attention_mixer(
                params, a_in, n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=hd,
                rope_base=self.rope_base,
                rotary_dims=int(round(hd * self.rotary_factor)),
                eps=self.eps, precision=prec, mesh=self.mesh, scope=scope)
        elif self.mixer == "softmax":
            hd, vd, rotary = self._widths(x.shape[-1])
            sink = params["sink"] if self.has_sink else None
            if attend is None:
                def attend(q, k, v):
                    return blocked_causal_attention(
                        q, k, v, window=self.window or None, sink=sink)
            mixed, k, v = softmax_mixer(
                params, a_in, attend, n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=hd, v_head_dim=vd,
                rope_base=self.rope_base, rotary_dims=rotary,
                value_scale=self.value_scale, precision=prec, scope=scope,
                attn_scope=self.attn_scope,
                positions=positions,
                qk_norm_eps=self.eps if self.qk_norm else None)
        else:
            mixed = delta_rule_mixer(
                params, a_in, n_k=self.n_key_heads, n_v=self.n_value_heads,
                dk=self.key_dim, dv=self.value_dim, eps=self.eps,
                precision=prec, scope=scope, beta_scale=self.beta_scale,
                carry=carry, live=None if carry is None
                else live.reshape(x.shape[:2]))
            if carry is not None:
                mixed, k, v = mixed
        if post:
            with scope("norm1"):
                mixed = rms_norm(mixed, params["ln1_w"], self.eps)
        with scope("mixer_out"):
            x = x + mixed.astype(x.dtype)
        with scope("norm2"):
            f_in = x if post else zero_centred_norm(
                x, params["ln2_w"], self.eps)
        if self.ffn == "dense":
            with scope("ffn"):
                y = dense_swiglu(params, f_in, prec)
                if not post:
                    return x + y, k, v
            with scope("norm2"):
                return x + rms_norm(y, params["ln2_w"], self.eps), k, v
        local_of = numpy.full((self.n_experts,), -1, numpy.int32)
        local_of[list(self.experts_held)] = numpy.arange(
            len(self.experts_held), dtype=numpy.int32)
        y = sparse_experts(params, f_in, top_k=self.top_k,
                           local_of=local_of,
                           n_held=len(self.experts_held), precision=prec,
                           scope=scope, block=block_rows or BLOCK_ROWS,
                           router=self.router_kind, shared=self.shared_expert,
                           live=live)
        if post:
            with scope("norm2"):
                y = rms_norm(y, params["ln2_w"], self.eps)
        with scope("experts_out"):
            return x + y, k, v

    def apply(self, params, x, *, train=False, rng=None):
        return self._block(params, x, self._scope)[0]

    # -- serving: what a slot holds of this layer, and its two programs ------
    def cache_geometry(self, d, page_size):
        """{"kv_heads", "k_dim", "v_dim", "ring"}: the K and V rows a
        position leaves in this layer's cache (at their
        ``stored_width``), and how many positions of
        them a slot keeps where the window bounds that (``ring``: the
        window's pages and one more, so that a prompt's last page can be
        written whole; 0: all of them, in pages). A delta-rule layer
        keeps {"state", "tail"} instead, a row of each a slot: the state
        (value heads, key width, value width) in float32 and the
        convolution's last ``conv_taps - 1`` inputs (positions,
        channels). Raises for a mixer that keeps neither."""
        from ..error import VelesError
        if self.mixer == "delta_rule":
            width = (2 * self.n_key_heads * self.key_dim
                     + self.n_value_heads * self.value_dim)
            return {"state": (self.n_value_heads, self.key_dim,
                              self.value_dim),
                    "tail": (self.conv_taps - 1, width)}
        if self.mixer != "softmax":
            raise VelesError(
                "%s (%s): a hybrid_block is served with mixer='softmax' "
                "or 'delta_rule' only; mixer=%r keeps no K/V rows and "
                "has no per-slot state for the engines yet" % (
                    self.name, type(self).__name__, self.mixer))
        hd, vd, _ = self._widths(d)
        pages = -(-self.window // page_size) + 1 if self.window else 0
        return {"kv_heads": stored_heads(self.n_kv_heads),
                "k_dim": stored_width(hd), "v_dim": stored_width(vd),
                "ring": pages * page_size}

    def serve_prefill(self, params, x, live=None):
        """(1, T, D) -> (out, k (1, T, KV, Dk), v (1, T, KV, Dv)): the
        whole prompt at once, its K and V rows as the cache keeps them.
        ``live`` (T,) bool: the rows that are the prompt's (the bucket's
        padding reaches no expert). A delta-rule layer returns (out,
        state (1, H, K, V), tail (1, taps - 1, C)) at the prompt's last
        position instead: the padding leaves both alone."""
        if self.mixer == "delta_rule":
            return self._block(params, x, self._serve_scope, live=live,
                               carry=(None, None))
        out, k, v = self._block(params, x, self._serve_scope, live=live,
                                block_rows=self.PREFILL_BLOCK_ROWS)
        heads = stored_heads(k.shape[-2])
        return (out, _pad_heads(_pad_last(k, stored_width(k.shape[-1])),
                                heads),
                _pad_heads(_pad_last(v, stored_width(v.shape[-1])), heads))

    def serve_step(self, params, x, cache_k, cache_v, pos, live,
                   attend=None):
        """One position a row. x (S, D); ``cache_k`` (S, Tc, KV, Dk) and
        ``cache_v`` (S, Tc, KV, Dv) each row's view of its cache: its
        pages in order, or its ring; ``pos`` (S,) the position each row
        stands at (its tokens already cached); ``live`` (S,) bool.
        Returns (out (S, D), cache_k, cache_v with the row written, k_new
        (S, KV, Dk), v_new (S, KV, Dv)). A delta-rule layer takes every
        slot's state (S, H, K, V) and tail (S, taps - 1, C) as
        ``cache_k`` and ``cache_v`` and returns them advanced a token
        where ``live`` and as they were elsewhere, with no new rows
        (None, None); it counts its live rows (``STATE_ROWS``).

        ``attend``: the caller's read of a full layer's cache in place of
        the view, the serving engine's read through the page table
        (``ContinuousEngine._paged_attend``) for a layer with no sink;
        ``cache_k`` and ``cache_v`` are then the rows the caller carries
        beside its pool and returns with this row's written, and
        ``attend(q, k, v, cache_k, cache_v, pos, scale=, name=)`` gets q
        and the new row at the cache's widths and heads (q's heads padded
        to as many groups as the cache keeps KV heads) and the name of the
        scope it runs under, for its kernel's device operation (which a
        profile files under that name and not under the scopes around
        it), and returns (o (S, 1, heads, Dv), cache_k, cache_v)."""
        import jax.numpy as jnp
        from .attention import masked_attention
        if self.mixer == "delta_rule":
            from ..telemetry import steptaps
            out, state, tail = self._block(
                params, x[:, None, :], self._serve_scope, live=live,
                carry=(cache_k, cache_v))
            steptaps.emit(steptaps.counter_key(STATE_ROWS),
                          jnp.sum(live.astype(jnp.float32)))
            return out[:, 0], state, tail, None, None
        box = {}

        def padded(q, k, v):
            # rows as the cache stores them: padded with noughts, which
            # add nothing to a score or to a value's sum; the nought heads
            # are no head's
            k_w, v_w = cache_k.shape[-1], cache_v.shape[-1]
            heads = cache_k.shape[-2]
            box["kn"] = _pad_heads(_pad_last(k[:, 0], k_w), heads)
            box["vn"] = _pad_heads(_pad_last(v[:, 0], v_w), heads)
            return _pad_last(q, k_w), heads

        def read(q, k, v):
            # through the caller, under the block's own scope, whose name
            # the kernel takes: the padded query heads attend to the
            # nought heads and are dropped with the padded lanes
            q, heads = padded(q, k, v)
            group = q.shape[-2] // k.shape[-2]
            o, box["k"], box["v"] = attend(
                _pad_heads(q, heads * group), box["kn"][:, None],
                box["vn"][:, None], cache_k, cache_v, pos,
                scale=1.0 / numpy.sqrt(k.shape[-1]), name=self.attn_scope)
            return o[:, :, :q.shape[-2], :v.shape[-1]].astype(v.dtype)

        s_rows, t_c = cache_k.shape[:2]
        ring = bool(self.window)
        at = pos % t_c if ring else jnp.clip(pos, 0, t_c - 1)
        rows = jnp.arange(s_rows)
        idx = jnp.arange(t_c, dtype=pos.dtype)[None, :]
        if ring:
            # index j of a ring holds the newest position congruent to j
            held = pos[:, None] - (pos[:, None] - idx) % t_c
            seen = (held >= 0) & (held > pos[:, None] - self.window)
        else:
            seen = idx <= pos[:, None]

        def view(q, k, v):
            q, heads = padded(q, k, v)
            box["k"] = cache_k.at[rows, at].set(box["kn"])
            box["v"] = cache_v.at[rows, at].set(box["vn"])
            keys, values = box["k"], box["v"]
            if heads != k.shape[-2]:
                keys = keys[:, :, :k.shape[-2]]
                values = values[:, :, :k.shape[-2]]
            o = masked_attention(
                q, keys, values, seen[:, None, :],
                params["sink"] if self.has_sink else None,
                scale=1.0 / numpy.sqrt(k.shape[-1]))
            return o[..., :v.shape[-1]]

        out, _, _ = self._block(
            params, x[:, None, :], self._serve_scope,
            attend=view if attend is None else read,
            positions=pos[:, None], live=live,
            block_rows=self.STEP_BLOCK_ROWS)
        return out[:, 0], box["k"], box["v"], box["kn"], box["vn"]


@matches(HybridBlock)
class GDHybridBlock(GradientDescentBase):
    MAPPING = "gd_hybrid_block"
    hide_from_registry = False


class FinalNorm(ForwardBase):
    """(B, T, D) -> (B, T, D): the plain RMS norm before the head,
    ``x / rms(x) * w`` with ``w`` one at the start. ``nn/sampling.py
    split_stack`` gives it a place between the blocks and the head for
    the engine that serves ``HybridBlock`` stacks (``stack["norm"]``);
    the other consumers of a stack refuse it."""

    MAPPING = "final_norm"
    PARAMETERIZED = True
    hide_from_registry = False
    PARAM_NAMES = ("w",)

    def __init__(self, workflow, eps=1e-6, **kwargs):
        super().__init__(workflow, **kwargs)
        self.eps = float(eps)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def create_params(self, rng: prng.RandomGenerator) -> Dict[str, Array]:
        d = self.input.shape[-1]
        w = numpy.ones((d,), dtype=root.common.engine.precision_type)
        return {"w": Array(w, name=self.name + ".w")}

    def apply(self, params, x, *, train=False, rng=None):
        from jax import named_scope
        with named_scope(self.name):
            return rms_norm(x, params["w"], self.eps)


@matches(FinalNorm)
class GDFinalNorm(GradientDescentBase):
    MAPPING = "gd_final_norm"
    hide_from_registry = False
