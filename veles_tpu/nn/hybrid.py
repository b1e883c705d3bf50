"""HybridBlock: a pre-norm residual block that is told its mixer.

    h   = x + mixer(norm1(x))
    out = h + experts(norm2(h))

``mixer`` is ``"attention"`` (gated softmax attention: a sigmoid output
gate beside the query, RMS norms on q and k, rotary on a leading part of
the head, any head size the flash kernel takes) or ``"delta_rule"`` (the
gated delta rule of ``nn/delta_rule.py``); the feed-forward is the sparse
experts with a shared one of ``nn/experts.py``, told which experts it
holds. The norms are zero-centred: ``x / rms(x) * (1 + w)``, ``w`` nought
at the start.

One chip's share of a layer: with ``experts_held`` the unit holds some of
the ``n_experts`` that its router scores, and what the others would add
is left out. The loss is then the partial layer's and every gradient that
loss's.

Training only: the unit has ``apply`` (traced by ``TrainStep`` and
differentiated by ``jax.grad`` like every unit) and no per-slot state
for the serving engines yet.

Named scopes go mechanism first, block second (``delta_rule/blk2``,
``experts/blk0``): in a train step jax puts ``forward`` or the backward
pass's mark in front, and a reader that cuts the stack at two levels
(``chipbench``'s ``scope_ms``) then sees ``forward/delta_rule`` and
``backward/delta_rule``, every block's together; ``trace self-time`` at
three levels splits them by block. That order serves the reader and is
the wrong way round for everything else (a lower layer's name in front of
its block's): it turns to ``blk2/delta_rule`` with ``delta_rule.remat``'s
going, once the reader cuts deeper (ROADMAP S0).
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


def zero_centred_norm(x, w, eps):
    """``x / rms(x) * (1 + w)`` in float32, returned in x's type."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def partial_rope(np_mod, x, base, rotary_dims):
    """Half-split rotary on the first ``rotary_dims`` features of each
    head of x (B, T, H, Dh); the others pass untouched."""
    if rotary_dims >= x.shape[-1]:
        return _rope(np_mod, x, base)
    return np_mod.concatenate(
        [_rope(np_mod, x[..., :rotary_dims], base), x[..., rotary_dims:]],
        axis=-1)


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


class HybridBlock(ForwardBase):
    """(B, T, D) -> (B, T, D): ``mixer`` then sparse experts, pre-norm."""

    MAPPING = "hybrid_block"
    PARAMETERIZED = True
    hide_from_registry = False
    ATTENTION = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
    DELTA_RULE = ("in_qkvz", "in_ba", "conv", "A_log", "dt_bias",
                  "gnorm_w", "out_proj")
    EXPERTS = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
               "s_down", "s_mix")
    PARAM_NAMES = ("ln1_w", "ln2_w") + ATTENTION + DELTA_RULE + EXPERTS
    #: leaves that ``--mixed-precision`` leaves in float32: the router
    #: (its softmax decides which experts a token reaches), the decay's
    #: two (``g`` is float32 as published), and the held experts'
    #: matrices, which the grouped loop casts a block at a time and whose
    #: gradients it sums in float32
    AMP_FLOAT32 = ("router", "A_log", "dt_bias", "e_gate", "e_up",
                   "e_down")

    def __init__(self, workflow, mixer="attention", n_heads=4,
                 n_kv_heads=None, head_dim=0, rope_base=10000.0,
                 rotary_factor=1.0, n_key_heads=2, n_value_heads=4,
                 key_dim=16, value_dim=16, conv_taps=4, n_experts=8,
                 experts_held=None, top_k=2, expert_hidden=0,
                 shared_hidden=0, eps=1e-6, **kwargs):
        super().__init__(workflow, **kwargs)
        if mixer not in ("attention", "delta_rule"):
            raise ValueError("mixer must be 'attention' or 'delta_rule'")
        self.mixer = mixer
        self.n_heads = int(n_heads)
        self.n_kv_heads = int(n_kv_heads) if n_kv_heads else self.n_heads
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads %d not divisible by n_kv_heads %d"
                             % (self.n_heads, self.n_kv_heads))
        self.head_dim = int(head_dim)
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
        self.eps = float(eps)
        self.mesh = None
        self.weights_stddev = kwargs.get("weights_stddev", None)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def step_taps(self):
        """The accumulator keys this unit's ``apply`` emits in a train
        step (telemetry/steptaps.py)."""
        from .experts import tap_keys
        return tap_keys()

    def leaf_shapes(self, d):
        """{leaf: (shape, how it starts)}: "normal", "zeros", "ones", or
        "log_uniform16" (the log of uniform(0, 16), ``A_log``)."""
        f = self.expert_hidden or 4 * d
        sf = self.shared_hidden or f
        held = len(self.experts_held)
        shapes = {"ln1_w": ((d,), "zeros"), "ln2_w": ((d,), "zeros")}
        if self.mixer == "attention":
            hd = self.head_dim or d // self.n_heads
            q_d, kv_d = self.n_heads * hd, self.n_kv_heads * hd
            shapes.update(wq=((d, 2 * q_d), "normal"),
                          wk=((d, kv_d), "normal"),
                          wv=((d, kv_d), "normal"),
                          wo=((q_d, d), "normal"),
                          q_norm=((hd,), "zeros"), k_norm=((hd,), "zeros"))
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
        shapes.update(
            router=((d, self.n_experts), "normal"),
            e_gate=((held, d, f), "normal"), e_up=((held, d, f), "normal"),
            e_down=((held, f, d), "normal"),
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
        """The named scope of one part of the block: mechanism first,
        block second (this module's docstring says why)."""
        from jax import named_scope
        stack = contextlib.ExitStack()
        stack.enter_context(named_scope(part))
        stack.enter_context(named_scope(self.name))
        return stack

    def apply(self, params, x, *, train=False, rng=None):
        from ..ops import matmul_precision
        from .delta_rule import delta_rule_mixer
        from .experts import sparse_experts
        prec = matmul_precision()
        scope = self._scope
        with scope("norm1"):
            a_in = zero_centred_norm(x, params["ln1_w"], self.eps)
        if self.mixer == "attention":
            hd = self.head_dim or x.shape[-1] // self.n_heads
            mixed = gated_attention_mixer(
                params, a_in, n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=hd,
                rope_base=self.rope_base,
                rotary_dims=int(round(hd * self.rotary_factor)),
                eps=self.eps, precision=prec, mesh=self.mesh, scope=scope)
        else:
            mixed = delta_rule_mixer(
                params, a_in, n_k=self.n_key_heads, n_v=self.n_value_heads,
                dk=self.key_dim, dv=self.value_dim, eps=self.eps,
                precision=prec, scope=scope)
        with scope("mixer_out"):
            x = x + mixed.astype(x.dtype)
        with scope("norm2"):
            f_in = zero_centred_norm(x, params["ln2_w"], self.eps)
        local_of = numpy.full((self.n_experts,), -1, numpy.int32)
        local_of[list(self.experts_held)] = numpy.arange(
            len(self.experts_held), dtype=numpy.int32)
        y = sparse_experts(params, f_in, top_k=self.top_k,
                           local_of=local_of,
                           n_held=len(self.experts_held), precision=prec,
                           scope=scope)
        with scope("experts_out"):
            return x + y


@matches(HybridBlock)
class GDHybridBlock(GradientDescentBase):
    MAPPING = "gd_hybrid_block"
    hide_from_registry = False
