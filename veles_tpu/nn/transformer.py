"""TransformerBlock: attention + FFN + layernorm as ONE forward unit.

New capability vs the reference (sequence models there were Znicz
RNN/LSTM, SURVEY.md §5.7). Fusing the whole pre-LN residual block into
one shape-preserving unit is deliberate TPU-first design: a stack of
``{"type": "transformer_block", ...} * N`` layers is exactly the
"contiguous identical shape-preserving run" that TrainStep's pipeline
stage-grouper consumes (parallel/pipeline.plan_pipeline), so the same
model pipelines over ``{'pipeline': P}`` with no model changes — and
the attention core routes through the shared per-shape chooser
(flash / ring / Ulysses / fused-XLA, nn/attention.attention_core).

Block (pre-LN, GPT-style):
    h = x + W_o · attn(LN1(x))
    y = h + W2 · gelu(W1 · LN2(h))
"""

from __future__ import annotations

from typing import Dict

import numpy

from ..config import root
from ..memory import Array
from .. import prng
from .nn_units import ForwardBase, GradientDescentBase, matches
from .attention import attention_core, device_mesh


def _layernorm(np_mod, x, g, b, eps=1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / np_mod.sqrt(var + eps) * g + b


def _gelu(np_mod, x):
    # tanh approximation — identical formula on both jnp and numpy
    c = numpy.sqrt(2.0 / numpy.pi).astype("float32")
    return 0.5 * x * (1.0 + np_mod.tanh(c * (x + 0.044715 * x ** 3)))


def _rmsnorm(np_mod, x, g, eps=1e-5):
    return x / np_mod.sqrt((x ** 2).mean(axis=-1, keepdims=True)
                           + eps) * g


def _silu(np_mod, x):
    return x / (1.0 + np_mod.exp(-x))


def block_norm(np_mod, block, p, x, which: str):
    """The block's normalization sub-layer (``which``: "ln1"/"ln2") —
    one definition shared by training (apply/numpy_apply) and the
    KV-cached sampler so the two cannot drift. norm="rms" drops the
    mean-centering and the bias (llama convention)."""
    if getattr(block, "norm", "layer") == "rms":
        return _rmsnorm(np_mod, x, p[which + "_g"])
    return _layernorm(np_mod, x, p[which + "_g"], p[which + "_b"])


def block_ffn(np_mod, block, p, x, prec=None, tp_axis=None):
    """The block's FFN sub-layer, shared the same way. ffn="swiglu":
    W2·(silu(W1 x) ⊙ W3 x), no biases (llama convention); default
    GELU: W2·gelu(W1 x + b1) + b2.

    ``tp_axis`` names a tensor-parallel mesh axis the caller is
    shard_mapped over (serving engine, ``--serve-tp``): w1/w3 are then
    column shards, b1 a hidden shard and w2 a row shard, so the
    partial W2 products psum into the full output — with b2 kept
    REPLICATED and added once AFTER the psum (a sharded b2 would be
    N-counted). ``tp_axis=None`` is bit-identical to the pre-TP
    path."""
    if np_mod is numpy:
        def dot(a, b):
            return a @ b
    else:
        def dot(a, b):
            return np_mod.dot(a, b, precision=prec)
    if getattr(block, "ffn", "gelu") == "swiglu":
        out = dot(_silu(np_mod, dot(x, p["w1"])) * dot(x, p["w3"]),
                  p["w2"])
        if tp_axis is not None:
            from jax import lax
            out = lax.psum(out, tp_axis)
        return out
    out = dot(_gelu(np_mod, dot(x, p["w1"]) + p["b1"]), p["w2"])
    if tp_axis is not None:
        from jax import lax
        out = lax.psum(out, tp_axis)
    return out + p["b2"]


def _rope(np_mod, x, base=10000.0, positions=None):
    """Rotary position embedding on (B, T, H, Dh), HALF-SPLIT pairing
    (GPT-NeoX convention: feature j rotates with j+half — NOT the
    interleaved even/odd RoFormer layout; the two are not weight-
    compatible). Relative by construction, so it needs no learned table
    and no length cap; applied to the GLOBAL q/k before attention_core,
    it stays correct under every attention path (single-chip, flash,
    ring, Ulysses). ``positions`` (B, T), traced or not: each row's own
    positions (a decode step's rows stand at different ones); None is
    0..T-1 for every row."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = (base ** (-numpy.arange(half, dtype="float32") / half))
    if positions is None:
        ang = np_mod.asarray(
            numpy.arange(t, dtype="float32")[:, None] * inv[None, :])
        cos, sin = np_mod.cos(ang), np_mod.sin(ang)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        ang = (np_mod.asarray(positions).astype("float32")[..., None]
               * np_mod.asarray(inv))
        cos = np_mod.cos(ang)[:, :, None, :]
        sin = np_mod.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot1 = x1 * cos - x2 * sin
    rot2 = x1 * sin + x2 * cos
    if 2 * half == hd:
        return np_mod.concatenate([rot1, rot2], axis=-1)
    return np_mod.concatenate([rot1, rot2, x[..., 2 * half:]], axis=-1)


class TransformerBlock(ForwardBase):
    """(B, T, D) → (B, T, D); the canonical pipelineable stage."""

    MAPPING = "transformer_block"
    PARAMETERIZED = True
    hide_from_registry = False
    PARAM_NAMES = ("wq", "wk", "wv", "wo", "w1", "b1", "w2", "b2",
                   "w3", "ln1_g", "ln1_b", "ln2_g", "ln2_b")

    def __init__(self, workflow, n_heads=4, ffn_hidden=0, causal=True,
                 rope=False, n_kv_heads=None, window=None,
                 norm="layer", ffn="gelu", rope_base=10000.0,
                 **kwargs):
        super().__init__(workflow, **kwargs)
        self.n_heads = int(n_heads)
        #: "layer" (GPT: centered, with bias) | "rms" (llama: scale
        #: only); "gelu" (W1+b1 → gelu → W2+b2) | "swiglu" (llama:
        #: W2·(silu(W1 x) ⊙ W3 x), no biases)
        if norm not in ("layer", "rms"):
            raise ValueError("norm must be 'layer' or 'rms'")
        if ffn not in ("gelu", "swiglu"):
            raise ValueError("ffn must be 'gelu' or 'swiglu'")
        self.norm = norm
        self.ffn = ffn
        #: sliding-window attention span (self + window-1 predecessors,
        #: Mistral convention); unset = full attention. Causal only.
        #: The attribute only exists when set, so full-attention
        #: exports carry no null config key.
        if window is not None:
            if int(window) < 1:
                raise ValueError("window must be a positive span, got "
                                 "%r" % (window,))
            if not causal:
                raise ValueError("window requires causal=True")
            self.window = int(window)
        #: grouped-query attention: n_kv_heads < n_heads shares each K/V
        #: head across n_heads/n_kv_heads query heads — the KV cache
        #: (and wk/wv) shrink by that factor; None = classic MHA
        self.n_kv_heads = int(n_kv_heads) if n_kv_heads else self.n_heads
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads %d not divisible by n_kv_heads %d"
                             % (self.n_heads, self.n_kv_heads))
        self.ffn_hidden = int(ffn_hidden)
        self.causal = causal
        #: rotary position embedding on q/k — position information with
        #: no learned table and no trained-length cap (the alternative
        #: to a pos_embedding unit ahead of the stack)
        self.rope = bool(rope)
        #: RoPE frequency base (theta); raising it stretches the
        #: positional wavelengths for longer contexts (the llama-2/3
        #: long-context lever). Only meaningful with rope=True.
        self.rope_base = float(rope_base)
        self.mesh = None
        self.weights_stddev = kwargs.get("weights_stddev", None)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def create_params(self, rng: prng.RandomGenerator) -> Dict[str, Array]:
        d = self.input.shape[-1]
        if d % self.n_heads:
            raise ValueError("model dim %d not divisible by %d heads"
                             % (d, self.n_heads))
        f = self.ffn_hidden or 4 * d
        stddev = self.weights_stddev or (1.0 / numpy.sqrt(d))
        dtype = root.common.engine.precision_type

        def mk(name, shape, scale):
            w = numpy.zeros(shape, dtype=dtype)
            prng.get("%s.%s" % (self.name, name)).fill_normal(w, scale)
            return Array(w, name="%s.%s" % (self.name, name))

        ones = numpy.ones((d,), dtype=dtype)
        zeros = numpy.zeros((d,), dtype=dtype)
        kv_d = (d // self.n_heads) * self.n_kv_heads
        params = {
            "wq": mk("wq", (d, d), stddev),
            "wk": mk("wk", (d, kv_d), stddev),
            "wv": mk("wv", (d, kv_d), stddev),
            "wo": mk("wo", (d, d), stddev),
            "w1": mk("w1", (d, f), stddev),
            "w2": mk("w2", (f, d), 1.0 / numpy.sqrt(f)),
            "ln1_g": Array(ones.copy(), name=self.name + ".ln1_g"),
            "ln2_g": Array(ones.copy(), name=self.name + ".ln2_g"),
        }
        if self.ffn == "swiglu":
            params["w3"] = mk("w3", (d, f), stddev)
        else:
            params["b1"] = Array(numpy.zeros((f,), dtype=dtype),
                                 name=self.name + ".b1")
            params["b2"] = Array(zeros.copy(), name=self.name + ".b2")
        if self.norm == "layer":
            params["ln1_b"] = Array(zeros.copy(),
                                    name=self.name + ".ln1_b")
            params["ln2_b"] = Array(zeros.copy(),
                                    name=self.name + ".ln2_b")
        return params

    def initialize(self, device=None, **kwargs):
        res = super().initialize(device=device, **kwargs)
        if res:
            return res
        self.mesh = device_mesh(device)
        return None

    def apply(self, params, x, *, train=False, rng=None):
        import jax.numpy as jnp
        from jax import named_scope
        from ..ops import matmul_precision
        prec = matmul_precision()
        b, t, d = x.shape
        h = self.n_heads
        kv = getattr(self, "n_kv_heads", h)   # absent in old snapshots
        hd = d // h

        # the scopes name the block's device work in a profiler capture
        # (`veles_tpu trace self-time`): trace-time only, static strings
        with named_scope(self.name):
            with named_scope("norm1"):
                a_in = block_norm(jnp, self, params, x, "ln1")
            with named_scope("attn_qkv"):
                q = jnp.dot(a_in, params["wq"],
                            precision=prec).reshape(b, t, h, hd)
                k = jnp.dot(a_in, params["wk"],
                            precision=prec).reshape(b, t, kv, hd)
                v = jnp.dot(a_in, params["wv"],
                            precision=prec).reshape(b, t, kv, hd)
            if getattr(self, "rope", False):   # absent in pre-rope exports
                base = getattr(self, 'rope_base', 10000.0)
                with named_scope("rope"):
                    q, k = _rope(jnp, q, base), _rope(jnp, k, base)
            with named_scope("attn"):
                o = attention_core(q, k, v, causal=self.causal,
                                   mesh=self.mesh, n_heads=h,
                                   window=getattr(self, "window", None)
                                   ).reshape(b, t, d)
            with named_scope("attn_out"):
                x = x + jnp.dot(o, params["wo"], precision=prec)
            with named_scope("norm2"):
                f_in = block_norm(jnp, self, params, x, "ln2")
            with named_scope("ffn"):
                return x + block_ffn(jnp, self, params, f_in, prec)

    def numpy_apply(self, params, x):
        x = numpy.asarray(x, dtype=numpy.float32)
        b, t, d = x.shape
        h = self.n_heads
        kv = getattr(self, "n_kv_heads", h)
        hd = d // h
        a_in = block_norm(numpy, self, params, x, "ln1")

        q = (a_in @ params["wq"]).reshape(b, t, h, hd)
        k = (a_in @ params["wk"]).reshape(b, t, kv, hd)
        v = (a_in @ params["wv"]).reshape(b, t, kv, hd)
        if getattr(self, "rope", False):   # absent in pre-rope exports
            base = getattr(self, 'rope_base', 10000.0)
            q, k = _rope(numpy, q, base), _rope(numpy, k, base)
        from .attention import expand_kv
        k = expand_kv(numpy, k, h)
        v = expand_kv(numpy, v, h)
        s = numpy.einsum("bqhd,bkhd->bhqk", q, k) / numpy.sqrt(hd)
        if self.causal:
            rel = numpy.arange(t)[:, None] - numpy.arange(t)[None, :]
            mask = rel >= 0
            win = getattr(self, "window", None)
            if win:
                mask = mask & (rel < win)
            s = numpy.where(mask[None, None], s, -1e30)
        s = s - s.max(axis=-1, keepdims=True)
        p = numpy.exp(s)
        p /= p.sum(axis=-1, keepdims=True)
        o = numpy.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, d)
        x = x + o @ params["wo"]
        f_in = block_norm(numpy, self, params, x, "ln2")
        return (x + block_ffn(numpy, self, params, f_in)).astype(
            numpy.float32)


@matches(TransformerBlock)
class GDTransformerBlock(GradientDescentBase):
    MAPPING = "gd_transformer_block"
    hide_from_registry = False


class PositionalEmbedding(ForwardBase):
    """(B, T, D) → (B, T, D): adds a learned per-position table.
    Transformer blocks are permutation-equivariant; position-dependent
    tasks need this (or a rotary variant) ahead of the stack. Shape-
    preserving, so it sits in `pre` when the block run pipelines."""

    MAPPING = "pos_embedding"
    PARAMETERIZED = True
    hide_from_registry = False
    PARAM_NAMES = ("table",)

    def __init__(self, workflow, stddev=0.02, **kwargs):
        super().__init__(workflow, **kwargs)
        self.stddev = float(stddev)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def create_params(self, rng: prng.RandomGenerator) -> Dict[str, Array]:
        t, d = self.input.shape[1], self.input.shape[2]
        w = numpy.zeros((t, d), dtype=root.common.engine.precision_type)
        prng.get(self.name + ".table").fill_normal(w, self.stddev)
        return {"table": Array(w, name=self.name + ".table")}

    def apply(self, params, x, *, train=False, rng=None):
        return x + params["table"][None, :x.shape[1]]

    def numpy_apply(self, params, x):
        return (numpy.asarray(x, dtype=numpy.float32)
                + params["table"][None, :x.shape[1]])


@matches(PositionalEmbedding)
class GDPositionalEmbedding(GradientDescentBase):
    MAPPING = "gd_pos_embedding"
    hide_from_registry = False


class Embedding(ForwardBase):
    """(B, T) int tokens → (B, T, D) vectors: the text-model stem.
    The lookup is a device-side take, so the fused step's gradient is
    the usual scatter-add into the table (jax.grad of jnp.take)."""

    MAPPING = "embedding"
    PARAMETERIZED = True
    hide_from_registry = False
    PARAM_NAMES = ("table",)

    def __init__(self, workflow, vocab_size: int, dim: int,
                 stddev: float = 0.02, **kwargs):
        super().__init__(workflow, **kwargs)
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.stddev = float(stddev)

    def output_shape_for(self, input_shape):
        return tuple(input_shape) + (self.dim,)

    def create_params(self, rng: prng.RandomGenerator) -> Dict[str, Array]:
        w = numpy.zeros((self.vocab_size, self.dim),
                        dtype=root.common.engine.precision_type)
        prng.get(self.name + ".table").fill_normal(w, self.stddev)
        return {"table": Array(w, name=self.name + ".table")}

    def apply(self, params, x, *, train=False, rng=None):
        import jax.numpy as jnp
        # mode="clip" made explicit: out-of-range ids clamp to the edge
        # rows, and ALL runtimes (oracle, C++ twin) mirror exactly that
        # — XLA cannot raise on device, so clip is the one semantic
        # every path can share
        from jax import named_scope
        with named_scope(self.name), named_scope("embed"):
            return jnp.take(params["table"], x.astype(jnp.int32),
                            axis=0, mode="clip")

    def numpy_apply(self, params, x):
        ids = numpy.clip(numpy.asarray(x, dtype=numpy.int64), 0,
                         params["table"].shape[0] - 1)
        return params["table"][ids]


@matches(Embedding)
class GDEmbedding(GradientDescentBase):
    MAPPING = "gd_embedding"
    hide_from_registry = False


class LMHead(ForwardBase):
    """(B, T, D) → (B, T, V) per-position logits — the language-model
    output head, paired with ``loss_function="softmax_seq"`` (per-token
    cross-entropy on shifted targets)."""

    MAPPING = "lm_head"
    PARAMETERIZED = True
    hide_from_registry = False

    def __init__(self, workflow, vocab_size: int, **kwargs):
        super().__init__(workflow, **kwargs)
        self.vocab_size = int(vocab_size)
        self.weights_stddev = kwargs.get("weights_stddev", None)

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.vocab_size,)

    def create_params(self, rng: prng.RandomGenerator) -> Dict[str, Array]:
        d = self.input.shape[-1]
        stddev = self.weights_stddev or (1.0 / numpy.sqrt(d))
        dtype = root.common.engine.precision_type
        w = numpy.zeros((d, self.vocab_size), dtype=dtype)
        prng.get(self.name + ".weights").fill_normal(w, stddev)
        return {"weights": Array(w, name=self.name + ".weights"),
                "bias": Array(numpy.zeros((self.vocab_size,),
                                          dtype=dtype),
                              name=self.name + ".bias")}

    def apply(self, params, x, *, train=False, rng=None):
        import jax.numpy as jnp
        from jax import named_scope
        from ..ops import matmul_precision
        with named_scope(self.name), named_scope("head"):
            return (jnp.dot(x, params["weights"],
                            precision=matmul_precision())
                    + params["bias"])

    def numpy_apply(self, params, x):
        return (numpy.asarray(x, dtype=numpy.float32)
                @ params["weights"] + params["bias"]).astype(
            numpy.float32)


@matches(LMHead)
class GDLMHead(GradientDescentBase):
    MAPPING = "gd_lm_head"
    hide_from_registry = False


class MeanPool(ForwardBase):
    """(B, T, D) → (B, D): mean over the sequence axis (classification
    head plumbing for sequence stacks)."""

    MAPPING = "mean_pool"
    hide_from_registry = False

    def output_shape_for(self, input_shape):
        return (input_shape[0],) + tuple(input_shape[2:])

    def apply(self, params, x, *, train=False, rng=None):
        return x.mean(axis=1)

    def numpy_apply(self, params, x):
        return numpy.asarray(x, dtype=numpy.float32).mean(axis=1)
