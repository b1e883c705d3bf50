"""KV-cached autoregressive sampling — the LM serving path.

New capability vs the reference (its inference story was the libVeles
chain executor; no autoregressive models existed). Naive sampling
re-forwards the whole window per new token — O(T²) matmuls per token
and a fresh device round trip each step. This module keeps per-block
K/V caches on device and runs the WHOLE generation as one
``lax.scan``: per token only the single-position projections + one
attention row run, and the host gets back the finished sequence.

Operates on the public parameter contract of the ``Embedding`` →
``TransformerBlock``×N → ``LMHead`` stack (optionally with a
``PositionalEmbedding`` after the stem); reuses transformer.py's
layernorm/gelu/rope math so cached and full paths cannot drift.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import numpy

from ..error import VelesError
from .transformer import (Embedding, LMHead, PositionalEmbedding,
                          TransformerBlock, _rope, block_ffn,
                          block_norm)


def _count_decode_dispatches(program):
    """Decorator applied DIRECTLY over ``jax.jit`` at every decode
    program definition (here and nn/speculative.py): each invocation
    of the jitted program counts one ``veles_decode_dispatches_total``.
    The counter sits at the device-program boundary, not the public
    generate() entry, so a decode restructured into a host loop of
    per-token jitted steps reads as n_new dispatches — the round-5
    dispatch-count regression lock measures, it does not assert. Any
    new jitted decode program MUST wear this decorator."""
    from ..telemetry.counters import inc

    @functools.wraps(program)
    def counted(*args, **kwargs):
        inc("veles_decode_dispatches_total")
        return program(*args, **kwargs)
    return counted


def params_of(wf):
    """The device-side parameter pytree of a workflow's forwards — the
    ONE copy of the extraction every decoding entry point shares."""
    return {f.name: {k: v.device_view()
                     for k, v in f.param_arrays().items()}
            for f in wf.forwards if f.PARAMETERIZED}


def _rope_at(np_mod, x, pos, base=10000.0):
    """RoPE for a SINGLE position a row: x (B, 1, H, Dh), pos a scalar
    or (B,) (traced ok). Same half-split pairing as transformer._rope."""
    hd = x.shape[-1]
    half = hd // 2
    inv = np_mod.asarray(
        (base ** (-numpy.arange(half, dtype="float32") / half)))
    ang = np_mod.reshape(pos, (-1, 1, 1, 1)).astype("float32") * inv
    cos = np_mod.cos(ang)                       # (1 or B, 1, 1, half)
    sin = np_mod.sin(ang)
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot1 = x1 * cos - x2 * sin
    rot2 = x1 * sin + x2 * cos
    if 2 * half == hd:
        return np_mod.concatenate([rot1, rot2], axis=-1)
    return np_mod.concatenate([rot1, rot2, x[..., 2 * half:]], axis=-1)


def split_stack(forwards, hybrid: bool = False) -> Dict[str, object]:
    """Stem / block-list / head decomposition of a generation-capable
    forward chain; raises for anything else. ``hybrid``: the caller
    serves a ``HybridBlock`` through the block's own ``serve_prefill``
    and ``serve_step`` (the paged engine); every other consumer (the
    scan sampler, speculation, beam search) runs ``_block_prefill`` and
    ``_block_step``, which are ``TransformerBlock``'s, and refuses the
    block by name. The same holds for a ``FinalNorm`` before the head
    (``"norm"``; None for every other stack), which the engine's
    ``_head_logits`` calls apply."""
    from .hybrid import FinalNorm, HybridBlock
    stem = pos_emb = head = norm = None
    blocks: List[TransformerBlock] = []
    for f in forwards:
        if isinstance(f, Embedding):
            stem = f
        elif isinstance(f, PositionalEmbedding):
            pos_emb = f
        elif isinstance(f, TransformerBlock):
            blocks.append(f)
        elif isinstance(f, HybridBlock):
            if not hybrid:
                raise VelesError(
                    "%s is a hybrid_block: it is served by the continuous "
                    "engine's greedy and sampled decode step only (--serve-"
                    "generate with the paged slot pool); the scan sampler, "
                    "speculative and beam decoding take transformer_block "
                    "stacks" % f.name)
            blocks.append(f)
        elif isinstance(f, FinalNorm):
            if not hybrid:
                raise VelesError(
                    "%s is a final_norm: it is served by the continuous "
                    "engine's greedy and sampled decode step only; the "
                    "scan sampler, speculative and beam decoding take "
                    "stacks with no norm before the head" % f.name)
            norm = f
        elif isinstance(f, LMHead):
            head = f
        else:
            raise VelesError(
                "cached sampling supports Embedding → [PositionalEmbedding]"
                " → TransformerBlock* → LMHead chains; found %s"
                % type(f).__name__)
    if stem is None or head is None or not blocks:
        raise VelesError("not a generation stack: stem=%r head=%r "
                         "blocks=%d" % (stem, head, len(blocks)))
    return {"stem": stem, "pos_emb": pos_emb, "blocks": blocks,
            "norm": norm, "head": head}


def _block_prefill(block, p, x, cache_k, cache_v, tp=1, tp_axis=None):
    """Full-window pass through one block, writing K/V into the caches'
    first T positions. The attention goes through the SAME per-shape
    chooser as TransformerBlock.apply (attention_core: f32 softmax,
    flash kernel above the crossover) so prefill logits cannot drift
    from the trained forward.

    ``tp``/``tp_axis`` (serving engine's ``--serve-tp``): inside a
    shard_map over a 1D ``("model",)`` mesh, ``p`` holds head-sharded
    weight shards (wq/wk/wv column, wo row) and the caches hold this
    shard's ``kv/tp`` K/V heads (Ulysses-style head sharding); the
    partial wo product psums into the full residual. ``hd`` always
    derives from the FULL head count — the residual ``d`` never
    shards."""
    import jax.numpy as jnp
    from jax import named_scope
    from .attention import attention_core
    from ..ops import matmul_precision
    prec = matmul_precision()
    b, t, d = x.shape
    h = block.n_heads // tp
    kv = getattr(block, "n_kv_heads", block.n_heads) // tp
    hd = d // block.n_heads

    # the same scopes as TransformerBlock.apply, under the unit's name
    with named_scope(block.name):
        with named_scope("norm1"):
            a_in = block_norm(jnp, block, p, x, "ln1")
        with named_scope("attn_qkv"):
            q = jnp.dot(a_in, p["wq"],
                        precision=prec).reshape(b, t, h, hd)
            k = jnp.dot(a_in, p["wk"],
                        precision=prec).reshape(b, t, kv, hd)
            v = jnp.dot(a_in, p["wv"],
                        precision=prec).reshape(b, t, kv, hd)
        if block.rope:
            base = getattr(block, 'rope_base', 10000.0)
            with named_scope("rope"):
                q, k = _rope(jnp, q, base), _rope(jnp, k, base)
        with named_scope("attn"):
            # the cache stores the UNREPEATED kv heads — with GQA it is
            # n_heads/n_kv_heads times smaller than an MHA cache
            cache_k = cache_k.at[:, :t].set(k)
            cache_v = cache_v.at[:, :t].set(v)
            o = attention_core(q, k, v, causal=True, mesh=None,
                               n_heads=h,
                               window=getattr(block, "window", None)
                               ).reshape(b, t, h * hd)
        with named_scope("attn_out"):
            proj = jnp.dot(o, p["wo"], precision=prec)
            if tp_axis is not None:
                import jax
                proj = jax.lax.psum(proj, tp_axis)
            x = x + proj
        with named_scope("norm2"):
            f_in = block_norm(jnp, block, p, x, "ln2")
        with named_scope("ffn"):
            return x + block_ffn(jnp, block, p, f_in, prec,
                                 tp_axis=tp_axis), cache_k, cache_v


def _dense_read(q, k, v, cache_k, cache_v, pos, window=None):
    """The one-token step's attention read of a dense cache: q (B, 1,
    H, Dh), the new rows k, v (B, 1, KV, Dh), caches (B, T_max, KV,
    Dh), pos scalar. Writes the rows at ``pos`` and reads the rows <=
    pos (the last ``window`` of them, where given) -> (o (B, 1, KV,
    H/KV, Dh) float32, cache_k, cache_v)."""
    import jax.numpy as jnp
    b, _, h, hd = q.shape
    kv = cache_k.shape[2]
    cache_k = jnp.asarray(cache_k).at[:, pos].set(k[:, 0])
    cache_v = jnp.asarray(cache_v).at[:, pos].set(v[:, 0])
    t_max = cache_k.shape[1]
    # single-row attention over the cache; scores/softmax in f32 like
    # attention_reference so the step matches the full-window forward.
    # GQA reads the unrepeated cache through a (kv, group) view of the
    # query heads — no (B, T, H, Dh) materialization.
    q5 = q.reshape(b, 1, kv, h // kv, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,btkd->bkgqt", q5,
                   cache_k.astype(jnp.float32)) / numpy.sqrt(hd)
    valid = jnp.arange(t_max) <= pos
    if window:
        # sliding window: only the last `window` cached rows are visible
        valid = valid & (jnp.arange(t_max) > pos - window)
    valid = valid[None, None, None, None, :]
    s = jnp.where(valid, s, -1e30)
    w = jnp.exp(s - s.max(axis=-1, keepdims=True))
    w = w / w.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bkgqt,btkd->bqkgd", w, cache_v.astype(jnp.float32))
    return o, cache_k, cache_v


def _block_step(block, p, x_t, cache_k, cache_v, pos, tp=1,
                tp_axis=None, attend=None):
    """One-token pass: x_t (B, 1, D), caches (B, T_max, H, Dh), pos =
    tokens already cached. Attention reads the cache rows <= pos.
    ``tp``/``tp_axis``: head-sharded weights + ``kv/tp``-head caches
    inside a shard_map, exactly as :func:`_block_prefill`.
    ``attend(q, k, v, cache_k, cache_v, pos)`` is the attention read
    and what it keeps (:func:`_dense_read`'s contract, the default): the
    serving engine's float step passes one that reads its page pool
    through the page table, with a (B,) ``pos``; the block's norms,
    projections, rotary and FFN are this one body either way."""
    import jax.numpy as jnp
    from jax import named_scope
    from ..ops import matmul_precision
    prec = matmul_precision()
    b, _, d = x_t.shape
    h = block.n_heads // tp
    kv = getattr(block, "n_kv_heads", block.n_heads) // tp
    hd = d // block.n_heads
    if attend is None:
        attend = functools.partial(_dense_read,
                                   window=getattr(block, "window", None))

    with named_scope(block.name):
        with named_scope("norm1"):
            a_in = block_norm(jnp, block, p, x_t, "ln1")
        with named_scope("attn_qkv"):
            q = jnp.dot(a_in, p["wq"],
                        precision=prec).reshape(b, 1, h, hd)
            k = jnp.dot(a_in, p["wk"],
                        precision=prec).reshape(b, 1, kv, hd)
            v = jnp.dot(a_in, p["wv"],
                        precision=prec).reshape(b, 1, kv, hd)
        if block.rope:
            base = getattr(block, 'rope_base', 10000.0)
            with named_scope("rope"):
                q = _rope_at(jnp, q, pos, base)
                k = _rope_at(jnp, k, pos, base)
        with named_scope("attn"):
            o, cache_k, cache_v = attend(q, k, v, cache_k, cache_v, pos)
            o = o.astype(x_t.dtype).reshape(b, 1, h * hd)
        with named_scope("attn_out"):
            proj = jnp.dot(o, p["wo"], precision=prec)
            if tp_axis is not None:
                import jax
                proj = jax.lax.psum(proj, tp_axis)
            x_t = x_t + proj
        with named_scope("norm2"):
            f_in = block_norm(jnp, block, p, x_t, "ln2")
        with named_scope("ffn"):
            return x_t + block_ffn(jnp, block, p, f_in, prec,
                                   tp_axis=tp_axis), \
                cache_k, cache_v


def _embed_ids(stem, params, ids, tp=1, tp_axis=None):
    """Embedding-table gather for int token ids of ANY shape —
    ``mode="clip"`` semantics. Under ``tp_axis`` the table is a
    vocab-row shard: ids are clipped against the GLOBAL vocab, rows
    this shard owns gather locally, foreign rows contribute EXACT
    zeros, and the psum rebuilds the full embedding bit-exactly (a
    sum of one real row and N-1 exact zeros is the row)."""
    import jax
    import jax.numpy as jnp
    table = params[stem.name]["table"]
    ids = ids.astype(jnp.int32)
    with jax.named_scope(stem.name), jax.named_scope("embed"):
        if tp_axis is None:
            return jnp.take(table, ids, axis=0, mode="clip")
        vloc = table.shape[0]
        gids = jnp.clip(ids, 0, vloc * tp - 1)
        local = gids - jax.lax.axis_index(tp_axis) * vloc
        own = (local >= 0) & (local < vloc)
        x = jnp.where(own[..., None],
                      jnp.take(table, jnp.clip(local, 0, vloc - 1),
                               axis=0), 0)
        return jax.lax.psum(x, tp_axis)


def _embed_prompt(stem, pos_emb, params, ids, pos0=0, tp=1,
                  tp_axis=None):
    """(B, T) token ids → (B, T, D): embedding-table gather plus the
    positional rows ``pos0..pos0+T`` — THE stack entry every prompt
    consumer shares (the sampler, the serving engine's bucketed
    prefill, :func:`prompt_logits`). One definition, so a change to
    how the stack enters (a new pos-emb variant, a promotion tweak)
    cannot drift between the serving programs and the float reference
    the quantization gate measures against. ``tp``/``tp_axis``: the
    vocab-row-sharded gather of :func:`_embed_ids`; the positional
    table stays replicated."""
    import jax.numpy as jnp
    x = _embed_ids(stem, params, ids, tp=tp, tp_axis=tp_axis)
    if pos_emb is not None:
        idx = pos0 + jnp.arange(ids.shape[-1])
        x = x + jnp.take(params[pos_emb.name]["table"], idx,
                         axis=0, mode="clip")[None]
    return x


def _prefill_blocks(blocks, params, x, cache_len, dim, tp=1,
                    tp_axis=None, live=None):
    """Run every transformer block's ``_block_prefill`` over fresh
    zero K/V caches of ``cache_len`` rows → (x, [(ck, cv), ...]) —
    the shared prompt forward. Each block shapes its OWN cache (the
    layers config allows heterogeneous n_heads; with GQA the cache
    holds the unrepeated n_kv_heads rows; under ``tp`` each shard
    caches its own ``n_kv_heads/tp`` slice). A block that states its
    own ``serve_prefill`` (``HybridBlock``) runs that and hands back its
    K and V rows at its own widths; ``live`` (T,) bool tells it which
    rows are the prompt's."""
    import jax.numpy as jnp
    b = x.shape[0]
    caches = []
    for blk in blocks:
        if hasattr(blk, "serve_prefill"):
            x, ck, cv = blk.serve_prefill(params[blk.name], x, live=live)
            caches.append((ck, cv))
            continue
        bkv = getattr(blk, "n_kv_heads", blk.n_heads) // tp
        hd = dim // blk.n_heads
        ck = jnp.zeros((b, cache_len, bkv, hd), x.dtype)
        cv = jnp.zeros((b, cache_len, bkv, hd), x.dtype)
        x, ck, cv = _block_prefill(blk, params[blk.name], x, ck, cv,
                                   tp=tp, tp_axis=tp_axis)
        caches.append((ck, cv))
    return x, caches


def _head_logits(head, params, x_last, prec, tp_axis=None, norm=None):
    """Vocabulary head projection, shared by the same three consumers
    as :func:`_embed_prompt`, after the stack's ``FinalNorm`` where it
    has one (``norm``). Under ``tp_axis`` weights/bias are
    vocab-column shards: each shard computes its own logit columns
    (bit-exact — every column is one full-depth dot), and a tiled
    all_gather rebuilds the full replicated (…, V) row so sampling
    runs identically on every shard."""
    import jax
    import jax.numpy as jnp
    if norm is not None:
        x_last = norm.apply(params[norm.name], x_last)
    with jax.named_scope(head.name), jax.named_scope("head"):
        out = (jnp.dot(x_last, params[head.name]["weights"],
                       precision=prec) + params[head.name]["bias"])
        if tp_axis is not None:
            out = jax.lax.all_gather(out, tp_axis, axis=out.ndim - 1,
                                     tiled=True)
        return out


def _build_sampler(wf, t_p, n_new, temperature):
    """Compile-once generation program for one (prompt length, n_new,
    temperature) shape; params are ARGUMENTS (not baked constants), so
    repeated calls — and continued training between them — reuse the
    executable."""
    import jax
    import jax.numpy as jnp
    from ..ops import matmul_precision
    stack = split_stack(list(wf.forwards))
    stem, pos_emb = stack["stem"], stack["pos_emb"]
    blocks, head = stack["blocks"], stack["head"]
    t_max = t_p + int(n_new)
    d = stem.dim
    prec = matmul_precision()
    if pos_emb is not None:
        table_len = pos_emb.param_arrays()["table"].shape[0]
        if t_max > table_len:
            raise VelesError(
                "generation to %d positions exceeds the trained "
                "PositionalEmbedding table (%d rows); the real forward "
                "would fail too — use RoPE blocks for open-ended "
                "generation" % (t_max, table_len))
    greedy = temperature <= 0

    def embed(params, ids, pos0):
        return _embed_prompt(stem, pos_emb, params, ids, pos0)

    def sample(logits, keys):
        """``logits`` (B, V), ``keys`` (B, 2): every row draws from its
        OWN key, so a row's token depends only on (its seed, its
        prompt) — never on batch size or on which strangers share the
        dispatch. This is what lets the serving planes coalesce
        ``mode=sample`` requests without breaking the same-request →
        same-tokens contract (for B=1 the bits match the old
        single-key path exactly: categorical noise of shape (1, V) and
        (V,) draw the same stream)."""
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.vmap(
            lambda k, row: jax.random.categorical(k, row / temperature)
        )(keys, logits).astype(jnp.int32)

    def head_logits(params, x_last):
        return _head_logits(head, params, x_last, prec)

    @_count_decode_dispatches
    @jax.jit
    def run(params, prompt_ids, keys):
        x = embed(params, prompt_ids, 0)       # (B, T_p, D)
        x, caches = _prefill_blocks(blocks, params, x, t_max, d)
        # keys (B, 2): one independent stream per row (see sample)
        keys, subs = _split_rows(keys)
        first = sample(head_logits(params, x[:, -1]), subs)   # (B,)

        def step(carry, i):
            tok, caches, keys = carry
            pos = t_p + i
            x_t = embed(params, tok[:, None], pos)   # (B, 1, D)
            new_caches = []
            for blk, (ck, cv) in zip(blocks, caches):
                x_t, ck, cv = _block_step(blk, params[blk.name], x_t,
                                          ck, cv, pos)
                new_caches.append((ck, cv))
            keys, subs = _split_rows(keys)
            nxt = sample(head_logits(params, x_t[:, 0]), subs)
            return (nxt, tuple(new_caches), keys), tok

        (_, _, _), toks = jax.lax.scan(
            step, (first, tuple(caches), keys), jnp.arange(n_new))
        return toks                                  # (n_new, B)

    return run


def prompt_logits(wf, prompt, params=None):
    """Last-position logits for ``prompt`` through the cached-decode
    prefill path (``_block_prefill`` + head) — the float reference the
    quantization bench measures its max-logit-delta against. ``params``
    overrides the workflow's own tree (pass a
    dequantize(quantize(...)) twin to measure pure quantization
    error). Eager, host-sized: a measurement helper, not a serving
    path."""
    import jax.numpy as jnp
    from ..ops import matmul_precision
    stack = split_stack(list(wf.forwards))
    stem, pos_emb = stack["stem"], stack["pos_emb"]
    blocks, head = stack["blocks"], stack["head"]
    prec = matmul_precision()
    if params is None:
        params = params_of(wf)
    ids = jnp.asarray(numpy.asarray(prompt, numpy.int32))[None]
    x = _embed_prompt(stem, pos_emb, params, ids)
    x, _ = _prefill_blocks(blocks, params, x, ids.shape[-1], stem.dim)
    return numpy.asarray(_head_logits(head, params, x[0, -1], prec))


def _split_rows(keys):
    """Advance a batch of per-row PRNG streams one step: ``keys``
    (B, 2) → (new carries (B, 2), subkeys (B, 2)). Row r's stream is
    exactly what ``split`` would produce from that row's key alone, so
    decode outputs are invariant to batch composition."""
    import jax
    out = jax.vmap(jax.random.split)(keys)      # (B, 2, 2)
    return out[:, 0], out[:, 1]


def _row_keys(seed, batch):
    """(B, 2) per-row PRNG keys from ``seed``: an int seeds every row
    identically (same request → same tokens whatever the batch), a
    sequence of B ints gives each row its own stream. Each row's key
    is exactly ``jax.random.PRNGKey(seed_row)`` — any int a solo
    decode accepted before (negative, 64-bit) still works and maps to
    the same key."""
    import jax
    import jax.numpy as jnp
    seeds = numpy.asarray(seed)
    if seeds.ndim == 0:
        seeds = numpy.broadcast_to(seeds, (batch,))
    elif seeds.shape != (batch,):
        raise VelesError("seed must be an int or a sequence of %d ints,"
                         " got shape %s" % (batch, seeds.shape))
    return jnp.asarray(numpy.stack(
        [numpy.asarray(jax.random.PRNGKey(int(s))) for s in seeds]))


def generate(wf, prompt, n_new, temperature=1.0, seed=0):
    """Sample ``n_new`` tokens continuing ``prompt`` from a trained
    Embedding→blocks→LMHead workflow. ``prompt`` is a list of ids (→
    returns a flat token list) or a batch of B equal-length prompts (→
    returns B lists; the whole batch decodes in the same single
    dispatch). Prefill warms the caches in one full-window pass;
    generation is one ``lax.scan``. ``temperature <= 0`` = greedy.
    ``seed`` is an int (every row draws the same per-row stream — a
    request's tokens never depend on who shares the batch) or a
    sequence of B ints giving each row its own stream. Compiled
    programs cache per (batch, prompt length, n_new, temperature)."""
    import jax  # noqa: F401 — backend init before key construction
    import jax.numpy as jnp
    try:
        prompt = numpy.asarray(prompt, dtype=numpy.int32)
    except ValueError as e:
        raise VelesError(
            "batched generation needs EQUAL-length prompts (pad or "
            "group by length): %s" % e) from e
    batched = prompt.ndim == 2
    if not batched:
        prompt = prompt[None, :]
    t_p = prompt.shape[1]
    cache = getattr(wf, "_sampler_cache", None)
    if cache is None:
        cache = wf._sampler_cache = {}
    key = (prompt.shape[0], t_p, int(n_new), float(temperature))
    run = cache.get(key)
    if run is None:
        run = cache[key] = _build_sampler(wf, t_p, n_new, temperature)
    params = params_of(wf)
    from ..telemetry.counters import inc
    from ..telemetry.spans import span
    with span("decode.cached", batch=int(prompt.shape[0]),
              n_new=int(n_new)):
        # prefill + scan is ONE device program, so this whole decode
        # must cost exactly one decode dispatch (the round-5
        # regression lock). The counter rides the PROGRAM wrapper, not
        # this call site: a restructure that invokes the program per
        # token shows up as n_new dispatches, not a hand-asserted 1.
        toks = numpy.asarray(
            run(params, jnp.asarray(prompt),
                _row_keys(seed, prompt.shape[0])))
    inc("veles_decode_tokens_total", int(n_new) * int(prompt.shape[0]))
    if not batched:
        return [int(t) for t in toks[:, 0]]
    return [[int(t) for t in toks[:, i]] for i in range(toks.shape[1])]
