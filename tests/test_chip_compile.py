"""The Pallas kernels compiled by the TPU's own compiler for a v5e that
is described, not attached: every tile pair devices/kernel_tuning.json
commits for "TPU v5 lite" lowers, forward and custom-VJP backward, at its
own length, head size, grouping and operand dtype, with the VMEM limit
the kernels compute for themselves; and the paged decode read at the
dense serving cell's widths. What interpret mode cannot see (VMEM,
tiling) and a chip run costs minutes to see.

All in this one file, the topology described inside a fixture: only one
process may load libtpu, and only the worker that runs this file does."""
import json
import os
import re

import pytest

from veles_tpu.ops import autotune

V5E = "TPU v5 lite"
with open(autotune.SHIPPED) as _f:
    ROWS = sorted((key, row) for key, row in json.load(_f)[V5E].items()
                  if "block_q" in row)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the persistent
    # cache and cannot be read back without a chip: keep it out
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("key,row", ROWS, ids=[key for key, _ in ROWS])
def test_committed_tiles_lower_on_a_described_v5e(key, row, one_chip,
                                                  no_compile_cache):
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import flash_attention as fa
    t, d = map(int, re.fullmatch(r"flash_t(\d+)_d(\d+)_causal",
                                 key).groups())
    h, kv = row.get("h", 8), row.get("kv", row.get("h", 8))
    dtype = jnp.dtype(row.get("dtype", "bfloat16"))
    q, k = (jax.ShapeDtypeStruct((1, t, heads, d), dtype, sharding=one_chip)
            for heads in (h, kv))
    fwd = (row["block_q"], row["block_k"])
    bwd = (row.get("bwd_block_q", fwd[0]), row.get("bwd_block_k", fwd[1]))

    def loss(q, k, v, blocks):
        return fa.flash_attention(
            q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
            interpret=False).astype(jnp.float32).sum()

    # the forward at its tiles, and the backward pair at its own (a
    # gradient's forward rides along at the backward's tiles: it lowers
    # wherever the larger backward working set does)
    text = jax.jit(lambda q, k, v: loss(q, k, v, fwd)).lower(
        q, k, k).compile().as_text()
    assert "veles_flash_fwd" in text and "tpu_custom_call" in text
    text = jax.jit(jax.grad(lambda q, k, v: loss(q, k, v, bwd),
                            argnums=(0, 1, 2))).lower(
        q, k, k).compile().as_text()
    assert "veles_flash_bwd_dkv" in text and "veles_flash_bwd_dq" in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_read_lowers_on_a_described_v5e(dtype, one_chip,
                                              no_compile_cache):
    """ops/paged_attention.py at mistral7b_decode_sat's widths (32 slots,
    32 query heads on 8 KV heads of 128, pages of 16, a table of 128
    pages) with its write-back beside it: the kernel lowers, and the
    pools are read and written where they lie, with no copy of either."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import paged_attention as pa
    slots, heads, kv, hd, size, width = 32, 32, 8, 128, 16, 128
    pages = slots * width + 1
    dtype = jnp.dtype(dtype)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(q, k_pool, v_pool, tables, pos, k_new):
        o = pa.paged_decode_attention(
            q, k_pool, v_pool, tables, jnp.zeros_like(pos), pos,
            k_new[:, None], k_new[:, None], jnp.ones((slots, 1), bool),
            interpret=False)
        page = jnp.take_along_axis(tables, (pos // size)[:, None], 1)[:, 0]
        return (o, k_pool.at[page, pos % size].set(k_new),
                v_pool.at[page, pos % size].set(k_new))

    pool = spec((pages, size, kv, hd), dtype)
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        spec((slots, heads, hd), jnp.float32), pool, pool,
        spec((slots, width), jnp.int32), spec((slots,), jnp.int32),
        spec((slots, kv, hd), dtype)).compile()
    text = compiled.as_text()
    assert "veles_paged_attn" in text and "tpu_custom_call" in text
    # temporaries of a few (S, H, hd) states, nothing of a pool's size
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def _olmo_step(one_chip, layers, paged):
    """The continuous engine's decode step for ``olmo-hybrid-7b`` at its
    widths and the cell's 64 slots and 1,536 positions, ``layers`` of it
    (the vocabulary cut, which only the head sees), compiled for a
    described v5e: (compiled, the pools' shapes as "a,b,c,d"). ``paged``:
    the full layers read through the page table, as the engine's
    predicate decides on a TPU (tests/ladder_drill.py ``force_paged``),
    the kernel lowered for the chip as on a TPU, not interpreted as off
    one."""
    import sys
    from unittest import mock
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import paged_attention as pa
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import modules
    from veles_tpu import nn
    from veles_tpu.config import root as config
    from veles_tpu.serving import ContinuousEngine
    with open(os.path.join(root, "chipbench", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=layers, vocab_size=2048)
    ref = modules.reference_of(cfg, serving=True)
    kinds = {"embedding": nn.Embedding, "hybrid_block": nn.HybridBlock,
             "final_norm": nn.FinalNorm, "lm_head": nn.LMHead}
    units = [kinds[layer["type"]](None, **{k: v for k, v in layer.items()
                                           if k != "type"})
             for layer in ref.layer_list(cfg)]

    class Stack:
        forwards = units

    slots = 64
    keep = config.common.engine.precision_type
    config.common.engine.precision_type = "bfloat16"
    try:
        eng = ContinuousEngine(Stack, max_slots=slots,
                               buckets=(256, 512, 1024), max_context=1536,
                               name="olmo_compile")

        def spec(shape, dt):
            return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                        sharding=one_chip)
        params = {u: {k: spec(s, "bfloat16") for k, (s, _) in
                      ref.unit_shapes(cfg, u).items()}
                  for u in ref.unit_names(cfg)}
        caches, big = [], set()
        for blk in eng.stack["blocks"]:
            state = eng._state(blk)
            if state:
                pair = (spec((slots,) + tuple(state[0]), "float32"),
                        spec((slots,) + tuple(state[1]), "bfloat16"))
            else:
                kv, kd, vd, _ = eng._geometry(blk)
                assert kv == 32
                rows = eng.page_pool.device_rows
                pair = (spec((rows, 16, kv, kd), "bfloat16"),
                        spec((rows, 16, kv, vd), "bfloat16"))
            caches.append(pair)
            big.add(",".join(map(str, pair[0].shape)))
        eng._caches = caches
        if paged:
            import ladder_drill
            ladder_drill.force_paged(eng)
        width = eng.view_ladder[0]
        ints = [spec((slots,), "int32")] * 2
        read = pa.paged_attention

        def on_chip(*args, **kwargs):
            return read(*args, **dict(kwargs, interpret=False))

        with mock.patch.object(pa, "paged_attention", on_chip):
            compiled = eng._build_decode().lower(
                params, *ints, spec((slots,), "float32"), ints[0],
                spec((slots, width), "int32"), ints[0],
                spec((1, slots + len(eng._tap_names)), "int32"),
                spec((slots, 2), "uint32"), tuple(caches)).compile()
    finally:
        config.common.engine.precision_type = keep
    return compiled, big


def _copies(compiled):
    return re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(",
                      compiled.as_text())


def test_the_hybrid_state_step_copies_no_pool(one_chip, no_compile_cache):
    """The continuous engine's decode step for ``olmo-hybrid-7b`` at its
    widths and the cell's 64 slots and 1,536 positions, one period of its
    layers (three delta-rule layers, one full layer; the vocabulary cut,
    which only the head sees): the delta-rule states (64, 30, 96, 192) in
    float32 and the full layer's pages (30 KV heads stored as 32,
    ``nn/hybrid.stored_heads``) are read and written where they lie, with
    no copy of any of them (at 30 heads XLA converted both page pools
    whole, to and fro, every step)."""
    compiled, big = _olmo_step(one_chip, 4, paged=False)
    assert big == {"64,30,96,192", "6145,16,32,128"}
    assert not big & set(_copies(compiled)), _copies(compiled)
    # beside the pools: a view of a full layer's K and V and smaller
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_the_hybrid_step_reads_its_full_layers_through_the_table(
        one_chip, no_compile_cache):
    """The same step at the cell's 8 layers, its two full layers read
    through the page table as on a TPU: the kernel is in it, no gather of
    a view is, and no state or page pool is copied; the views' gigabyte of
    temporaries is gone with the gather. Each full layer's kernel is one
    device operation named after the scope it runs in, ``full_attn``, the
    name a profile files its time under (``scope_ms.full_attn``)."""
    compiled, big = _olmo_step(one_chip, 8, paged=True)
    text = compiled.as_text()
    assert "page_gather" not in text
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert {re.match(r"\s*(?:ROOT )?%?([\w.-]+) = ", line).group(1)
            .rsplit(".", 1)[0] for line in calls} == {"full_attn"}
    assert sorted(re.search(r'op_name="[^"]*?/(blk\d+)/full_attn/', line)
                  .group(1) for line in calls) == ["blk3", "blk7"]
    assert big == {"64,30,96,192", "6145,16,32,128"}
    assert not big & set(_copies(compiled)), _copies(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("slots,heads,kv,hd,true_hd,vd,width", [
    (64, 32, 32, 128, 128, 128, 96),
    (64, 64, 4, 256, 192, 128, 288),
], ids=["olmo_full", "mimo_full"])
def test_the_hybrid_paged_read_lowers_on_a_described_v5e(
        slots, heads, kv, hd, true_hd, vd, width, one_chip,
        no_compile_cache):
    """ops/paged_attention.py at the hybrid cells' full layers, bfloat16
    pools: Olmo's 30 heads stored as 32 on as many KV heads, mimo's 64
    on 4 KV heads with keys of 192 at 256 lanes and values of 128 (the
    pages handed over as they lie).
    The kernel lowers and no pool is copied to reach it."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import paged_attention as pa
    pages = slots * width + 1

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def read(q, k_pool, v_pool, tables, pos, k_new, v_new):
        return pa.paged_decode_attention(
            q, k_pool, v_pool, tables, jnp.zeros_like(pos), pos,
            k_new[:, None], v_new[:, None], jnp.ones((slots, 1), bool),
            scale=1 / true_hd ** 0.5, interpret=False)

    compiled = jax.jit(read).lower(
        spec((slots, heads, hd), "bfloat16"),
        spec((pages, 16, kv, hd), "bfloat16"),
        spec((pages, 16, kv, vd), "bfloat16"),
        spec((slots, width), "int32"), spec((slots,), "int32"),
        spec((slots, kv, hd), "bfloat16"),
        spec((slots, kv, vd), "bfloat16")).compile()
    text = compiled.as_text()
    assert "veles_paged_attn" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20
