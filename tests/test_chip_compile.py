"""The flash kernels compiled by the TPU's own compiler for a v5e that
is described, not attached: every tile pair devices/kernel_tuning.json
commits for "TPU v5 lite" lowers, forward and custom-VJP backward, at its
own length, head size, grouping and operand dtype, with the VMEM limit
the kernels compute for themselves. What interpret mode cannot see (VMEM,
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
