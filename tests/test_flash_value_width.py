"""The flash kernels told a value width apart from the key width (latent
attention's keys of 192 over values of 128), in pallas interpret mode
against ``nn.attention.masked_attention``, which takes any pair of widths:
the forward and every input's gradient through the Pallas backward pair
and through the plain blockwise one, grouped and not; the scale is that
of the unpadded key width; the analytic cost counts each width apart."""
import functools

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.nn.attention import masked_attention
from veles_tpu.ops import autotune
from veles_tpu.ops import flash_attention as fa

flash = functools.partial(fa.flash_attention, interpret=True, causal=True,
                          block_q=128, block_k=128)


def inputs(t, h, kv, dk, dv, seed=0):
    rng = numpy.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(1, t, heads, width), jnp.float32)
                 for heads, width in ((h, dk), (kv, dk), (kv, dv)))


def plain(q, k, v):
    t = q.shape[1]
    mask = jnp.asarray(numpy.tril(numpy.ones((t, t), bool))[None])
    return masked_attention(q, k, v, mask)


def close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


#: (key width, value width, query heads, KV heads): latent attention's
#: pair, a small pair inside one lane group, values wider than keys, and
#: latent attention's widths on grouped heads
PAIRS = [(192, 128, 2, 2), (48, 16, 2, 2), (64, 160, 2, 2),
         (192, 128, 4, 2)]


@pytest.mark.parametrize("dk,dv,h,kv", PAIRS,
                         ids=["%d_%d_h%d_kv%d" % p for p in PAIRS])
def test_forward_with_a_value_width_of_its_own(dk, dv, h, kv):
    q, k, v = inputs(256, h, kv, dk, dv)
    o = flash(q, k, v)
    assert o.shape == (1, 256, h, dv)
    close(o, plain(q, k, v), 1e-5)


@pytest.mark.parametrize("pallas_bwd", [True, False],
                         ids=["pallas_pair", "blockwise"])
@pytest.mark.parametrize("dk,dv,h,kv", PAIRS,
                         ids=["%d_%d_h%d_kv%d" % p for p in PAIRS])
def test_gradients_with_a_value_width_of_its_own(dk, dv, h, kv, pallas_bwd,
                                                 monkeypatch):
    monkeypatch.setattr(fa, "_use_pallas_bwd", lambda: pallas_bwd)
    q, k, v = inputs(256, h, kv, dk, dv, seed=1)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        close(g, w, 2e-5)


def test_the_scale_is_the_unpadded_key_width_s():
    """192 lanes go padded to 256: the scores are still over sqrt(192)."""
    q, k, v = inputs(128, 2, 2, 192, 128, seed=2)
    o = flash(q, k, v)
    close(o, plain(q, k, v), 1e-5)
    wrong = masked_attention(q, k, v, jnp.asarray(
        numpy.tril(numpy.ones((128, 128), bool))[None]),
        scale=1.0 / numpy.sqrt(256.0))
    assert float(jnp.max(jnp.abs(o - wrong))) > 1e-3


def test_the_analytic_cost_counts_each_width():
    """One call of 2 sequences, 16 heads of 192 keys and 128 values over
    8,192 causal positions: QK^T 2*2*16*8192*4096*192 and PV at 128, by
    hand; q and k read at 192, v read and o written at 128."""
    c = fa.analytic_cost(2, 8192, 16, 192, causal=True, dv=128)
    qk = 2.0 * 2 * 16 * 8192 * 4096 * 192
    pv = 2.0 * 2 * 16 * 8192 * 4096 * 128
    assert c.flops == qk + pv == 687_194_767_360
    assert c.bytes_accessed == 2 * 16 * 8192 * (2 * 192 + 2 * 128) * 2 \
        + 2 * 16 * 8192 * 4
    # a call whose widths agree is counted as it always was
    same = fa.analytic_cost(2, 8192, 16, 128, causal=True)
    assert same.flops == fa.analytic_cost(2, 8192, 16, 128, causal=True,
                                          dv=128).flops
    assert same.flops == 4.0 * 2 * 16 * 8192 * 4096 * 128


def test_a_value_width_is_a_row_of_its_own():
    assert autotune.flash_key(8192, 192, True, dv=128) == \
        "flash_t8192_d192_v128_causal"
    assert autotune.flash_key(8192, 256, True, dv=256) == \
        autotune.flash_key(8192, 256, True) == "flash_t8192_d256_causal"


def test_keys_and_queries_of_different_widths_are_refused():
    q, k, v = inputs(128, 2, 2, 64, 32)
    with pytest.raises(ValueError, match="q is 64 wide, k 32"):
        flash(q, v, v)
