"""Flash attention under the block-causal mask of generation by diffusion over
blocks (``mask_block``): against the dense mask, forward and backward, at one
block a head and at several; and the causal kernel unchanged by the option."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.causal_lm import block_causal_mask
from deepspeed_tpu.ops.attention.flash import flash_attention
from deepspeed_tpu.ops.transformer.attention import xla_attention


def _qkv(t, seed=0, h=2, d=32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, t, h, d)), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("t,blocks", [(256, (1024, 1024)), (512, (128, 128)),
                                      (512, (256, 128)), (1024, (1024, 1024))],
                         ids=["one-block", "square-tiles", "uneven-tiles", "strips"])
@pytest.mark.parametrize("mask_block", [4, 8])
def test_block_causal_flash_agrees_with_the_dense_mask(t, blocks, mask_block):
    q, k, v = _qkv(t)
    mask = jnp.asarray(block_causal_mask(t, mask_block))[None, None]
    want = xla_attention(q, k, v, causal=False, mask=mask)
    got = flash_attention(q, k, v, causal=True, mask_block=mask_block,
                          block_q=blocks[0], block_k=blocks[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # it is not the causal result: a query sees to the end of its block
    plain = flash_attention(q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1])
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-2


def test_block_causal_flash_differentiates_as_the_dense_mask_does():
    q, k, v = _qkv(256, seed=1)
    mask = jnp.asarray(block_causal_mask(256, 4))[None, None]

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    want = jax.grad(loss(lambda q, k, v: xla_attention(
        q, k, v, causal=False, mask=mask)), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, mask_block=4)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_the_causal_kernel_is_unchanged_bit_for_bit():
    """``mask_block=1`` is the causal mask and adds no operation to it: the
    same jaxpr as a call that does not name the option and the same bits; the
    block mask's one extra op, a remainder, shows only with a block."""
    q, k, v = _qkv(512, seed=2)
    default = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    named = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, mask_block=1))(q, k, v)
    assert str(default) == str(named)
    block = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, mask_block=4))(q, k, v)
    assert str(block).count(" rem ") > str(default).count(" rem ")
    a = flash_attention(q, k, v, causal=True)
    b = flash_attention(q, k, v, causal=True, mask_block=1)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(a), np.asarray(xla_attention(q, k, v, causal=True)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bad", [dict(causal=False, mask_block=4),
                                 dict(causal=True, mask_block=3)])
def test_a_block_mask_the_tiles_cannot_hold_is_refused(bad):
    q, k, v = _qkv(256)
    with pytest.raises(ValueError, match="block-causal"):
        flash_attention(q, k, v, **bad)
