"""Gather-fused MoE decode FFN kernel vs the XLA gather reference (interpret mode).

Real-TPU compiled parity rides the shared kernel gate
(``ops/kernel_checks.py::check_moe_decode_ffn``, run by ``chip_smoke.py`` and the TPU lane).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.moe.decode_ffn import moe_decode_ffn, moe_decode_ffn_xla


def _mk(e, d, f, n, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.standard_normal((n, d)), dtype),
            jnp.asarray(rng.randint(0, e, size=(n,)), jnp.int32),
            jnp.asarray(rng.standard_normal((e, d, f)) * d ** -0.5, dtype),
            jnp.asarray(rng.standard_normal((e, f)) * 0.02, dtype),
            jnp.asarray(rng.standard_normal((e, f, d)) * f ** -0.5, dtype),
            jnp.asarray(rng.standard_normal((e, d)) * 0.02, dtype))


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("shape", [(4, 128, 256), (8, 256, 512)])
def test_kernel_matches_xla_gather(n, shape):
    e, d, f = shape
    args = _mk(e, d, f, n, seed=n)
    o_kernel = jax.jit(lambda *a: moe_decode_ffn(*a, act=jax.nn.gelu))(*args)
    o_ref = moe_decode_ffn_xla(*args, act=jax.nn.gelu)
    assert o_kernel.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_unblockable_shapes_fall_back():
    # f with no 128-multiple divisor under the VMEM cap → must still be correct
    e, d, f, n = 4, 96, 200, 3
    args = _mk(e, d, f, n, seed=9)
    o = moe_decode_ffn(*args, act=jax.nn.relu)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(moe_decode_ffn_xla(*args, act=jax.nn.relu)),
        rtol=2e-5, atol=2e-5)


def test_every_token_hits_its_own_expert():
    # one token per expert, expert weights made distinguishable by scaling
    e, d, f = 4, 128, 256
    x, _, w1, b1, w2, b2 = _mk(e, d, f, e, seed=3)
    scale = jnp.arange(1, e + 1, dtype=jnp.float32)[:, None, None]
    w1 = w1 * scale
    idx = jnp.arange(e, dtype=jnp.int32)
    o = moe_decode_ffn(x, idx, w1, b1, w2, b2, act=jax.nn.gelu)
    ref = moe_decode_ffn_xla(x, idx, w1, b1, w2, b2, act=jax.nn.gelu)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
