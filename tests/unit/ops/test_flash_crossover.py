"""Flash-vs-XLA crossover timing (real TPU only — skipped on CPU where the Pallas
kernel runs in interpreter mode).

Documents the measurement backing ``FLASH_MIN_SEQ``: since the grid-pipelined kernel
rewrite, flash must beat XLA attention at seq >= 1024 for both forward and
forward+backward on a GPT-2-shaped workload. A regression here means the ``auto``
resolver default is routing the bench to the slower path.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="timing comparison only meaningful on TPU")


def _chain(attn, n, **kw):
    @jax.jit
    def run(q, k, v):
        def body(i, q):
            return attn(q, k, v, causal=True, **kw).astype(q.dtype)
        return lax.fori_loop(0, n, body, q)
    return run


def _total(fn, q, k, v, reps=3):
    _ = float(jnp.sum(fn(q, k, v).astype(jnp.float32)))   # compile + warm
    ts = []
    for _i in range(reps):
        t0 = time.perf_counter()
        _ = float(jnp.sum(fn(q, k, v).astype(jnp.float32)))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _per_iter(attn, q, k, v, **kw):
    # chain-length differencing cancels dispatch/fetch overhead —
    # per-iter = (T(n=40) - T(n=10)) / 30
    t10 = _total(_chain(attn, 10, **kw), q, k, v)
    t40 = _total(_chain(attn, 40, **kw), q, k, v)
    return (t40 - t10) / 30


@pytest.mark.parametrize("t", [1024, 2048, 4096])
def test_flash_beats_xla(t):
    from deepspeed_tpu.ops.attention.flash import flash_attention
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    rng = np.random.RandomState(0)
    b, h, d = max(1, 8192 // t), 12, 64
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
    tf = _per_iter(flash_attention, q, k, v)
    tx = _per_iter(xla_attention, q, k, v)
    assert tf < tx * 1.1, (f"flash {tf*1e3:.2f}ms should beat xla {tx*1e3:.2f}ms "
                           f"at seq {t}")
