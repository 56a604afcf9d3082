"""The decode step's K/V append (``models/causal_lm.py: _cache_update``)
against the form the tree had through PR 29, a vmapped
``dynamic_update_slice``: the same values in the same rows, a position past
the cap clamped to the last row as ``dynamic_update_slice`` clamps it. On the
chip the vmapped form became a serial loop over the slots
(``tests/unit/ops/test_compile_v5e.py`` holds the lowered chunk to no loop but
its own); here the two are compared value for value."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.causal_lm import _cache_update

HK, CAP, D = 2, 40, 8


def _vmapped_update(cache, new, cache_len):
    def one(c, n, p):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, p, 0))
    return jax.vmap(one)(cache, new, cache_len)


def _lens(slots, rng):
    """Every slot at a different length, 0 and cap - 1 among them (one slot:
    each in turn), then positions at and past the cap."""
    if slots == 1:
        return [[0], [CAP - 1], [CAP], [CAP + 7]]
    inner = rng.permutation(np.arange(1, CAP - 1))[:slots - 2]
    mixed = rng.permutation(np.concatenate([[0, CAP - 1], inner]))
    past = mixed.copy()
    past[:2] = [CAP, CAP + 7]
    return [mixed, past]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("slots", [1, 2, 32])
def test_the_append_writes_what_the_vmapped_update_wrote(slots, dtype):
    rng = np.random.default_rng(slots)
    cache = jnp.asarray(rng.standard_normal((slots, HK, CAP, D)), dtype)
    # the step's keys arrive in the compute type and are cast to the cache's
    new = jnp.asarray(rng.standard_normal((slots, HK, 1, D)), jnp.float32)
    for lens in _lens(slots, rng):
        lens = jnp.asarray(lens, jnp.int32)
        want = np.asarray(_vmapped_update(cache, new, lens), np.float32)
        for fn in (_cache_update, jax.jit(_cache_update)):
            got = fn(cache, new, lens)
            assert got.dtype == cache.dtype and got.shape == cache.shape
            np.testing.assert_array_equal(np.asarray(got, np.float32), want)
        # exactly one row a slot changed, the others are the cache's
        rows = np.minimum(np.asarray(lens), CAP - 1)
        same = np.ones((slots, CAP), bool)
        same[np.arange(slots), rows] = False
        np.testing.assert_array_equal(
            want.transpose(0, 2, 1, 3)[same],
            np.asarray(cache, np.float32).transpose(0, 2, 1, 3)[same])


def test_the_append_on_a_loop_carry_matches_step_by_step():
    """As the chunk runs it: the cache is a ``fori_loop`` carry and each
    slot's position advances only while it is active."""
    slots, steps = 3, 5
    rng = np.random.default_rng(0)
    cache = jnp.asarray(rng.standard_normal((slots, HK, CAP, D)), jnp.float32)
    news = jnp.asarray(rng.standard_normal((steps, slots, HK, 1, D)), jnp.float32)
    lens0 = jnp.asarray([0, CAP - 3, 17], jnp.int32)
    active = jnp.asarray([1, 1, 0], jnp.int32)

    def run(update):
        def body(i, carry):
            c, lens = carry
            return update(c, news[i], lens), lens + active
        return jax.jit(lambda c: jax.lax.fori_loop(0, steps, body, (c, lens0)))(cache)

    got, want = run(_cache_update), run(_vmapped_update)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
