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


# ------------------------------------------- a block step of two blocks a sequence (PR 32)
def _kernel_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("name=decode_attention")


def test_two_lengths_a_sequence_give_what_two_calls_of_one_length_give():
    """A block step that carries two blocks a sequence
    (``models/causal_lm.py: _block_decode``) lets the first block's queries
    see ``[0, lens + B)`` and the second's ``[0, lens + 2B)`` in ONE call of
    ``decode_attention``, given two lengths a sequence: what two calls with
    one length a sequence give, and what the masked ``jax.numpy`` form gives.
    One block stays the one-length call it was, as a decode step of one token
    a slot (the hybrid's route) does: there the kernel is traced to the
    program it was, with no run of rows in it."""
    from deepspeed_tpu.models.causal_lm import _block_decode
    from deepspeed_tpu.ops.attention.decode import (decode_attention,
                                                    decode_attention_xla)
    b, B, h, hk, d, T = 3, 4, 4, 2, 128, 256
    g = h // hk
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((b, 2 * B, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, T, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, T, d)), jnp.float32)
    # the second block ends in another key block than the first, in the same
    # one, and at the end of the cache
    lens = jnp.asarray([124, 40, T - 2 * B], jnp.int32)

    def rows(x):                # (b, t, h, d) -> the kernel's (b, hk * t * g, d)
        return x.reshape(b, -1, hk, g, d).transpose(0, 2, 1, 3, 4).reshape(b, -1, d)

    def back(o, t=B):
        return o.reshape(b, hk, t, g, d).transpose(0, 2, 1, 3, 4).reshape(b, t, h, d)

    got = _block_decode(q, k, v, lens, B)
    for j in range(2):
        half = q[:, j * B:(j + 1) * B]
        one = back(decode_attention(rows(half), k, v, lens + (j + 1) * B))
        np.testing.assert_allclose(np.asarray(got[:, j * B:(j + 1) * B]),
                                   np.asarray(one), atol=2e-6)
        plain = back(decode_attention_xla(rows(half), k, v, lens + (j + 1) * B))
        np.testing.assert_allclose(np.asarray(one), np.asarray(plain), atol=2e-5)
        # the one-block step: the same call, alone
        np.testing.assert_array_equal(
            np.asarray(_block_decode(half, k, v, lens + j * B, B)), np.asarray(one))
    two = jnp.stack([lens + B, lens + 2 * B], axis=1)
    np.testing.assert_allclose(
        np.asarray(back(decode_attention_xla(rows(q), k, v, two), 2 * B)),
        np.asarray(got), atol=2e-5)
    assert _kernel_calls(lambda q: _block_decode(q, k, v, lens, B), q) == 1
    assert _kernel_calls(lambda q: _block_decode(q, k, v, lens, B), q[:, :B]) == 1
    # the one-length kernel builds no index of runs: its one iota is the
    # key columns' (and its whole trace is the parent's, CHANGES.md, PR 32)
    token = str(jax.make_jaxpr(lambda q: decode_attention(q, k, v, lens + 1))(q[:, 0]))
    runs = str(jax.make_jaxpr(lambda q: decode_attention(q, k, v, two))(rows(q)))
    assert (token.count("iota"), runs.count("iota")) == (1, 2)


@pytest.mark.parametrize("cap,block,rows", [(2048, 4, 2176), (576, 4, 640),
                                            (96, 4, 128), (40, 4, 48), (64, 16, 128)])
def test_the_view_of_a_block_model_holds_two_blocks_past_the_cap(cap, block, rows):
    """``decode_fns.block_view_rows``: a forward appends two blocks at a
    slot's length whatever the slot does, and the append clamps: with the
    spare rows a slot at the cap (finished, idling through a chunk) leaves
    every row below the cap alone, and the decode kernel keeps the key block
    it had at ``cap`` rows."""
    from types import SimpleNamespace
    from deepspeed_tpu.inference.decode_fns import block_view_rows
    T = block_view_rows(SimpleNamespace(gen_block_length=block), cap)
    assert T == rows and T >= cap + 2 * block

    def key_block(T, bk=128):
        bk = min(bk, T)
        while T % bk:
            bk //= 2
        return bk
    assert key_block(T) >= key_block(cap)
    rng = np.random.default_rng(cap)
    cache = jnp.asarray(rng.standard_normal((2, HK, T, D)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((2, HK, 2 * block, D)), jnp.float32)
    lens = jnp.asarray([cap, cap - block], jnp.int32)
    got = np.asarray(_cache_update(cache, new, lens))
    np.testing.assert_array_equal(got[0, :, :cap], np.asarray(cache)[0, :, :cap])
    np.testing.assert_array_equal(got[1, :, :cap - block],
                                  np.asarray(cache)[1, :, :cap - block])
    np.testing.assert_array_equal(got[1, :, cap - block:cap + block], np.asarray(new)[1])
