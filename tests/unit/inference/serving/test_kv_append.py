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


def _unpacked(rows, r):
    """Cache rows (slots, hk / r, T, r * d) -> the heads apart, (slots, hk, T, d)."""
    s, p, T, rd = rows.shape
    return rows.reshape(s, p, T, r, rd // r).transpose(0, 1, 3, 2, 4).reshape(
        s, p * r, T, rd // r)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("slots", [1, 2, 32])
@pytest.mark.parametrize("hk,d,r", [(HK, D, 1), (4, 64, 2)], ids=["a-head-a-row", "two-heads-a-row"])
def test_the_append_writes_what_the_vmapped_update_wrote(slots, dtype, hk, d, r):
    """Whatever the row holds: with ``r`` KV heads side by side in it
    (``ops/paged_attention.heads_per_row``) the step's keys are laid out in
    rows once (``kv_rows``) and the same in-place write puts each head's ``d``
    values where the vmapped update of the heads-apart cache put them."""
    from deepspeed_tpu.ops.paged_attention import heads_per_row, kv_rows
    assert heads_per_row(d, hk) == r
    rng = np.random.default_rng(slots)
    cache = kv_rows(jnp.asarray(rng.standard_normal((slots, CAP, hk, d)), dtype), r)
    assert cache.shape == (slots, hk // r, CAP, r * d)
    # the step's keys arrive in the compute type, as the projection wrote them,
    # and are cast to the cache's
    proj = jnp.asarray(rng.standard_normal((slots, 1, hk, d)), jnp.float32)
    new = kv_rows(proj, r)
    for lens in _lens(slots, rng):
        lens = jnp.asarray(lens, jnp.int32)
        want = np.asarray(_vmapped_update(_unpacked(cache, r), proj.transpose(0, 2, 1, 3),
                                          lens), np.float32)
        for fn in (_cache_update, jax.jit(_cache_update)):
            got = fn(cache, new, lens)
            assert got.dtype == cache.dtype and got.shape == cache.shape
            np.testing.assert_array_equal(np.asarray(_unpacked(got, r), np.float32), want)
        # exactly one row a slot changed, the others are the cache's
        rows = np.minimum(np.asarray(lens), CAP - 1)
        same = np.ones((slots, CAP), bool)
        same[np.arange(slots), rows] = False
        np.testing.assert_array_equal(
            want.transpose(0, 2, 1, 3)[same],
            np.asarray(_unpacked(cache, r), np.float32).transpose(0, 2, 1, 3)[same])


@pytest.mark.parametrize("hk,d,r", [(8, 64, 2), (2, 128, 1)], ids=["d64", "d128"])
def test_the_append_lowers_to_one_full_row_write_a_sequence(hk, d, r):
    """One ``dynamic_update_slice`` a sequence, its update a whole row of ``r
    * d`` = 128 lanes for every row of heads: at d 64 half as many lanes-wide
    writes as heads, none of them half a lane tile."""
    from deepspeed_tpu.ops.paged_attention import heads_per_row
    assert heads_per_row(d, hk) == r
    slots = 5
    cache = jnp.zeros((slots, hk // r, CAP, r * d), jnp.bfloat16)
    new = jnp.zeros((slots, hk // r, 1, r * d), jnp.bfloat16)
    text = jax.jit(_cache_update).lower(cache, new, jnp.zeros((slots,), jnp.int32)).as_text()
    writes = [line for line in text.splitlines() if "dynamic_update_slice" in line]
    assert len(writes) == slots
    assert all(f"tensor<1x{hk // r}x1x128xbf16>" in line for line in writes)
    assert "scatter" not in text and "while" not in text


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


# ------------------------------------------- rows of two heads in the chunk's loop (PR 42)
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from _eqns(inner)


@pytest.mark.parametrize("hidden,heads,hk,r", [(256, 4, 2, 2), (512, 8, 8, 2), (256, 2, 2, 1)],
                         ids=["d64-hk2", "d64-hk8", "d128"])
def test_the_chunks_loop_moves_no_operand_the_size_of_a_view(hidden, heads, hk, r):
    """The decode chunk of the tiny LFM2 at head 64 (and, ``r`` = 1, at 128):
    the pool's pages and the dense view are rows of ``r`` heads, the step
    packs its QUERIES to them, and inside the loop's body nothing as large as
    a layer's view is transposed or reshaped: the cache is read where it
    lies. Outside the loop the gather's page-to-row transpose stays, once a
    chunk."""
    from tests.unit import lfm2_tiny as lt
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    make_slot_select_fn)
    from deepspeed_tpu.inference.serving.kv_pool import PagedKVPool
    from deepspeed_tpu.models.causal_lm import CausalLM
    slots, cap, page, chunk = 3, 32, 8, 4
    cfg = lt.config(hidden_size=hidden, num_attention_heads=heads,
                    num_key_value_heads=hk, max_seq_len=cap)
    d = hidden // heads
    pool = PagedKVPool(cfg, slots, cap, page_size=page)
    assert pool.heads_per_row == r
    (pages,) = [c for c in pool.caches if "k" in c]
    assert pages["k"].shape == (slots * (cap // page) + 1, hk // r, page, r * d)
    module = CausalLM(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    fn = build_paged_decode_chunk(module, lambda p: p,
                                  make_slot_select_fn(False, 1.0, 0, 1.0),
                                  chunk, kv_cap=cap)
    ints = jnp.zeros((slots,), jnp.int32)
    jaxpr = jax.make_jaxpr(fn)(
        params, jnp.zeros((slots, 1), jnp.int32), pool.caches,
        jnp.asarray(pool.page_table), ints, ints.astype(bool), ints, ints, ints, ints,
        jnp.zeros((2,), jnp.uint32)).jaxpr
    # a fori_loop of static trip count is a scan in the jaxpr, a while once lowered
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name in ("while", "scan")]
    view = slots * hk * cap * d

    def cache_sized(e):         # the tiny model's weight matrices are as large: 2-D
        aval = e.invars[0].aval
        return aval.size >= view and aval.ndim >= 4

    moved = [(e.primitive.name, e.invars[0].aval.shape) for inner in _sub_jaxprs(loop)
             for e in _eqns(inner) if e.primitive.name in ("transpose", "reshape")
             and cache_sized(e)]
    assert not moved, moved
    # the view the loop carries is rows of r heads, and each step appends whole rows
    carried = [v.aval.shape for v in loop.invars if getattr(v.aval, "ndim", 0) == 4]
    assert (slots, hk // r, cap, r * d) in carried
    # outside it: the gather's own transpose of the gathered pages, k and v
    outside = [e for e in jaxpr.eqns if e.primitive.name == "transpose" and cache_sized(e)]
    assert len(outside) == 2
