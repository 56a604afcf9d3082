"""The expert layer's cost follows what is held (PR 58): the dispatch plan
counted two ways gives one plan, the kernel writes the live tiles alone and
adds its width blocks inside, and nothing reads a row of a dead tile."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.moe import grouped_ffn as g


def _crossover(k: int) -> int:
    """The fewest tokens of ``k`` assignments whose plan counts by prefix sums."""
    T = 1
    while not g.plan_by_prefix_sums(T * k):
        T += 1
    return T


def _routers(case, T, k, n, first, count, rng):
    idx = np.stack([rng.permutation(n)[:k] for _ in range(T)]).astype(np.int32)
    valid = None
    if case == "valid_mask":
        valid = rng.rand(T) > 0.3
    elif case == "none_held":
        idx = (idx % (n - count) + first + count) % n      # every choice elsewhere
    elif case == "one_expert":
        idx[:] = first + count - 1                         # every assignment on one
    return jnp.asarray(idx), None if valid is None else jnp.asarray(valid)


@pytest.mark.parametrize("side", ["below", "above"])
@pytest.mark.parametrize("case,n,first,count", [
    ("share_held", 64, 16, 16), ("all_held", 16, 0, 16), ("valid_mask", 64, 8, 16),
    ("none_held", 64, 16, 16), ("one_expert", 64, 16, 16)])
def test_the_plan_is_one_plan_whichever_way_it_is_counted(
        case, n, first, count, side, monkeypatch):
    """Every field of ``dispatch_plan`` equal between the dense comparisons
    and the prefix sums, at the sizes on both sides of the crossover where
    the one hands over to the other (``plan_by_prefix_sums``: chosen on the
    static ``A`` and ``count`` alone)."""
    k = 8
    T = _crossover(k) - (side == "below")
    assert T * k in (4096, 4104) and g.plan_by_prefix_sums(T * k) is (side == "above")
    tm = g.tile_rows(T * k, n)
    idx, valid = _routers(case, T, k, n, first, count, np.random.RandomState(T + n))
    plans = {}
    for prefix in (False, True):
        monkeypatch.setattr(g, "plan_by_prefix_sums", lambda A: prefix)
        plans[prefix] = jax.tree_util.tree_map(np.asarray, jax.jit(functools.partial(
            g.dispatch_plan, first=first, count=count, tm=tm))(idx, valid=valid))
    for field, dense in plans[False].items():
        assert np.array_equal(dense, plans[True][field]), field
    plan = plans[True]
    held = np.asarray((idx >= first) & (idx < first + count))
    if valid is not None:
        held = held & np.asarray(valid)[:, None]
    assert plan["n_assigned"] == held.sum()
    assert bool(plan["n_assigned"] == 0) is (case == "none_held")
    R = g.plan_rows(T * k, count, n)
    assert plan["row_token"].shape == (R,) and (plan["pos"][~held] == R).all()
    # a held assignment's row is fed by its token
    t, j = np.nonzero(held)
    assert np.array_equal(plan["row_token"][plan["pos"][t, j]], t)
    n_tiles = int(plan["tile_valid"].sum())
    assert n_tiles * tm >= held.sum() and (n_tiles == 0) is (case == "none_held")
    if case == "one_expert":
        assert plan["n_touched"] == 1 and n_tiles == -(-T * k // tm)
        assert (plan["tile_expert"] == count - 1).all()


def _layer_operands(T=48, k=4, n=32, count=8, l=128, f=256, dtype=jnp.bfloat16):
    rng = np.random.RandomState(3)
    idx = jnp.asarray(np.stack([rng.permutation(n)[:k] for _ in range(T)]), jnp.int32)
    x = jnp.asarray(rng.standard_normal((T, l)), dtype)
    w = jnp.asarray(rng.rand(T, k), jnp.float32)
    w1, wg = (jnp.asarray(rng.standard_normal((count, l, f)) * 0.1, dtype)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((count, f, l)) * 0.1, dtype)
    return x, idx, w, w1, w2, wg


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("blocks", [1, 2])
def test_the_kernel_writes_the_live_tiles_and_adds_its_width_blocks_inside(
        blocks, gated, monkeypatch):
    """``grouped_ffn`` (interpret mode here) against ``grouped_ffn_xla`` on
    the rows of the live tiles, whole and cut in two over the experts' width;
    the cut form is bit-equal to the sum of the two calls it replaces (call 1
    is handed call 0's result and adds its product to it in the kernel)."""
    first, count, tm = 8, 8, 8
    x, idx, _, w1, w2, wg = _layer_operands()
    wg = wg if gated else None
    plan = g.dispatch_plan(idx, first, count, tm)
    te, tv = plan["tile_expert"], plan["tile_valid"]
    live = np.repeat(np.asarray(tv) == 1, tm)
    assert 0 < live.sum() < live.size                    # real and dead tiles both
    x_rows = x[plan["row_token"]]
    monkeypatch.setattr(g, "width_blocks", lambda *a: blocks)
    got = np.asarray(g.grouped_ffn(x_rows, te, tv, w1, w2, jax.nn.silu, tm, wg))
    want = np.asarray(g.grouped_ffn_xla(x_rows, te, tv, w1, w2, jax.nn.silu, tm, wg))
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2, atol=2e-2)
    assert np.isfinite(got[live]).all()
    if blocks == 2:
        monkeypatch.setattr(g, "width_blocks", lambda *a: 1)
        fb = w1.shape[2] // 2
        halves = [np.asarray(g.grouped_ffn(
            x_rows, te, tv, w1[:, :, s], w2[:, s], jax.nn.silu, tm,
            None if wg is None else wg[:, :, s]))
            for s in (slice(0, fb), slice(fb, None))]
        assert np.array_equal(got[live], (halves[0] + halves[1])[live])


@pytest.mark.parametrize("blocks", [1, 2])
def test_nothing_reads_a_row_of_a_dead_tile(blocks, monkeypatch):
    """``grouped_experts``' output with the rows of every dead tile poisoned
    with NaN is the output, bit for bit and finite: an assignment not held
    gathers the fill and no row past the last real tile is anyone's."""
    first, count = 8, 8
    x, idx, w, w1, w2, wg = _layer_operands()
    monkeypatch.setattr(g, "width_blocks", lambda *a: blocks)
    served = g.grouped_ffn
    tm = g.tile_rows(idx.size, 32)

    def poisoned(x_rows, te, tv, *a, **kw):
        dead = jnp.repeat(tv == 0, tm)
        assert bool(dead.any())
        return jnp.where(dead[:, None], jnp.nan, served(x_rows, te, tv, *a, **kw))

    want, stats = g.grouped_experts(x, idx, w, first, count, 32, w1, w2,
                                    jax.nn.silu, None, wg)
    monkeypatch.setattr(g, "grouped_ffn", poisoned)
    got, _ = g.grouped_experts(x, idx, w, first, count, 32, w1, w2, jax.nn.silu, None,
                               wg)
    assert np.isfinite(np.asarray(got)).all() and np.array_equal(got, want)
    assert int(stats[0]) == int(((idx >= first) & (idx < first + count)).sum())


@pytest.mark.parametrize("blocks", [1, 2])
def test_a_layer_that_holds_no_assignment_adds_nothing(blocks, monkeypatch):
    """No assignment held (``n_tiles == 0``): the kernel writes nothing,
    nothing is gathered, the layer's share is exactly zero."""
    x, idx, w, w1, w2, wg = _layer_operands()
    monkeypatch.setattr(g, "width_blocks", lambda *a: blocks)
    out, stats = g.grouped_experts(x, idx % 8, w, 8, 8, 32, w1, w2, jax.nn.silu, None,
                                   wg)
    assert not np.asarray(out).any() and list(np.asarray(stats)) == [0, 0]


@pytest.mark.parametrize("assignments,count,experts,rows", [
    (256, 16, 128, (16 + 16) * 16), (32768, 16, 128, (256 + 16) * 128),
    (704, 128, 512, (44 + 128) * 16), (11264, 128, 512, (352 + 128) * 32),
    (3, 8, 8, (0 + 3) * 16)])
def test_the_rows_a_plan_lays_out_are_its_static_worst_case(assignments, count,
                                                            experts, rows):
    assert g.plan_rows(assignments, count, experts) == rows
    tm = g.tile_rows(assignments, experts)
    shapes = jax.eval_shape(functools.partial(g.dispatch_plan, first=0, count=count,
                                              tm=tm),
                            jax.ShapeDtypeStruct((assignments, 1), jnp.int32))
    assert shapes["row_token"].shape == (rows,)
