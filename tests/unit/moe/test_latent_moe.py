"""The latent mixture of experts and its grouped kernel at a small size on the
CPU: routing (sigmoid scores, selection bias, normalised and scaled weights),
the dispatch plan, the kernel against its ``jax.numpy`` form, and the test that
ties a share to the model: the four shares of an expert layer, the shared
expert counted once, add up to the uncut reference's layer."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit import hybrid_tiny as ht

REF = ht.reference()


def test_routing_is_sigmoid_scores_a_selection_bias_and_scaled_normalised_weights():
    from deepspeed_tpu.moe.latent_moe import route
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.standard_normal((6, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 16)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.5, jnp.float32)
    idx, wt = route(h, w, bias, top_k=4, scale=2.5, norm=True)
    s = 1.0 / (1.0 + np.exp(-np.asarray(h) @ np.asarray(w)))
    want = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(want, 1))
    # the weights come from the scores alone: the bias selects, it does not weigh
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(wt), chosen / chosen.sum(1, keepdims=True) * 2.5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(wt).sum(1), 2.5, rtol=1e-5)
    # dropping the bias picks other experts
    no_bias, _ = route(h, w, jnp.zeros(16), top_k=4, scale=2.5, norm=True)
    assert not np.array_equal(np.sort(np.asarray(no_bias), 1), np.sort(np.asarray(idx), 1))
    _, raw = route(h, w, bias, top_k=4, scale=1.0, norm=False)
    np.testing.assert_allclose(np.asarray(raw), chosen, rtol=1e-5)


@pytest.mark.parametrize("T,k,n,first,count,tm", [
    (8, 4, 16, 4, 8, 4), (5, 3, 12, 0, 12, 8), (32, 6, 64, 16, 16, 16),
    (3, 2, 8, 6, 2, 4)])
def test_the_dispatch_plan_puts_each_held_assignment_in_a_tile_of_its_expert(
        T, k, n, first, count, tm):
    from deepspeed_tpu.ops.moe.grouped_ffn import dispatch_plan
    rng = np.random.RandomState(T)
    idx = np.stack([rng.permutation(n)[:k] for _ in range(T)]).astype(np.int32)
    valid = np.ones(T, bool)
    valid[-1] = False                                   # a padded token
    plan = jax.tree_util.tree_map(np.asarray, jax.jit(
        dispatch_plan, static_argnums=(1, 2, 3))(
            jnp.asarray(idx), first, count, tm, jnp.asarray(valid)))
    held = (idx >= first) & (idx < first + count) & valid[:, None]
    assert np.array_equal(plan["held"], held)
    assert plan["n_assigned"] == held.sum()
    assert plan["n_touched"] == len(set(idx[held].tolist()))
    NT = plan["tile_expert"].shape[0]
    R = NT * tm
    assert NT == (T * k) // tm + min(count, T * k)
    rows = set()
    for t in range(T):
        for j in range(k):
            r = plan["pos"][t, j]
            if not held[t, j]:
                assert r == R                           # gathers a zero
                continue
            assert r not in rows                        # a row of its own
            rows.add(int(r))
            assert plan["row_token"][r] == t
            assert plan["tile_valid"][r // tm] == 1
            assert plan["tile_expert"][r // tm] == idx[t, j] - first
    n_tiles = int(plan["tile_valid"].sum())
    assert list(plan["tile_valid"]) == [1] * n_tiles + [0] * (NT - n_tiles)
    # experts in order, so every touched expert's tiles are consecutive (read once)
    real = plan["tile_expert"][:n_tiles]
    assert list(real) == sorted(real)
    # tiles past the last real one repeat its expert: nothing new is fetched
    if n_tiles:
        assert set(plan["tile_expert"][n_tiles:]) <= {real[-1]}


@pytest.mark.parametrize("T,tm", [(32, 16), (7, 8)])
def test_the_grouped_kernel_agrees_with_its_jnp_form(T, tm):
    from deepspeed_tpu.moe.latent_moe import relu2
    from deepspeed_tpu.ops.moe.grouped_ffn import (dispatch_plan, grouped_ffn,
                                                   grouped_ffn_xla)
    rng = np.random.RandomState(1)
    k, count, lat, f = 4, 8, 32, 48
    idx = jnp.asarray(np.stack([rng.permutation(16)[:k] for _ in range(T)]), jnp.int32)
    z = jnp.asarray(rng.standard_normal((T, lat)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((count, lat, f)) * 0.2, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((count, f, lat)) * 0.2, jnp.float32)
    plan = dispatch_plan(idx, 4, count, tm)
    x = z[plan["row_token"]]
    a = grouped_ffn(x, plan["tile_expert"], plan["tile_valid"], w1, w2, relu2, tm)
    b = grouped_ffn_xla(x, plan["tile_expert"], plan["tile_valid"], w1, w2, relu2, tm)
    # over the rows of the live tiles: a tile past the last real one is not
    # written (PR 58), and nothing gathers its rows back
    live = np.repeat(np.asarray(plan["tile_valid"]) == 1, tm)
    assert 0 < live.sum() < live.size
    np.testing.assert_allclose(np.asarray(a)[live], np.asarray(b)[live],
                               rtol=1e-5, atol=1e-5)
    # and each held assignment's row is that expert's feed-forward of its token
    pos, held = np.asarray(plan["pos"]), np.asarray(plan["held"])
    for t, j in zip(*np.nonzero(held)):
        e = int(idx[t, j]) - 4
        want = np.square(np.maximum(np.asarray(z[t]) @ np.asarray(w1[e]), 0)) \
            @ np.asarray(w2[e])
        np.testing.assert_allclose(np.asarray(a[pos[t, j]]), want, rtol=1e-4, atol=1e-4)


def _moe_layer(cfg, params, index, x):
    from deepspeed_tpu.models.causal_lm import make_layer
    out, _ = jax.jit(make_layer(cfg, index).apply)(
        {"params": params[f"layers_{index}"]}, x, jnp.arange(x.shape[1])[None])
    return out[0] - x[0]                               # what the mixer adds


def test_the_four_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Expert parallelism's contract, at a small size: each share routes over
    all 16 experts and computes its own 4; the sum of the shares' outputs,
    less the shared expert they all computed, is the whole layer's."""
    whole_cfg = ht.config(experts_held=[0, 16])
    _, whole = ht.init(whole_cfg, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 19, 64), jnp.float32)
    index = 1
    full = REF.moe_layer(x[0], whole[f"layers_{index}"],
                         REF._Frozen({**ht.MODEL, "experts_held": [0, 16]})) - x[0]
    lp = whole[f"layers_{index}"]
    model = REF._Frozen({**ht.MODEL, "experts_held": [0, 16]})
    _, _, shared = REF.moe_route(x[0], lp, model)      # the shared expert alone
    total = jnp.zeros_like(full)
    for first in (0, 4, 8, 12):
        cfg = ht.config(experts_held=[first, 4])
        share = jax.tree_util.tree_map(lambda a: a, whole)
        moe = dict(lp["moe"])
        moe["experts_w1"] = lp["moe"]["experts_w1"][first:first + 4]
        moe["experts_w2"] = lp["moe"]["experts_w2"][first:first + 4]
        share[f"layers_{index}"] = {**lp, "moe": moe}
        part = _moe_layer(cfg, share, index, x)
        # the program's share is the reference's share
        want = REF.moe_layer(x[0], share[f"layers_{index}"],
                             REF._Frozen({**ht.MODEL, "experts_held": [first, 4]})) - x[0]
        routed = float(jnp.abs(part - shared).max())        # its experts' part
        assert routed > 0.1 * float(jnp.abs(shared).max())
        assert float(jnp.abs(part - want).max()) < 1e-4 * routed
        total = total + part
    routed = float(jnp.abs(full - shared).max())
    assert float(jnp.abs(total - 3.0 * shared - full).max()) < 1e-4 * routed


def test_the_layer_counts_its_assignments_and_the_experts_it_touched():
    cfg = ht.config()
    module, params = ht.init(cfg)
    ids = jnp.asarray(ht.ids(12))
    _, sown = jax.jit(lambda v, i: module.apply(v, i, mutable=["stats"]))(
        {"params": params}, ids)
    for name in ("layers_1", "layers_4"):
        (assigned, touched), = sown["stats"][name]["moe_counts"]
        assert 0 < int(touched) <= 8 and int(touched) <= int(assigned) <= 12 * 4
    # padding is routed nowhere: with seq_lens the counts are those of the real tokens
    from deepspeed_tpu.models.causal_lm import init_cache
    pad = jnp.zeros((1, 16), jnp.int32).at[0, :12].set(ids[0])
    lens = jnp.asarray([12])
    _, padded = jax.jit(lambda v, i, c, n: module.apply(
        v, i, caches=c, cache_lens=jnp.zeros_like(n), logits_positions=n - 1,
        seq_lens=n, mutable=["stats"]))({"params": params}, pad,
                                        init_cache(cfg, 1, 32), lens)
    assert int(padded["stats"]["layers_1"]["moe_counts"][0][0]) == \
        int(sown["stats"]["layers_1"]["moe_counts"][0][0])


def test_levelling_finds_the_bias_that_spreads_a_skewed_load():
    """One batch of scores with an offset an expert (what the part common to
    all tokens does to a random router): the rule walks the bias until the
    chosen experts are loaded evenly."""
    from deepspeed_tpu.moe.latent_moe import level_selection_bias
    rng = np.random.RandomState(0)
    offset = rng.standard_normal(32) * 1.5
    scores = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((1024, 32)) + offset,
                                        jnp.float32))
    bias, before, after = level_selection_bias(scores, jnp.zeros((32,)), top_k=4)
    assert float(before) > 3.0 and float(after) < 1.15
    # the bias undoes the offset: experts the router favoured are held back
    assert np.corrcoef(np.asarray(bias), offset)[0, 1] < -0.8
    _, idx = jax.lax.top_k(scores + bias, 4)
    load = np.bincount(np.asarray(idx).ravel(), minlength=32)
    assert load.min() > 0.8 * load.mean()


def test_a_stand_in_is_levelled_layer_by_layer_and_only_where_the_config_asks():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.moe.latent_moe import level_expert_load
    cfg = ht.config()
    module, params = ht.init(cfg)
    skew = jnp.zeros((16,)).at[:4].set(1.5)    # four experts take every token
    params["layers_1"]["moe"]["router_bias"] = skew
    new, before, after = level_expert_load(cfg, params, seed=3, batches=2, tokens=64)
    assert before > 3.0 and after < 1.5
    moved = np.asarray(new["layers_1"]["moe"]["router_bias"])
    assert moved[:4].max() < 1.5 and moved[4:].min() > 0.0
    # the other expert layer's bias moved too; nothing else did
    assert not np.array_equal(np.asarray(new["layers_4"]["moe"]["router_bias"]),
                              np.asarray(params["layers_4"]["moe"]["router_bias"]))
    assert new["layers_0"] is params["layers_0"]
    assert np.array_equal(np.asarray(new["layers_1"]["moe"]["router"]),
                          np.asarray(params["layers_1"]["moe"]["router"]))
    # the levelled model routes a fresh batch more evenly than the skewed one
    ids = jnp.asarray(ht.ids(96, seed=11))

    def touched(p):
        _, sown = module.apply({"params": p}, ids, mutable=["stats"])
        return int(sown["stats"]["layers_1"]["moe_counts"][0][1])
    assert touched(new) > touched(params)
    # an engine draws the seeded bias and levels it only if the config says so
    conf = DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64)
    plain = InferenceEngine(ht.config(), conf, seed=5).params
    level = InferenceEngine(ht.config(level_random_experts=True), conf, seed=5).params
    for name in ("layers_1", "layers_4"):
        seeded = np.asarray(plain[name]["moe"]["router_bias"])
        assert 0.0 < np.abs(seeded).max() < 0.05
        assert not np.array_equal(seeded, np.asarray(level[name]["moe"]["router_bias"]))
    same = jax.tree_util.tree_map(lambda a, b: bool(jnp.array_equal(a, b)), plain, level)
    assert all(v for k, sub in same.items() for kk, v in
               jax.tree_util.tree_leaves_with_path(sub) if "router_bias" not in str(kk))
