"""The softmax-routed mixture of gated experts (``moe/gated_moe.py``) and the
gated form of the grouped expert kernel (``ops/moe/grouped_ffn.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("norm", [True, False])
def test_routing_is_softmax_top_k_renormalised_over_the_chosen(norm):
    from deepspeed_tpu.moe.gated_moe import route_softmax
    rng = np.random.RandomState(0)
    h = rng.standard_normal((9, 16)).astype(np.float32)
    w = rng.standard_normal((16, 12)).astype(np.float32)
    idx, wt = route_softmax(jnp.asarray(h), jnp.asarray(w), 3, norm)
    z = h @ w
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    for t in range(9):
        top = np.argsort(-p[t])[:3]
        assert list(np.asarray(idx[t])) == list(top)
        want = p[t, top] / (p[t, top].sum() if norm else 1.0)
        np.testing.assert_allclose(np.asarray(wt[t]), want, rtol=1e-5)
    if norm:
        np.testing.assert_allclose(np.asarray(wt).sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("T,tm", [(32, 16), (7, 8)])
def test_the_gated_kernel_agrees_with_its_jnp_form_and_with_each_expert(T, tm):
    """``moe_grouped_ffn`` with a gate block (interpret mode here) against
    ``grouped_ffn_xla`` and, row by row, against ``W_down (silu(x W_gate) *
    (x W_up))`` of the row's expert; without a gate it is the two-matrix form
    unchanged."""
    from deepspeed_tpu.ops.moe.grouped_ffn import (dispatch_plan, grouped_ffn,
                                                   grouped_ffn_xla)
    rng = np.random.RandomState(1)
    k, count, d, f = 4, 8, 32, 48
    idx = jnp.asarray(np.stack([rng.permutation(16)[:k] for _ in range(T)]), jnp.int32)
    z = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((count, d, f)) * 0.2, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.standard_normal((count, f, d)) * 0.2, jnp.float32)
    plan = dispatch_plan(idx, 4, count, tm)
    x = z[plan["row_token"]]
    args = (x, plan["tile_expert"], plan["tile_valid"], up, down, jax.nn.silu, tm)
    a = grouped_ffn(*args, w_gate=gate)
    b = grouped_ffn_xla(*args, w_gate=gate)
    # rows of the live tiles: a tile past the last real one is not written
    live = np.repeat(np.asarray(plan["tile_valid"]) == 1, tm)
    assert 0 < live.sum() < live.size
    np.testing.assert_allclose(np.asarray(a)[live], np.asarray(b)[live],
                               rtol=1e-5, atol=1e-5)
    pos, held = np.asarray(plan["pos"]), np.asarray(plan["held"])
    for t, j in zip(*np.nonzero(held)):
        e = int(idx[t, j]) - 4
        g = np.asarray(z[t]) @ np.asarray(gate[e])
        want = (g / (1 + np.exp(-g)) * (np.asarray(z[t]) @ np.asarray(up[e]))) \
            @ np.asarray(down[e])
        np.testing.assert_allclose(np.asarray(a[pos[t, j]]), want, rtol=1e-4, atol=1e-4)
    plain = grouped_ffn(*args)
    assert np.abs(np.asarray(plain)[live] - np.asarray(a)[live]).max() > 1e-2
    np.testing.assert_allclose(np.asarray(plain)[live],
                               np.asarray(grouped_ffn_xla(*args))[live],
                               rtol=1e-5, atol=1e-5)


def test_padding_is_routed_nowhere_and_the_layer_counts_what_it_touched():
    from deepspeed_tpu.moe.gated_moe import GatedMoE
    layer = GatedMoE(d_model=32, n_routed=8, top_k=2, expert_width=16, norm_topk=True,
                     experts_held=(0, 8), dtype=jnp.float32, init_std=0.3, out_std=0.3)
    h = jnp.asarray(np.random.RandomState(2).standard_normal((2, 6, 32)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    valid = jnp.arange(6)[None, :] < jnp.asarray([6, 2])[:, None]
    out, stats = layer.apply({"params": params}, h, valid)
    assert int(stats[0]) == (6 + 2) * 2 and 1 <= int(stats[1]) <= 8
    assert not np.asarray(out[1, 2:]).any()          # the padded rows get nothing
    whole, _ = layer.apply({"params": params}, h)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(whole[0]), atol=1e-6)


# ------------------------------------------ the sigmoid router with an expert bias
def _sigmoid_layer(held=(0, 32), **over):
    from deepspeed_tpu.moe.gated_moe import GatedMoE
    return GatedMoE(**{**dict(
        d_model=32, n_routed=32, top_k=4, expert_width=16, norm_topk=True,
        experts_held=held, dtype=jnp.float32, init_std=0.3, out_std=0.3,
        router="sigmoid_bias", scale=1.0, topk_eps=1e-6), **over})


@pytest.mark.parametrize("norm,scale", [(True, 1.0), (True, 2.5), (False, 1.0)])
def test_the_bias_moves_the_choice_and_never_the_weight(norm, scale):
    """``latent_moe.route`` with the epsilon as an argument: the top k of
    score + bias are chosen, the weights are the chosen experts' SCORES over
    their sum + eps (1e-6 for LFM2, 1e-20 where none is given), times the
    scale."""
    from deepspeed_tpu.moe.latent_moe import route
    rng = np.random.RandomState(3)
    h = rng.standard_normal((11, 16)).astype(np.float32)
    w = rng.standard_normal((16, 12)).astype(np.float32)
    bias = rng.standard_normal(12).astype(np.float32)
    s = 1.0 / (1.0 + np.exp(-(h @ w)))
    idx, wt = route(jnp.asarray(h), jnp.asarray(w), jnp.asarray(bias), 3, scale, norm, 1e-6)
    plain, _ = route(jnp.asarray(h), jnp.asarray(w), jnp.zeros(12), 3, scale, norm, 1e-6)
    assert not np.array_equal(np.asarray(idx), np.asarray(plain))    # the choice moved
    for t in range(11):
        top = np.argsort(-(s[t] + bias))[:3]
        assert sorted(np.asarray(idx[t])) == sorted(top)
        chosen = s[t, np.asarray(idx[t])]
        want = chosen / (chosen.sum() + 1e-6) * scale if norm else chosen * scale
        np.testing.assert_allclose(np.asarray(wt[t]), want, rtol=1e-5)
    # the epsilon is the argument's: a thousandth of the sum shows, 1e-20 does not
    _, coarse = route(jnp.asarray(h), jnp.asarray(w), jnp.asarray(bias), 3, scale, True, 1e-3)
    _, fine = route(jnp.asarray(h), jnp.asarray(w), jnp.asarray(bias), 3, scale, True)
    assert np.all(np.asarray(coarse) < np.asarray(fine))
    np.testing.assert_allclose(np.asarray(fine).sum(-1), scale, rtol=1e-6)


def test_the_router_is_data_of_the_gated_layer():
    """``router="sigmoid_bias"`` gives the layer an expert bias and the
    sigmoid's weights; ``"softmax"`` (SDAR's, the default) has neither."""
    h = jnp.asarray(np.random.RandomState(4).standard_normal((1, 9, 32)), jnp.float32)
    layer = _sigmoid_layer()
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    assert params["router_bias"].shape == (32,)
    soft = _sigmoid_layer(router="softmax")
    assert "router_bias" not in soft.init(jax.random.PRNGKey(0), h)["params"]
    out, stats = layer.apply({"params": params}, h)
    assert int(stats[0]) == 9 * 4
    moved = dict(params, router_bias=params["router_bias"].at[:4].add(5.0))
    out2, _ = layer.apply({"params": moved}, h)
    assert np.abs(np.asarray(out2) - np.asarray(out)).max() > 1e-3
    # a bias common to every expert moves no choice, and no weight either
    same, _ = layer.apply({"params": dict(params, router_bias=params["router_bias"] + 7.0)}, h)
    np.testing.assert_allclose(np.asarray(same), np.asarray(out), atol=1e-6)
    rest = {k: v for k, v in params.items() if k != "router_bias"}
    other, _ = soft.apply({"params": rest}, h)
    assert np.abs(np.asarray(other) - np.asarray(out)).max() > 1e-3


def test_the_shares_of_four_chips_add_up_to_the_uncut_reference_layer():
    """THE SHARE TEST for LFM2's layer: ``experts_held`` tells a program its
    share; the outputs of the shares (0, 8) (8, 8) (16, 8) (24, 8) add up to
    what the plain reference (``benchmarks/chipbench/reference/lfm2_moe.py``,
    every one of the 32 experts on every token) gives for the whole layer."""
    from tests.unit import lfm2_tiny as lt
    ref = lt.reference()
    h = jnp.asarray(np.random.RandomState(5).standard_normal((1, 16, 32)), jnp.float32)
    params = _sigmoid_layer().init(jax.random.PRNGKey(1), h)["params"]
    params = dict(params, router_bias=0.3 * jax.random.normal(jax.random.PRNGKey(2), (32,)))

    def share(first, count):
        p = {"router": params["router"], "router_bias": params["router_bias"],
             **{k: params[k][first:first + count]
                for k in ("experts_gate", "experts_up", "experts_down")}}
        return _sigmoid_layer(held=(first, count)).apply({"params": p}, h)

    whole, stats = share(0, 32)
    parts = [share(a, 8) for a in (0, 8, 16, 24)]
    assert np.abs(sum(np.asarray(o) for o, _ in parts) - np.asarray(whole)).max() < 1e-5
    assert sum(int(s[0]) for _, s in parts) == int(stats[0]) == 16 * 4
    assert max(int(s[0]) for _, s in parts) < 16 * 4           # no share is the whole
    model = ref._Frozen(hidden_size=32, num_experts=32, num_experts_per_tok=4,
                        norm_topk_prob=True, routed_scaling_factor=1.0, norm_eps=1e-5)
    x = h[0]            # the reference norms its input; the layer is given the normed one
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)
    want = ref.moe_layer(x, {"norm": {"scale": jnp.ones(32)}, "moe": params}, model) - x
    got, _ = _sigmoid_layer().apply({"params": params}, normed[None])
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-4
    assert np.abs(sum(np.asarray(o) for o, _ in [
        _sigmoid_layer(held=(a, 8)).apply({"params": {
            "router": params["router"], "router_bias": params["router_bias"],
            **{k: params[k][a:a + 8] for k in ("experts_gate", "experts_up",
                                               "experts_down")}}}, normed[None])
        for a in (0, 8, 16, 24)]) - np.asarray(want)[None]).max() < 1e-4
