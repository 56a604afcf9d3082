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
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    pos, held = np.asarray(plan["pos"]), np.asarray(plan["held"])
    for t, j in zip(*np.nonzero(held)):
        e = int(idx[t, j]) - 4
        g = np.asarray(z[t]) @ np.asarray(gate[e])
        want = (g / (1 + np.exp(-g)) * (np.asarray(z[t]) @ np.asarray(up[e]))) \
            @ np.asarray(down[e])
        np.testing.assert_allclose(np.asarray(a[pos[t, j]]), want, rtol=1e-4, atol=1e-4)
    dead = np.repeat(np.asarray(plan["tile_valid"]) == 0, tm)
    assert not np.asarray(a)[dead].any()
    plain = grouped_ffn(*args)
    assert np.abs(np.asarray(plain) - np.asarray(a)).max() > 1e-2
    np.testing.assert_allclose(np.asarray(plain), np.asarray(grouped_ffn_xla(*args)),
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
