"""LFM2's mixture-of-experts model in plain float32 ``jax.numpy``: no kernel,
no cache, no batching; every matmul at highest precision; every held expert
on every token, weighted by the top-k mask. It follows the published
architecture (``model_type: lfm2_moe``).

``h0 = E[ids]``. A published layer ``i``, for ``x`` (t, d): ``x = x +
op_i(rmsnorm(x))``, then ``x = x + ffn_i(rmsnorm(x))``; logits = ``rmsnorm(x)
E^T`` (the head is tied).

- ``conv`` operator: ``[B | C | u] = h W_in``; ``v = B * u``; ``c_t = sum_j
  w[j] * v_{t - (K - 1) + j}`` over ``K = conv_L_cache`` taps, zeros before
  position 0, no activation, no bias; ``out = (C * c) W_out``.
- ``full_attention`` operator: ``q = h W_q``, ``k = h W_k``, ``v = h W_v`` in
  heads of ``hidden_size / num_attention_heads``; ``q, k = rmsnorm(q),
  rmsnorm(k)`` per head with learned weights; a rotation over the whole head
  at base ``rope_theta``, halves paired; causal ``softmax(q k^T /
  sqrt(head))`` over grouped keys and values; ``W_o``.
- feed-forward of the first ``num_dense_layers`` layers: ``W2 (silu(h W1) *
  (h W3))``.
- experts after them: ``s = sigmoid(h W_r)``; the ``num_experts_per_tok``
  largest of ``s + b`` (``b`` the expert bias: the choice only); ``w = s[idx]
  / (sum(s[idx]) + 1e-6)`` where ``norm_topk_prob``; ``w *
  routed_scaling_factor``; ``sum_j w_j W_down[idx_j] (silu(h W_gate[idx_j]) *
  (h W_up[idx_j]))``.

Departures from the published description: the parameters are read in the
layout of the program's tree (a published layer is the pair ``layers_<2i>``
(operator) and ``layers_<2i+1>`` (feed-forward), each ``{norm, ...}``; the
convolution's taps are ``conv_w`` (K, d) with ``conv_w[K-1]`` on the current
position); the first ``num_hidden_layers`` entries of ``layer_types`` are
run (a pipeline's first stage where the depth is cut); only the experts of
``experts_held`` exist, so what absent experts would add is left out (the
configuration holds all of them).
"""

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"
EXPERT_BLOCK = 8           # experts made float32 at a time
TOPK_EPS = 1e-6


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _eps(model) -> float:
    return float(model.get("norm_eps", 1e-5))


class _Frozen(dict):
    """The model section as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def rotate(x, base: float):
    """``x`` (t, heads, d): rotary embedding over all ``d`` dims at positions
    ``0..t-1``, halves paired (``x1, x2 -> x1 cos - x2 sin, x2 cos + x1 sin``)."""
    t, _, d = x.shape
    inv = 1.0 / (base ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None]
    cos, sin = jnp.asarray(np.cos(ang))[:, None, :], jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def conv_layer(x, lp, model):
    lp = _f32(lp)
    d, K = x.shape[-1], int(model.get("conv_L_cache", 3))
    with jax.default_matmul_precision(HI):
        t = x.shape[0]
        proj = rmsnorm(x, lp["norm"]["scale"], _eps(model)) @ lp["conv"]["in_proj"]
        B, C, u = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
        v = jnp.concatenate([jnp.zeros((K - 1, d), jnp.float32), B * u], axis=0)
        c = sum(lp["conv"]["conv_w"][j] * v[j:j + t] for j in range(K))
        return x + (C * c) @ lp["conv"]["out_proj"]


def attention_layer(x, lp, model):
    lp = _f32(lp)
    nh, nk = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    hd = int(model["hidden_size"]) // nh
    eps, base = _eps(model), float(model.get("rope_theta", 1e6))
    with jax.default_matmul_precision(HI):
        t = x.shape[0]
        hn = rmsnorm(x, lp["norm"]["scale"], eps)
        q = (hn @ lp["q_proj"]["kernel"]).reshape(t, nh, hd)
        k = (hn @ lp["k_proj"]["kernel"]).reshape(t, nk, hd)
        v = (hn @ lp["v_proj"]["kernel"]).reshape(t, nk, hd)
        q = rotate(rmsnorm(q, lp["q_norm"]["scale"], eps), base)
        k = rotate(rmsnorm(k, lp["k_norm"]["scale"], eps), base)
        k, v = (jnp.repeat(a, nh // nk, axis=1) for a in (k, v))
        scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores, -jnp.inf)
        attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
        return x + attn.reshape(t, nh * hd) @ lp["o_proj"]["kernel"]


def ffn_layer(x, lp, model):
    lp = _f32(lp)
    with jax.default_matmul_precision(HI):
        hn = rmsnorm(x, lp["norm"]["scale"], _eps(model))
        g = hn @ lp["gate_proj"]["kernel"]
        return x + (g * jax.nn.sigmoid(g) * (hn @ lp["up_proj"]["kernel"])) \
            @ lp["fc_out"]["kernel"]


def _held(model):
    held = model.get("experts_held") or [0, int(model["num_experts"])]
    return int(held[0]), int(held[1])


def moe_route(x, lp, model):
    """Normed input and the dense weights ``(t, held experts)``."""
    first, count = _held(model)
    with jax.default_matmul_precision(HI):
        hn = rmsnorm(x, jnp.asarray(lp["norm"]["scale"], jnp.float32), _eps(model))
        s = jax.nn.sigmoid(hn @ jnp.asarray(lp["moe"]["router"], jnp.float32))
        _, idx = jax.lax.top_k(s + jnp.asarray(lp["moe"]["router_bias"], jnp.float32),
                               int(model["num_experts_per_tok"]))
        w = jnp.take_along_axis(s, idx, axis=-1)
        if model.get("norm_topk_prob", True):
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + TOPK_EPS)
        w = w * float(model.get("routed_scaling_factor", 1.0))
        dense = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(w)
        return hn, dense[:, first:first + count]


def expert_block(hn, weights, gate, up, down):
    """Every expert of the block on every token, weighted: ``(t, d)``."""
    gate, up, down = _f32((gate, up, down))
    with jax.default_matmul_precision(HI):
        g = jnp.einsum("td,edf->etf", hn, gate)
        u = jnp.einsum("td,edf->etf", hn, up)
        y = jnp.einsum("etf,efd->etd", g * jax.nn.sigmoid(g) * u, down)
        return jnp.einsum("te,etd->td", weights, y)


_conv_jit = jax.jit(conv_layer, static_argnums=2)
_attention_jit = jax.jit(attention_layer, static_argnums=2)
_ffn_jit = jax.jit(ffn_layer, static_argnums=2)
_route_jit = jax.jit(moe_route, static_argnums=2)
_block_jit = jax.jit(expert_block)


def moe_layer(x, lp, model, block: int = EXPERT_BLOCK):
    hn, weights = _route_jit(x, lp, model)
    m = lp["moe"]
    out = x
    for a in range(0, m["experts_up"].shape[0], block):
        out = out + _block_jit(hn, weights[:, a:a + block],
                               m["experts_gate"][a:a + block],
                               m["experts_up"][a:a + block],
                               m["experts_down"][a:a + block])
    return out


def head(x, ln_f, embedding_rows, model):
    """Logits over the rows of the (tied) embedding given: ``(t, rows)``."""
    with jax.default_matmul_precision(HI):
        return rmsnorm(x, jnp.asarray(ln_f["scale"], jnp.float32), _eps(model)) \
            @ _f32(embedding_rows).T


_head_jit = jax.jit(head, static_argnums=3)


def hidden(params, model, ids):
    """``ids`` (t,) -> the last layer's output ``(t, d)`` float32."""
    model = _Frozen(model)
    x = jnp.asarray(params["wte"][jnp.asarray(ids)], jnp.float32)
    dense = int(model["num_dense_layers"])
    for i, op in enumerate(model["layer_types"][:int(model["num_hidden_layers"])]):
        mix = {"conv": _conv_jit, "full_attention": _attention_jit}[op]
        x = mix(x, params[f"layers_{2 * i}"], model)
        lp = params[f"layers_{2 * i + 1}"]
        x = _ffn_jit(x, lp, model) if i < dense else moe_layer(x, lp, model)
    return x


def forward(params, model, ids):
    """One sequence ``ids`` (t,): logits ``(t, vocab)`` float32."""
    return _head_jit(hidden(params, model, ids), params["ln_f"], params["wte"],
                     _Frozen(model))


def next_token_logits(params, model: dict, ids, positions, vocab_block: int = 32768,
                      pad_to: int = 64):
    """Float32 logits ``(len(positions), vocab)`` of one sequence ``ids``
    ``(t,)`` at ``positions``: the mathematics of :func:`forward`, held beside
    a served model's weights: a layer at a time (its weights made float32
    inside its program; an expert layer ``EXPERT_BLOCK`` experts at a time)
    and the head in blocks of ``vocab_block`` rows of the embedding. The
    sequence is padded on the right to a multiple of ``pad_to`` (attention is
    causal and the convolution looks back, so no position asked for sees the
    padding) to keep the number of compiled shapes small."""
    ids = np.concatenate([np.asarray(ids), np.zeros(-len(ids) % pad_to, np.int32)])
    x = hidden(params, model, ids)[jnp.asarray(np.asarray(positions))]
    wte = params["wte"]
    blocks = [np.asarray(_head_jit(x, params["ln_f"], wte[a:a + vocab_block],
                                   _Frozen(model)))
              for a in range(0, wte.shape[0], vocab_block)]
    return np.concatenate(blocks, axis=-1)
