"""The Nemotron-H hybrid's forward pass in plain float32 ``jax.numpy``: no
kernels, no cache, no batching, no chunking; every matmul at highest
precision. It follows the published architecture (``model_type: nemotron_h``;
the mixers of Mamba-2, Dao & Gu, arXiv 2405.21060, and of DeepSeek-V3-style
routing, arXiv 2412.19437): word embeddings; per layer ``x = x + mixer(
RMSNorm(x))`` with ONE mixer chosen by the letter of
``hybrid_override_pattern``; a final RMSNorm; an untied head.

- ``M``: ``in_proj`` -> [z | xBC | dt]; causal depthwise convolution (kernel
  ``conv_kernel``, with bias) over xBC, then SiLU; x (heads x head_dim), B and
  C (groups x state); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t``, ``y_t = C_t
  . h_t + D x_t`` as a ``lax.scan`` over the tokens, one at a time;
  ``RMSNorm`` over each group's slice of ``y * silu(z)``; ``out_proj``.
- ``*``: causal attention, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, no position encoding, no bias.
- ``E``: ``s = sigmoid(h W_r)``; the ``num_experts_per_tok`` largest of ``s +
  bias``; weights ``s[chosen] / sum x routed_scaling_factor``; ``z = h
  W_down``; ``sum_k w_k W2_e relu(W1_e z)^2`` over the chosen experts THIS
  share holds (``experts_held = [first, count]``; every held expert is
  computed for every token and weighted, mostly by zero); ``W_up``; plus the
  shared expert ``W2_s relu(W1_s h)^2`` on the full width.

Departures from the published description: the parameters are read in the
layout of the program's tree (``layers_<i>/{norm, mamba | q_proj.. | moe}``;
``in_proj`` ordered [z | xBC | dt] as published); only the experts of
``experts_held`` exist, so what the absent experts would add is left out, as
in the program; attention has no position encoding (the family uses none;
``rope_theta`` is not read); the multi-token-prediction module is no layer of
the pattern and is absent; ``time_step_limit`` is (0, inf), so ``dt`` is not
clamped.
"""

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"
EXPERT_BLOCK = 16          # held experts made float32 at a time


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _eps(model) -> float:
    return float(model.get("layer_norm_epsilon", 1e-5))


def mamba_layer(x, lp, model):
    """``x`` (t, d) float32, one sequence."""
    lp = _f32(lp)
    p = lp["mamba"]
    h, hd = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    n, g, K = (int(model["ssm_state_size"]), int(model["n_groups"]),
               int(model["conv_kernel"]))
    d_in = h * hd
    conv_dim = d_in + 2 * g * n
    with jax.default_matmul_precision(HI):
        t = x.shape[0]
        proj = rmsnorm(x, lp["norm"]["scale"], _eps(model)) @ p["in_proj"]
        z, xbc, dt = (proj[:, :d_in], proj[:, d_in:d_in + conv_dim],
                      proj[:, d_in + conv_dim:])
        ext = jnp.concatenate([jnp.zeros((K - 1, conv_dim), jnp.float32), xbc])
        conv = sum(ext[k:k + t] * p["conv_w"][k] for k in range(K)) + p["conv_b"]
        conv = conv * jax.nn.sigmoid(conv)                            # SiLU
        xs = conv[:, :d_in].reshape(t, h, hd)
        B = jnp.repeat(conv[:, d_in:d_in + g * n].reshape(t, g, n), h // g, axis=1)
        C = jnp.repeat(conv[:, d_in + g * n:].reshape(t, g, n), h // g, axis=1)
        dt = jax.nn.softplus(dt + p["dt_bias"])                       # (t, h)
        A = -jnp.exp(p["A_log"])

        def token(state, inp):
            x_t, B_t, C_t, dt_t = inp
            state = jnp.exp(dt_t * A)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
            y_t = jnp.sum(state * C_t[:, None, :], axis=-1) + p["D"][:, None] * x_t
            return state, y_t

        _, y = jax.lax.scan(token, jnp.zeros((h, hd, n), jnp.float32),
                            (xs, B, C, dt))
        y = y.reshape(t, d_in) * (z * jax.nn.sigmoid(z))
        yg = y.reshape(t, g, d_in // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                + _eps(model))
        return x + (yg.reshape(t, d_in) * p["norm_w"]) @ p["out_proj"]


def attention_layer(x, lp, model):
    lp = _f32(lp)
    nh, nk, hd = (int(model["num_attention_heads"]),
                  int(model["num_key_value_heads"]), int(model["head_dim"]))
    with jax.default_matmul_precision(HI):
        t = x.shape[0]
        hn = rmsnorm(x, lp["norm"]["scale"], _eps(model))
        q = (hn @ lp["q_proj"]["kernel"]).reshape(t, nh, hd)
        k = jnp.repeat((hn @ lp["k_proj"]["kernel"]).reshape(t, nk, hd),
                       nh // nk, axis=1)
        v = jnp.repeat((hn @ lp["v_proj"]["kernel"]).reshape(t, nk, hd),
                       nh // nk, axis=1)
        scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
        pos = jnp.arange(t)
        scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
        attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
        return x + attn.reshape(t, nh * hd) @ lp["o_proj"]["kernel"]


def _held(model):
    held = model.get("experts_held") or [0, int(model["n_routed_experts"])]
    return int(held[0]), int(held[1])


def moe_route(x, lp, model):
    """Normed input, dense weights ``(t, held experts)`` and latent ``z``."""
    p = _f32({k: v for k, v in lp["moe"].items() if not k.startswith("experts_")})
    first, count = _held(model)
    k = int(model["num_experts_per_tok"])
    with jax.default_matmul_precision(HI):
        hn = rmsnorm(x, jnp.asarray(lp["norm"]["scale"], jnp.float32), _eps(model))
        s = jax.nn.sigmoid(hn @ p["router"])
        _, idx = jax.lax.top_k(s + p["router_bias"], k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if model.get("norm_topk_prob", True):
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * float(model["routed_scaling_factor"])
        dense = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(w)
        shared = relu2(hn @ p["shared_w1"]) @ p["shared_w2"]
        return dense[:, first:first + count], hn @ p["down"], shared


def expert_block(z, weights, w1, w2, model):
    """Every expert of the block on every token, weighted: ``(t, latent)``."""
    w1, w2 = _f32((w1, w2))
    with jax.default_matmul_precision(HI):
        y = jnp.einsum("etf,efl->etl", relu2(jnp.einsum("tl,elf->etf", z, w1)), w2)
        return jnp.einsum("te,etl->tl", weights, y)


def moe_finish(x, r, shared, up, model):
    with jax.default_matmul_precision(HI):
        return x + r @ _f32(up) + shared


_mamba_jit = jax.jit(mamba_layer, static_argnums=2)
_attention_jit = jax.jit(attention_layer, static_argnums=2)
_route_jit = jax.jit(moe_route, static_argnums=2)
_block_jit = jax.jit(expert_block, static_argnums=4)
_finish_jit = jax.jit(moe_finish, static_argnums=4)


class _Frozen(dict):
    """The model section as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def moe_layer(x, lp, model, block: int = EXPERT_BLOCK):
    weights, z, shared = _route_jit(x, lp, model)
    w1, w2 = lp["moe"]["experts_w1"], lp["moe"]["experts_w2"]
    r = jnp.zeros_like(z)
    for a in range(0, w1.shape[0], block):
        r = r + _block_jit(z, weights[:, a:a + block], w1[a:a + block],
                           w2[a:a + block], model)
    return _finish_jit(x, r, shared, lp["moe"]["up"], model)


def head(x, ln_f, kernel_cols, model):
    with jax.default_matmul_precision(HI):
        return rmsnorm(x, jnp.asarray(ln_f["scale"], jnp.float32), _eps(model)) \
            @ _f32(kernel_cols)


_head_jit = jax.jit(head, static_argnums=3)


def hidden(params, model, ids):
    """``ids`` (t,) -> the last layer's output ``(t, d)`` float32."""
    model = _Frozen(model)
    x = jnp.asarray(params["wte"][jnp.asarray(ids)], jnp.float32)
    for i, kind in enumerate(model["hybrid_override_pattern"]):
        lp = params[f"layers_{i}"]
        if kind == "M":
            x = _mamba_jit(x, lp, model)
        elif kind == "*":
            x = _attention_jit(x, lp, model)
        elif kind == "E":
            x = moe_layer(x, lp, model)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return x


def forward(params, model, ids):
    """One sequence ``ids`` (t,): logits ``(t, vocab)`` float32."""
    return _head_jit(hidden(params, model, ids), params["ln_f"],
                     params["lm_head"]["kernel"], _Frozen(model))


def next_token_logits(params, model: dict, ids, positions, vocab_block: int = 32768,
                      pad_to: int = 128):
    """Float32 logits ``(len(positions), vocab)`` of one sequence ``ids``
    ``(t,)`` at ``positions``: the mathematics of :func:`forward`, held beside
    a served model's weights: a layer at a time (its weights made float32
    inside its program; an expert layer ``EXPERT_BLOCK`` experts at a time)
    and the head in blocks of ``vocab_block`` columns. The sequence is padded
    on the right to a multiple of ``pad_to`` (attention is causal and the
    recurrence runs forward, so no position asked for sees the padding) to
    keep the number of compiled shapes small."""
    ids = np.concatenate([np.asarray(ids), np.zeros(-len(ids) % pad_to, np.int32)])
    x = hidden(params, model, ids)[jnp.asarray(positions)]
    kernel = params["lm_head"]["kernel"]
    blocks = [np.asarray(_head_jit(x, params["ln_f"], kernel[:, a:a + vocab_block],
                                   _Frozen(model)))
              for a in range(0, kernel.shape[1], vocab_block)]
    return np.concatenate(blocks, axis=-1)
