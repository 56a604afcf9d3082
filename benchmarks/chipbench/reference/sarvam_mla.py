"""Sarvam's latent-attention mixture of experts (``model_type: sarvam_mla``:
DeepSeek-V2's layer without a query latent) in plain float32 ``jax.numpy``: no
kernel, no cache, nothing absorbed, no batching; every matmul at highest
precision. ``model`` below is the configuration's ``model`` section, the
published config's keys::

    x0 = E[ids]
    per published layer l:
      u = RMSNorm(x; w1_l)
      q_t = W_q u_t                       -> heads x (nope | rope);   q^r rotated at t
      [c_t ; k^r_t] = W_kva u_t           -> kv_lora_rank | rope;     c_t = RMSNorm(c_t; w_c)
                                             k^r_t rotated at t, ONE for all heads
      [k^n_{t,i} ; v_{t,i}] = W_kvb,i c_t -> nope | v_head_dim for head i
      s_{t,j,i} = scale (q^n_{t,i} . k^n_{j,i} + q^r_{t,i} . k^r_j),  causal softmax over j <= t
      x = x + W_o [sum_j p_{t,j,i} v_{j,i}]_i
      b = RMSNorm(x; w2_l)
      l < first_k_dense_replace:  x = x + W_down (silu(b W_gate) * (b W_up))      # intermediate_size
      else: g = sigmoid(b W_r);  S = the num_experts_per_tok largest of g + bias
            w_e = routed_scaling_factor g_e / (sum over S of g + 1e-20)
            x = x + sum over e in S of w_e Expert_e(b) + Shared(b)                # moe_intermediate_size
    logits = RMSNorm(x; w_f) W_head                                               # untied

Rotary positions are ``deepseek_yarn``'s: frequency ``i`` of ``rope / 2`` is
``f_i = base^(-2i/rope)`` blended with ``f_i / factor`` by the linear ramp
between the dimensions that make ``beta_fast`` and ``beta_slow`` rotations in
``original_max_position_embeddings`` positions; lanes ``(2i, 2i + 1)`` of a
rotary part are one pair, turned in place (a complex multiplication); cos and
sin are multiplied by ``m(factor, mscale) / m(factor, mscale_all_dim)``, ``m(s,
a) = 0.1 a ln s + 1``, and ``scale = (nope + rope)^-1/2 m(factor,
mscale_all_dim)^2``.

The chip's share: ``model["experts_held"] = [first, count]`` says which experts'
matrices the parameters hold. The router scores ALL ``num_experts`` and chooses
among all; an expert held elsewhere adds nothing here, as in the program, and
that partial sum goes on to the next layer. The shared expert is every chip's
and is added whole. ``vocab_size`` is the rows held: logits are over them.

Departures from the published description: the parameters are read in the
layout of the program's tree (a published layer is ``layers_<2l>`` with
``norm``, ``q_proj``, ``kv_a_proj``, ``kv_a_norm``, ``kv_b_k`` (rank, heads,
nope) and ``kv_b_v`` (rank, heads, v): ``kv_b_proj``'s two halves a head kept
apart, ``o_proj``; then ``layers_<2l+1>`` with ``norm`` and ``gate_proj`` /
``up_proj`` / ``fc_out`` or ``moe``: ``router`` (d, experts), ``router_bias``,
``experts_gate`` / ``experts_up`` (held, d, width), ``experts_down``,
``shared_gate`` / ``shared_up`` / ``shared_down``); attention runs a block of
queries at a time so that a 4k prompt's scores fit; the experts are computed
densely, ``EXPERT_BLOCK`` at a time over every token with the weights of
tokens that did not choose an expert at 0. This file imports nothing of the
program's model code.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"
EXPERT_BLOCK = 4           # experts made float32 at a time
QUERY_BLOCK = 512          # queries scored at a time
TOPK_EPS = 1e-20


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def _eps(model) -> float:
    return float(model.get("rms_norm_eps", 1e-6))


class _Frozen(dict):
    """The model section as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def mscale(s: float, a: float) -> float:
    return 0.1 * a * math.log(s) + 1.0 if s > 1 else 1.0


def rope_tables(model, t: int):
    """``(cos, sin)`` ``(t, rope / 2)`` at positions ``0..t-1`` and the
    attention's score scale."""
    dim = int(model["qk_rope_head_dim"])
    base = float(model.get("rope_theta", 10000.0))
    scale = (int(model["qk_nope_head_dim"]) + dim) ** -0.5
    freq = base ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    m = 1.0
    rs = model.get("rope_scaling")
    if rs:
        factor, orig = float(rs["factor"]), float(rs["original_max_position_embeddings"])

        def dim_of(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(dim_of(float(rs.get("beta_fast", 32)))), 0)
        high = min(math.ceil(dim_of(float(rs.get("beta_slow", 1)))), dim - 1)
        high = high + 0.001 if low == high else high
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
        freq = freq * (1 - ramp) + freq / factor * ramp
        all_dim = float(rs.get("mscale_all_dim", 0))
        m = mscale(factor, float(rs.get("mscale", 1))) / mscale(factor, all_dim)
        if all_dim:
            scale *= mscale(factor, all_dim) ** 2
    ang = np.arange(t, dtype=np.float64)[:, None] * freq[None]
    return (np.cos(ang) * m).astype(np.float32), (np.sin(ang) * m).astype(np.float32), scale


def rotate(x, cos, sin):
    """``x (t, heads, rope)``: each pair of lanes ``(2i, 2i + 1)`` turned in
    place by the position's angle ``i``."""
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


def attention_layer(x, lp, model, query_block: int = QUERY_BLOCK):
    lp = _f32(lp)
    H = int(model["num_attention_heads"])
    rank, nope = int(model["kv_lora_rank"]), int(model["qk_nope_head_dim"])
    rope, dv = int(model["qk_rope_head_dim"]), int(model["v_head_dim"])
    t = x.shape[0]
    cos, sin, scale = rope_tables(model, t)
    with jax.default_matmul_precision(HI):
        u = rmsnorm(x, lp["norm"]["scale"], _eps(model))
        q = (u @ lp["q_proj"]["kernel"]).reshape(t, H, nope + rope)
        kva = u @ lp["kv_a_proj"]["kernel"]
        c = rmsnorm(kva[:, :rank], lp["kv_a_norm"]["scale"], _eps(model))
        k_r = rotate(kva[:, None, rank:], cos, sin)[:, 0]              # (t, rope)
        q_n, q_r = q[..., :nope], rotate(q[..., nope:], cos, sin)
        k_n = jnp.einsum("tl,lhn->thn", c, lp["kv_b_k"])
        v = jnp.einsum("tl,lhv->thv", c, lp["kv_b_v"])
        out = []
        for a in range(0, t, query_block):
            z = slice(a, min(a + query_block, t))
            s = scale * (jnp.einsum("thn,shn->hts", q_n[z], k_n)
                         + jnp.einsum("thr,sr->hts", q_r[z], k_r))
            seen = jnp.arange(t)[None, :] <= jnp.arange(z.start, z.stop)[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("hts,shv->thv", p, v))
        o = jnp.concatenate(out, axis=0).reshape(t, H * dv)
        return x + o @ lp["o_proj"]["kernel"]


def ffn_layer(x, lp, model):
    lp = _f32(lp)
    with jax.default_matmul_precision(HI):
        b = rmsnorm(x, lp["norm"]["scale"], _eps(model))
        return x + (silu(b @ lp["gate_proj"]["kernel"]) * (b @ lp["up_proj"]["kernel"])) \
            @ lp["fc_out"]["kernel"]


def held(model) -> tuple:
    first, count = model.get("experts_held") or (0, int(model["num_experts"]))
    return int(first), int(count)


def moe_route(x, lp, model):
    """Normed input and the dense weights ``(t, held experts)``."""
    first, count = held(model)
    with jax.default_matmul_precision(HI):
        b = rmsnorm(x, jnp.asarray(lp["norm"]["scale"], jnp.float32), _eps(model))
        g = jax.nn.sigmoid(b @ jnp.asarray(lp["moe"]["router"], jnp.float32))
        _, idx = jax.lax.top_k(g + jnp.asarray(lp["moe"]["router_bias"], jnp.float32),
                               int(model["num_experts_per_tok"]))
        w = jnp.take_along_axis(g, idx, axis=-1)
        if model.get("norm_topk_prob", True):
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + TOPK_EPS)
        w = w * float(model.get("routed_scaling_factor", 1.0))
        dense = jnp.zeros_like(g).at[jnp.arange(x.shape[0])[:, None], idx].set(w)
        return b, dense[:, first:first + count]


def expert_block(b, weights, gate, up, down):
    """Every expert of the block on every token, weighted: ``(t, d)``."""
    gate, up, down = _f32((gate, up, down))
    with jax.default_matmul_precision(HI):
        y = jnp.einsum("etf,efd->etd", silu(jnp.einsum("td,edf->etf", b, gate))
                       * jnp.einsum("td,edf->etf", b, up), down)
        return jnp.einsum("te,etd->td", weights, y)


def shared_expert(b, gate, up, down):
    gate, up, down = _f32((gate, up, down))
    with jax.default_matmul_precision(HI):
        return (silu(b @ gate) * (b @ up)) @ down


_attention_jit = jax.jit(attention_layer, static_argnums=(2, 3))
_ffn_jit = jax.jit(ffn_layer, static_argnums=2)
_route_jit = jax.jit(moe_route, static_argnums=2)
_block_jit = jax.jit(expert_block)
_shared_jit = jax.jit(shared_expert)


def moe_layer(x, lp, model, block: int = EXPERT_BLOCK):
    b, weights = _route_jit(x, lp, _Frozen(model))
    m = lp["moe"]
    out = x + _shared_jit(b, m["shared_gate"], m["shared_up"], m["shared_down"])
    for a in range(0, m["experts_up"].shape[0], block):
        out = out + _block_jit(b, weights[:, a:a + block],
                               m["experts_gate"][a:a + block],
                               m["experts_up"][a:a + block],
                               m["experts_down"][a:a + block])
    return out


def head(x, ln_f, kernel, model):
    with jax.default_matmul_precision(HI):
        return rmsnorm(x, jnp.asarray(ln_f["scale"], jnp.float32), _eps(model)) \
            @ jnp.asarray(kernel, jnp.float32)


_head_jit = jax.jit(head, static_argnums=3)


def hidden(params, model, ids):
    """``ids`` (t,) -> the last layer's output ``(t, d)`` float32."""
    model = _Frozen(model)
    x = jnp.asarray(params["wte"][jnp.asarray(ids)], jnp.float32)
    dense = int(model.get("first_k_dense_replace", 1))
    for i in range(int(model["num_hidden_layers"])):
        x = _attention_jit(x, params[f"layers_{2 * i}"], model, QUERY_BLOCK)
        lp = params[f"layers_{2 * i + 1}"]
        x = _ffn_jit(x, lp, model) if i < dense else moe_layer(x, lp, model)
    return x


def forward(params, model, ids):
    """One sequence ``ids`` (t,): logits ``(t, vocab)`` float32."""
    return _head_jit(hidden(params, model, ids), params["ln_f"],
                     params["lm_head"]["kernel"], _Frozen(model))


def next_token_logits(params, model: dict, ids, positions, pad_to: int = 64):
    """Float32 logits ``(len(positions), vocab)`` of one sequence ``ids``
    ``(t,)`` at ``positions``: the mathematics of :func:`forward`, held beside
    a served model's weights: a layer at a time (its weights made float32
    inside its program; an expert layer ``EXPERT_BLOCK`` experts at a time).
    The sequence is padded on the right to a multiple of ``pad_to``
    (attention is causal, so no position asked for sees the padding) to keep
    the number of compiled shapes small."""
    ids = np.concatenate([np.asarray(ids), np.zeros(-len(ids) % pad_to, np.int32)])
    x = hidden(params, model, ids)[jnp.asarray(np.asarray(positions))]
    return np.asarray(_head_jit(x, params["ln_f"], params["lm_head"]["kernel"],
                                _Frozen(model)))
