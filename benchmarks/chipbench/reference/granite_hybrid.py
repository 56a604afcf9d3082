"""A Granite 4.0 hybrid's forward pass (``model_type: granitemoehybrid`` with
``num_local_experts`` 0) in plain float32 ``jax.numpy``: no kernels, no cache,
no batching, no chunking; every matmul at highest precision. It follows the
published config's keys (``model`` below is the configuration's ``model``
section, those keys) and the Mamba-2 mixer of Dao & Gu, arXiv 2405.21060::

    x0 = embedding_multiplier * E[ids]
    per published layer i:
      a = RMSNorm(x; w1_i);  m = Mamba2(a) if layer_types[i] == "mamba" else Attention(a)
      x = x + residual_multiplier * m
      b = RMSNorm(x; w2_i)
      x = x + residual_multiplier * W_out (silu(b W_gate) * (b W_up))
    logits = (RMSNorm(x; w_f) E^T) / logits_scaling          # the tied table

- ``Mamba2``: ``in_proj`` -> [z | xBC | dt] (no bias); causal depthwise
  convolution (``mamba_d_conv`` taps, with bias) over xBC, then SiLU; x
  (``mamba_n_heads`` x ``mamba_d_head``), B and C (``mamba_n_groups`` x
  ``mamba_d_state``: with ONE group the same B and C serve every head);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t`` as a
  ``lax.scan`` over the tokens, one at a time, the state ``(heads, head size,
  state)`` float32; ``RMSNorm`` over each group's slice of ``y * silu(z)``
  (one group: over the whole inner width); ``out_proj`` (no bias).
- ``Attention``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, no bias, NO position encoding
  (``position_embedding_type: nope``), the scores multiplied by
  ``attention_multiplier`` (not by ``1 / sqrt(head size)``), causal softmax.

Departures from the published description: the parameters are read in the
layout of the program's tree (a published layer is ``layers_<2i>`` with
``norm`` and ``mamba`` or ``q_proj``.., then ``layers_<2i+1>`` with ``norm``,
``gate_proj``, ``up_proj``, ``fc_out``; ``wte``; ``ln_f``; ``in_proj`` ordered
[z | xBC | dt] as published); ``time_step_limit`` is (0, inf), so ``dt`` is
not clamped; ``intermediate_size`` (the absent experts') and ``rope_theta``
are not read. This file imports nothing of the program's model code.
"""

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def _eps(model) -> float:
    return float(model.get("rms_norm_eps", 1e-5))


def mamba(a, p, model):
    """The mixer on the normed rows ``a`` (t, d) of one sequence."""
    h, hd = int(model["mamba_n_heads"]), int(model["mamba_d_head"])
    n, g, K = (int(model["mamba_d_state"]), int(model["mamba_n_groups"]),
               int(model["mamba_d_conv"]))
    d_in = h * hd
    conv_dim = d_in + 2 * g * n
    t = a.shape[0]
    proj = a @ p["in_proj"]
    z, xbc, dt = (proj[:, :d_in], proj[:, d_in:d_in + conv_dim],
                  proj[:, d_in + conv_dim:])
    ext = jnp.concatenate([jnp.zeros((K - 1, conv_dim), jnp.float32), xbc])
    conv = silu(sum(ext[k:k + t] * p["conv_w"][k] for k in range(K)) + p["conv_b"])
    xs = conv[:, :d_in].reshape(t, h, hd)
    # a group's B and C serve its h / g heads: broadcast over them
    B = jnp.repeat(conv[:, d_in:d_in + g * n].reshape(t, g, n), h // g, axis=1)
    C = jnp.repeat(conv[:, d_in + g * n:].reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                           # (t, h)
    A = -jnp.exp(p["A_log"])

    def token(state, inp):
        x_t, B_t, C_t, dt_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        y_t = jnp.sum(state * C_t[:, None, :], axis=-1) + p["D"][:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(token, jnp.zeros((h, hd, n), jnp.float32), (xs, B, C, dt))
    y = y.reshape(t, d_in) * silu(z)
    yg = y.reshape(t, g, d_in // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + _eps(model))
    return (yg.reshape(t, d_in) * p["norm_w"]) @ p["out_proj"]


def attention(a, lp, model):
    nh, nk = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    hd = int(model["hidden_size"]) // nh
    t = a.shape[0]
    q = (a @ lp["q_proj"]["kernel"]).reshape(t, nh, hd)
    k = jnp.repeat((a @ lp["k_proj"]["kernel"]).reshape(t, nk, hd), nh // nk, axis=1)
    v = jnp.repeat((a @ lp["v_proj"]["kernel"]).reshape(t, nk, hd), nh // nk, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * float(model["attention_multiplier"])
    pos = jnp.arange(t)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
    return out.reshape(t, nh * hd) @ lp["o_proj"]["kernel"]


def mixer_layer(x, lp, kind, model):
    """``x + residual_multiplier * mixer(RMSNorm(x))``: ``x`` (t, d) float32,
    one sequence; ``kind`` the entry of ``layer_types``."""
    lp = _f32(lp)
    with jax.default_matmul_precision(HI):
        a = rmsnorm(x, lp["norm"]["scale"], _eps(model))
        m = mamba(a, lp["mamba"], model) if kind == "mamba" else attention(a, lp, model)
        return x + float(model["residual_multiplier"]) * m


def mlp_layer(x, lp, model):
    """``x + residual_multiplier * W_out (silu(b W_gate) * (b W_up))``."""
    lp = _f32(lp)
    with jax.default_matmul_precision(HI):
        b = rmsnorm(x, lp["norm"]["scale"], _eps(model))
        y = (silu(b @ lp["gate_proj"]["kernel"]) * (b @ lp["up_proj"]["kernel"])) \
            @ lp["fc_out"]["kernel"]
        return x + float(model["residual_multiplier"]) * y


def head(x, ln_f, table_rows, model):
    """Logits over the vocabulary rows ``table_rows`` of the tied table."""
    with jax.default_matmul_precision(HI):
        return (rmsnorm(x, jnp.asarray(ln_f["scale"], jnp.float32), _eps(model))
                @ _f32(table_rows).T) / float(model["logits_scaling"])


class _Frozen(dict):
    """The model section as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


# one program a layer kind, so that a layer's weights are float32 only inside it
_mixer_jit = jax.jit(mixer_layer, static_argnums=(2, 3))
_mlp_jit = jax.jit(mlp_layer, static_argnums=2)
_head_jit = jax.jit(head, static_argnums=3)


def _layer_types(model):
    return list(model["layer_types"][:int(model["num_hidden_layers"])])


def hidden(params, model, ids):
    """``ids`` (t,) -> the last layer's output ``(t, d)`` float32."""
    model = _Frozen(model)
    x = float(model["embedding_multiplier"]) * jnp.asarray(
        params["wte"][jnp.asarray(ids)], jnp.float32)
    for i, kind in enumerate(_layer_types(model)):
        x = _mixer_jit(x, params[f"layers_{2 * i}"], kind, model)
        x = _mlp_jit(x, params[f"layers_{2 * i + 1}"], model)
    return x


def forward(params, model, ids):
    """One sequence ``ids`` (t,): logits ``(t, vocab)`` float32."""
    return _head_jit(hidden(params, model, ids), params["ln_f"], params["wte"],
                     _Frozen(model))


def next_token_logits(params, model: dict, ids, positions, vocab_block: int = 32768,
                      pad_to: int = 128):
    """Float32 logits ``(len(positions), vocab)`` of one sequence ``ids``
    ``(t,)`` at ``positions``: the mathematics of :func:`forward`, held beside
    a served model's weights: a layer at a time (its weights made float32
    inside its program) and the head in blocks of ``vocab_block`` rows of the
    tied table. The sequence is padded on the right to a multiple of
    ``pad_to`` (attention is causal and the recurrence runs forward, so no
    position asked for sees the padding) to keep the number of compiled shapes
    small."""
    ids = np.concatenate([np.asarray(ids), np.zeros(-len(ids) % pad_to, np.int32)])
    x = hidden(params, model, ids)[jnp.asarray(positions)]
    table = params["wte"]
    blocks = [np.asarray(_head_jit(x, params["ln_f"], table[a:a + vocab_block],
                                   _Frozen(model)))
              for a in range(0, table.shape[0], vocab_block)]
    return np.concatenate(blocks, axis=-1)
