"""Phi-4-mini-flash's forward pass (``model_type: phi4flash``; SambaY, Ren et
al., arXiv 2507.06607) in plain float32 ``jax.numpy``: no kernels, no cache, no
ring, no batching; every matmul at highest precision; EVERY layer at EVERY
position. ``model`` below is the configuration's ``model`` section, the
published config's keys::

    x = E[ids]                                            # no multiplier, no positions
    per published layer l of n:
      h = x + Mixer_l(LayerNorm(x))
      x = h + W_2 (silu(g) * u),   [g ; u] = W_1 LayerNorm(h)
    logits = LayerNorm(x) E^T                             # the tied table, no bias

LayerNorm has weight and bias (``layer_norm_eps``). ``Mixer_l`` by ``l`` (the
family's configuration class: ``n % 4 == 0``, ``mb_per_layer`` 2):

- **Mamba-1** (even ``l <= n / 2``): ``[x ; z] = W_in u``; ``x <-
  silu(conv_causal(x) + b_c)`` (depthwise, ``mamba_d_conv`` taps); ``[r ; B ;
  C] = W_x x`` (``dt_rank``, ``d_state``, ``d_state``); ``dt = softplus(W_dt r +
  b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t) (x)
  B_t`` from ``S = 0``, a ``lax.scan`` a token; ``y_t = S_t C_t + D x_t``;
  output ``W_out (y * silu(z))``. Layer ``n / 2`` also hands on ``m = y`` (the
  scan's output with its ``D`` term, BEFORE the gate).
- **Differential attention** (odd ``l``): heads in adjacent pairs, query pair
  ``i`` = heads ``(2i, 2i + 1) = (q1, q2)``, key pair ``j = i // (pairs / key
  pairs)`` = KV heads ``(2j, 2j + 1) = (k1, k2)``, ``V_j = [v1_j ; v2_j]``;
  ``a1 = softmax(q1 k1^T / sqrt(d)) V``, ``a2 = softmax(q2 k2^T / sqrt(d)) V``
  under one mask; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)``,
  ``lam0(l) = 0.8 - 0.6 exp(-0.3 l)``; pair output ``(1 - lam0(l)) *
  RMSNorm(a1 - lam * a2)`` (``subln``: a weight over the ``2 d`` lanes,
  ``layer_norm_eps``); the pairs side by side through ``out_proj`` (bias).
  Windowed (odd ``l < n / 2``): query ``t`` sees keys ``max(0, t -
  sliding_window + 1) .. t``. Full (``l = n / 2 + 1``): ``.. t``. Cross (odd
  ``l >= n / 2 + 3``): a query projection alone; keys and values are layer ``n
  / 2 + 1``'s.
- **Gated memory unit** (even ``l >= n / 2 + 2``): ``W_out (silu(W_in u_t) *
  m_t)``, no biases.

Departures from the published description: the parameters are read in the
layout of the program's tree (published layer ``l`` is ``layers_<2l>`` with
``norm`` and its mixer, then ``layers_<2l+1>`` with ``norm``, ``gate_proj``,
``up_proj``, ``fc_out``: ``fc1``'s first half is the gate; the fused ``Wqkv`` is
``q_proj`` / ``k_proj`` / ``v_proj``; ``A_log`` is ``(d_state, channels)``);
``max_position_embeddings`` and the dropouts are not read. Attention runs a
key pair at a time so that a ``t x t`` score map fits beside a served model.
This file imports nothing of the program's model code.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layernorm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def _eps(model) -> float:
    return float(model.get("layer_norm_eps", 1e-5))


def layer_kinds(model) -> list:
    """The mixer of every published layer: ``mamba``, ``window``, ``full``,
    ``cross`` or ``memory``."""
    n = int(model["num_hidden_layers"])
    half = n // 2
    out = []
    for l in range(n):
        if l % 2 == 0:
            out.append("mamba" if l <= half else "memory")
        else:
            out.append("window" if l < half else "full" if l == half + 1 else "cross")
    return out


def mamba(u, p, model):
    """The mixer on the normed rows ``u`` (t, d) of one sequence: ``(output,
    memory)``, the memory ``y`` (t, channels) before the gate."""
    n, K = int(model.get("mamba_d_state", 16)), int(model.get("mamba_d_conv", 4))
    c = p["out_proj"].shape[0]
    rank = p["dt_proj"].shape[0]
    t = u.shape[0]
    proj = u @ p["in_proj"]
    x, z = proj[:, :c], proj[:, c:]
    ext = jnp.concatenate([jnp.zeros((K - 1, c), jnp.float32), x])
    x = silu(sum(ext[k:k + t] * p["conv_w"][k] for k in range(K)) + p["conv_b"])
    sel = x @ p["x_proj"]
    r, B, C = sel[:, :rank], sel[:, rank:rank + n], sel[:, rank + n:]
    dt = jax.nn.softplus(r @ p["dt_proj"] + p["dt_bias"])               # (t, c)
    A = -jnp.exp(p["A_log"])                                            # (n, c)

    def token(state, inp):
        x_t, dt_t, B_t, C_t = inp
        state = jnp.exp(dt_t[None, :] * A) * state \
            + B_t[:, None] * (dt_t * x_t)[None, :]
        return state, jnp.sum(state * C_t[:, None], axis=0) + p["D"] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((n, c), jnp.float32), (x, dt, B, C))
    return (y * silu(z)) @ p["out_proj"], y


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def diff_attention(u, lp, kv, l: int, window, model):
    """Differential attention of published layer ``l`` on the normed rows ``u``
    (t, d); ``kv`` = (k, v), each (t, kv heads, d), of this layer or of the
    full layer a cross layer reads; ``window`` keys a query sees, or None."""
    nh, nk = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    hd = int(model["hidden_size"]) // nh
    t = u.shape[0]
    k, v = kv
    q = (u @ lp["q_proj"]["kernel"] + lp["q_proj"]["bias"]).reshape(t, nh // 2, 2, hd)
    k = k.reshape(t, nk // 2, 2, hd)
    V = v.reshape(t, nk // 2, 2 * hd)                     # [v1 ; v2] a key pair
    g = (nh // 2) // (nk // 2)                            # query pairs a key pair
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen = seen & (pos[None, :] > pos[:, None] - window)
    lam0 = lambda_init(l)
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam0)

    def key_pair(args):
        qj, kj, Vj = args                     # (t, g, 2, hd), (t, 2, hd), (t, 2 hd)
        s = jnp.einsum("tgmd,smd->gmts", qj, kj) / math.sqrt(hd)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        a = jnp.einsum("gmts,sv->tgmv", jax.nn.softmax(s, -1), Vj)
        diff = a[:, :, 0] - lam * a[:, :, 1]                   # (t, g, 2 hd)
        diff = diff * jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True)
                                    + _eps(model))
        return (1.0 - lam0) * diff * lp["subln"]

    out = jax.lax.map(key_pair, (q.reshape(t, nk // 2, g, 2, hd).transpose(1, 0, 2, 3, 4),
                                 k.transpose(1, 0, 2, 3), V.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3).reshape(t, nh * hd)
    return out @ lp["o_proj"]["kernel"] + lp["o_proj"]["bias"]


def keys_values(u, lp, model):
    nk = int(model["num_key_value_heads"])
    hd = int(model["hidden_size"]) // int(model["num_attention_heads"])
    t = u.shape[0]
    return ((u @ lp["k_proj"]["kernel"] + lp["k_proj"]["bias"]).reshape(t, nk, hd),
            (u @ lp["v_proj"]["kernel"] + lp["v_proj"]["bias"]).reshape(t, nk, hd))


def mixer_layer(x, lp, kind, l, model, memory, kv):
    """``x + Mixer_l(LayerNorm(x))`` for one sequence ``x`` (t, d) float32:
    ``(x', memory', kv')``, the two streams handed on as they were unless
    this layer sets them."""
    lp = _f32(lp)
    with jax.default_matmul_precision(HI):
        u = layernorm(x, lp["norm"], _eps(model))
        if kind == "mamba":
            out, memory = mamba(u, lp["mamba"], model)
        elif kind == "memory":
            out = (silu(u @ lp["in_proj"]) * memory) @ lp["out_proj"]
        elif kind == "cross":
            out = diff_attention(u, lp, kv, l, None, model)
        else:
            own = keys_values(u, lp, model)
            window = int(model["sliding_window"]) if kind == "window" else None
            out = diff_attention(u, lp, own, l, window, model)
            if kind == "full":
                kv = own
        return x + out, memory, kv


def mlp_layer(x, lp, model):
    lp = _f32(lp)
    with jax.default_matmul_precision(HI):
        b = layernorm(x, lp["norm"], _eps(model))
        return x + (silu(b @ lp["gate_proj"]["kernel"]) * (b @ lp["up_proj"]["kernel"])) \
            @ lp["fc_out"]["kernel"]


def head(x, ln_f, table_rows, model):
    """Logits over the vocabulary rows ``table_rows`` of the tied table."""
    with jax.default_matmul_precision(HI):
        return layernorm(x, _f32(ln_f), _eps(model)) @ _f32(table_rows).T


class _Frozen(dict):
    """The model section as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


# one program a layer kind, so that a layer's weights are float32 only inside it
_mixer_jit = jax.jit(mixer_layer, static_argnums=(2, 3, 4))
_mlp_jit = jax.jit(mlp_layer, static_argnums=2)
_head_jit = jax.jit(head, static_argnums=3)


def hidden(params, model, ids):
    """``ids`` (t,) -> the last layer's output ``(t, d)`` float32."""
    model = _Frozen(model)
    x = jnp.asarray(params["wte"][jnp.asarray(ids)], jnp.float32)
    memory = kv = None
    for l, kind in enumerate(layer_kinds(model)):
        x, memory, kv = _mixer_jit(x, params[f"layers_{2 * l}"], kind, l, model,
                                   memory, kv)
        x = _mlp_jit(x, params[f"layers_{2 * l + 1}"], model)
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
    inside its program), attention a key pair at a time, the head in blocks of
    ``vocab_block`` rows of the tied table. The sequence is padded on the
    right to a multiple of ``pad_to`` (attention is causal and the recurrence
    runs forward, so no position asked for sees the padding) to keep the
    number of compiled shapes small."""
    ids = np.concatenate([np.asarray(ids), np.zeros(-len(ids) % pad_to, np.int32)])
    x = hidden(params, model, ids)[jnp.asarray(positions)]
    table = params["wte"]
    blocks = [np.asarray(_head_jit(x, params["ln_f"], table[a:a + vocab_block],
                                   _Frozen(model)))
              for a in range(0, table.shape[0], vocab_block)]
    return np.concatenate(blocks, axis=-1)
