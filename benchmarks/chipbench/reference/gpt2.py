"""GPT-2's forward pass and next-token loss in plain float32 ``jax.numpy``
(Radford et al. 2019; ``modeling_gpt2.py``): token plus learned position
embeddings; per layer, layernorm -> fused qkv with bias -> causal softmax
attention -> projection -> residual, then layernorm -> 4d feed-forward with
tanh-GELU -> residual; a final layernorm; logits against the tied embedding;
mean cross-entropy of each position's next token. Gradients are
``jax.grad`` of :func:`loss`.

Departure: parameters are read in the layout of the program's ``GPT2`` flax
module (``wte``, ``wpe``, ``h_<i>/{ln_1, c_attn, c_proj, ln_2, c_fc,
mlp_c_proj}``, ``ln_f``) or, for scanned layers, the same leaves stacked along
a leading layer axis under ``h``; the layers run under ``lax.scan`` so that the
compiler sees one layer. ``ln_eps`` is the published
``layer_norm_epsilon`` (1e-5); the program's ``models/gpt2.py`` leaves flax's
default (1e-6), so a comparison with it passes 1e-6 and says so.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chipbench.reference.bloom import dense, gelu_tanh, layernorm


def _stacked(p):
    """The layers' leaves stacked along a leading layer axis."""
    if "h" in p:                       # scanned: stacked already
        return p["h"]
    keys = sorted((k for k in p if k.startswith("h_")), key=lambda k: int(k[2:]))
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[p[k] for k in keys])


def forward(params, ids, n_head: int, ln_eps: float = 1e-5):
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        b, t = ids.shape
        x = p["wte"][ids] + p["wpe"][:t][None]
        d_head = x.shape[-1] // n_head
        causal = jnp.tril(jnp.ones((t, t), bool))

        def layer(x, lp):              # one program for every layer: it compiles once
            qkv = dense(layernorm(x, lp["ln_1"], ln_eps), lp["c_attn"])
            q, k, v = (a.reshape(b, t, n_head, d_head) for a in jnp.split(qkv, 3, -1))
            scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(d_head)
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v)
            x = x + dense(attn.reshape(b, t, -1), lp["c_proj"])
            h = layernorm(x, lp["ln_2"], ln_eps)
            return x + dense(gelu_tanh(dense(h, lp["c_fc"])), lp["mlp_c_proj"]), None

        x, _ = jax.lax.scan(layer, x, _stacked(p))
        return layernorm(x, p["ln_f"], ln_eps) @ p["wte"].T


def loss(params, ids, n_head: int, ln_eps: float = 1e-5):
    logits = forward(params, ids, n_head, ln_eps)[:, :-1]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()


def loss_and_grad_norm(params, model: dict, ids, rows: int = 2, ln_eps: float = 1e-5):
    """The mean next-token loss of the batch ``ids`` ``(b, t)`` and the global
    norm of its gradient, float32, ``rows`` sequences at a time (every
    sequence weighs the same, so the batch's loss and gradient are the means
    of the slices')."""
    n_head = int(model["n_head"])
    step = jax.jit(jax.value_and_grad(
        lambda p, x: loss(p, x, n_head, ln_eps)))
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    ids = np.asarray(ids)
    if ids.shape[0] % rows:
        rows = 1
    n = ids.shape[0] // rows
    total, grads = 0.0, None
    for i in range(n):
        value, g = step(params, jnp.asarray(ids[i * rows:(i + 1) * rows]))
        total += float(value)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    sq = sum(float(jnp.sum(jnp.square(g / n))) for g in jax.tree_util.tree_leaves(grads))
    return total / n, float(np.sqrt(sq))
