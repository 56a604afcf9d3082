"""BLOOM's forward pass in plain float32 ``jax.numpy``: no kernels, no cache,
no batching. It follows the published architecture (BigScience BLOOM,
arXiv 2211.05100; ``modeling_bloom.py``): word embeddings through an embedding
layernorm; per layer, layernorm -> q, k, v with bias -> causal attention whose
scores carry the ALiBi bias ``slope[h] * (key position - query position)`` ->
output projection -> residual, then layernorm -> 4d feed-forward with tanh-GELU
-> residual; a final layernorm; logits against the tied embedding.

Departures: the parameters are read in the layout of the program's ``CausalLM``
(separate ``q_proj``/``k_proj``/``v_proj`` instead of BLOOM's fused
per-head-interleaved ``query_key_value``), which is the same mathematics.
"""

import jax
import jax.numpy as jnp
import numpy as np


def alibi_slopes(n_head: int) -> np.ndarray:
    """ALiBi's slopes (Press et al., arXiv 2108.12409): a geometric sequence
    starting at 2^(-8/n) for a power-of-two ``n``; otherwise the closest lower
    power's, then every other slope of the next power's."""
    def power_of_two(n):
        start = 2.0 ** (-8.0 / n)
        return [start ** (i + 1) for i in range(n)]
    closest = 2 ** int(np.floor(np.log2(n_head)))
    slopes = power_of_two(closest)
    if closest < n_head:
        slopes += power_of_two(2 * closest)[0::2][:n_head - closest]
    return np.asarray(slopes, np.float32)


def layernorm(x, p, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.79788456 * x * (1.0 + 0.044715 * x * x)))


def embed(p, ids):
    """Word embeddings through the embedding layernorm, ``(b, t, d)`` float32."""
    return layernorm(jnp.asarray(p["wte"][ids], jnp.float32), _f32(p["ln_embed"]))


def layer(x, lp, n_head: int):
    """One decoder layer on ``x`` ``(b, t, d)``; ``lp`` in any dtype."""
    lp = _f32(lp)
    with jax.default_matmul_precision("highest"):
        b, t, d = x.shape
        d_head = d // n_head
        pos = jnp.arange(t)
        bias = jnp.asarray(alibi_slopes(n_head))[:, None, None] \
            * (pos[None, :] - pos[:, None])[None].astype(jnp.float32)
        causal = pos[None, :] <= pos[:, None]
        h = layernorm(x, lp["ln_attn"])
        split = lambda a: a.reshape(b, t, n_head, d_head)  # noqa: E731
        q, k, v = (split(dense(h, lp[n])) for n in ("q_proj", "k_proj", "v_proj"))
        scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(d_head) + bias[None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v)
        x = x + dense(attn.reshape(b, t, -1), lp["o_proj"])
        h = layernorm(x, lp["ln_mlp"])
        return x + dense(gelu_tanh(dense(h, lp["fc_in"])), lp["fc_out"])


def head(x, ln_f, wte_rows):
    """Final layernorm, then logits against ``wte_rows`` (rows of the tied
    embedding)."""
    with jax.default_matmul_precision("highest"):
        return layernorm(x, _f32(ln_f)) @ jnp.asarray(wte_rows, jnp.float32).T


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _n_layer(p) -> int:
    return sum(1 for k in p if k.startswith("layers_"))


def forward(params, ids, n_head: int):
    """``params``: the ``CausalLM`` tree (any dtype; computed in float32);
    ``ids`` ``(b, t)``. Returns logits ``(b, t, vocab)`` in float32."""
    x = embed(params, ids)
    for i in range(_n_layer(params)):
        x = layer(x, params[f"layers_{i}"], n_head)
    return head(x, params["ln_f"], params["wte"])


_layer_jit = jax.jit(layer, static_argnums=2)
_head_jit = jax.jit(head)


def next_token_logits(params, model: dict, ids, positions, vocab_block: int = 32768,
                      pad_to: int = 128):
    """Float32 logits ``(len(positions), vocab)`` of one sequence ``ids``
    ``(t,)`` at ``positions``: the same mathematics as :func:`forward`, held
    beside a served model's weights: a layer at a time (one compiled program
    for all of them, its weights made float32 inside it) and the head in
    blocks of ``vocab_block`` rows. The sequence is padded on the right to a
    multiple of ``pad_to`` (attention is causal, so no position asked for sees
    the padding) to keep the number of compiled shapes small."""
    n_head = int(model["n_head"])
    ids = np.concatenate([np.asarray(ids), np.zeros(-len(ids) % pad_to, np.int32)])
    x = embed(params, jnp.asarray(ids)[None])
    for i in range(_n_layer(params)):
        x = _layer_jit(x, params[f"layers_{i}"], n_head)
    x = x[0, jnp.asarray(positions)]
    wte = params["wte"]
    blocks = [np.asarray(_head_jit(x, params["ln_f"], wte[a:a + vocab_block]))
              for a in range(0, wte.shape[0], vocab_block)]
    return np.concatenate(blocks, axis=-1)
