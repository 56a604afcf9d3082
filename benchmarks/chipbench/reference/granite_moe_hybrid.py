"""A Granite 4.0 hybrid WITH experts (``model_type: granitemoehybrid``,
``num_local_experts`` > 0: granite-4.0-h-small) in plain float32
``jax.numpy``: no kernels, no cache, no batching; every matmul at highest
precision. ``model`` below is the configuration's ``model`` section, the
published config's keys::

    x0 = embedding_multiplier * E[ids]
    per published layer i:
      a = RMSNorm(x; w1_i);  m = Mamba2(a) if layer_types[i] == "mamba" else Attention(a)
      x = x + residual_multiplier * m
      b = RMSNorm(x; w2_i)                       # ONE norm: router, experts, shared expert
      l = b W_r                                  # num_local_experts logits, no bias
      S = the num_experts_per_tok largest of l;  g_e = softmax over S of l_e
      moe    = sum over e in S of g_e * W_down_e (silu(b W_gate_e) * (b W_up_e))   # intermediate_size
      shared = W_sdown (silu(b W_sgate) * (b W_sup))                         # shared_intermediate_size
      x = x + residual_multiplier * (moe + shared)                           # ONE multiplier over the sum
    logits = (RMSNorm(x; w_f) E^T) / logits_scaling                          # the tied table

``g`` is the softmax over all the logits with the chosen renormalised to sum
to one, which is the same number. ``Mamba2``, ``Attention``, the mixer layer
and the head are the accepted ``granite_hybrid.py``'s, called unchanged (the
family's mixers do not differ between its models; that file's docstring has
their equations).

The chip's share: ``model["experts_held"] = [first, count]`` (absent: every
expert) says which experts' matrices the parameters hold. The router scores
ALL ``num_local_experts`` and chooses among all; an expert held elsewhere adds
nothing here, as in the program, and that partial sum goes on to the next
layer. The shared expert is every chip's and is added whole.

Departures from the published description: the parameters are read in the
layout of the program's tree (a published layer is ``layers_<2i>`` with
``norm`` and ``mamba`` or ``q_proj``.., then ``layers_<2i+1>`` with ``norm``
and ``moe``: ``router`` (d, experts), ``experts_gate`` / ``experts_up``
(held, d, width), ``experts_down`` (held, width, d), ``shared_gate`` /
``shared_up`` (d, shared width), ``shared_down``; the published fused
``input_linear`` is stored as its two halves gate | up); the experts are
computed densely, ``expert_block`` at a time over every token with the
weights of tokens that did not choose an expert at 0, so that ten layers of
36 experts at d 4096 fit beside a served model; ``time_step_limit`` is (0,
inf); ``rope_theta`` is not read. This file imports nothing of the program's
model code.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chipbench.reference import granite_hybrid as gh
from benchmarks.chipbench.reference.granite_hybrid import rmsnorm, silu

HI = gh.HI


def held(model) -> tuple:
    first, count = model.get("experts_held") or (0, int(model["num_local_experts"]))
    return int(first), int(count)


def routing(b, w_router, model):
    """``b`` (t, d) normed rows. Returns (t, experts) float32: ``g_e`` at the
    chosen experts, 0 elsewhere."""
    logits = b @ w_router
    top, idx = jax.lax.top_k(logits, int(model["num_experts_per_tok"]))
    g = jax.nn.softmax(top, axis=-1)
    rows = jnp.arange(b.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(g)


def experts_block(b, g_block, w_gate, w_up, w_down):
    """``sum_e g_e W_down_e (silu(b W_gate_e) * (b W_up_e))`` over the experts
    of one block: ``g_block`` (t, e), the matrices (e, ...)."""
    w_gate, w_up, w_down = gh._f32((w_gate, w_up, w_down))
    with jax.default_matmul_precision(HI):
        mid = silu(jnp.einsum("td,edf->etf", b, w_gate)) \
            * jnp.einsum("td,edf->etf", b, w_up)
        return jnp.einsum("te,etd->td", g_block, jnp.einsum("etf,efd->etd", mid, w_down))


def shared_expert(b, p):
    return (silu(b @ p["shared_gate"]) * (b @ p["shared_up"])) @ p["shared_down"]


def normed_and_routed(x, lp, model):
    """``b``, the routing weights and the shared expert's term of one layer."""
    norm, router = gh._f32((lp["norm"], lp["moe"]["router"]))
    shared = gh._f32({k: v for k, v in lp["moe"].items() if k.startswith("shared_")})
    with jax.default_matmul_precision(HI):
        b = rmsnorm(x, norm["scale"], gh._eps(model))
        return b, routing(b, router, model), shared_expert(b, shared)


_routed_jit = jax.jit(normed_and_routed, static_argnums=2)
_block_jit = jax.jit(experts_block)


def moe_layer(x, lp, model, expert_block: int = 6):
    """``x + residual_multiplier * (moe(b) + shared(b))``, ``b = RMSNorm(x)``:
    ``x`` (t, d) float32, one sequence; the held experts ``expert_block`` at a
    time (their matrices are float32 only inside a block's program)."""
    model = gh._Frozen(model)
    first, count = held(model)
    b, g, out = _routed_jit(x, lp, model)
    p = lp["moe"]
    for a in range(0, count, expert_block):
        z = min(a + expert_block, count)
        out = out + _block_jit(b, g[:, first + a:first + z], p["experts_gate"][a:z],
                               p["experts_up"][a:z], p["experts_down"][a:z])
    return x + float(model["residual_multiplier"]) * out


def hidden(params, model, ids):
    """``ids`` (t,) -> the last layer's output ``(t, d)`` float32."""
    model = gh._Frozen(model)
    x = float(model["embedding_multiplier"]) * jnp.asarray(
        params["wte"][jnp.asarray(ids)], jnp.float32)
    for i, kind in enumerate(gh._layer_types(model)):
        x = gh._mixer_jit(x, params[f"layers_{2 * i}"], kind, model)
        x = moe_layer(x, params[f"layers_{2 * i + 1}"], model)
    return x


def forward(params, model, ids):
    """One sequence ``ids`` (t,): logits ``(t, vocab)`` float32."""
    return gh._head_jit(hidden(params, model, ids), params["ln_f"], params["wte"],
                        gh._Frozen(model))


def next_token_logits(params, model: dict, ids, positions, vocab_block: int = 32768,
                      pad_to: int = 128):
    """Float32 logits ``(len(positions), vocab)`` of one sequence ``ids``
    ``(t,)`` at ``positions``: the mathematics of :func:`forward`, held beside
    a served model's weights: a layer at a time, the experts in blocks, the
    head in blocks of ``vocab_block`` rows of the tied table. The sequence is
    padded on the right to a multiple of ``pad_to`` (attention is causal, the
    recurrence runs forward and an expert layer is per token, so no position
    asked for sees the padding) to keep the number of compiled shapes small."""
    ids = np.concatenate([np.asarray(ids), np.zeros(-len(ids) % pad_to, np.int32)])
    x = hidden(params, model, ids)[jnp.asarray(positions)]
    table = params["wte"]
    blocks = [np.asarray(gh._head_jit(x, params["ln_f"], table[a:a + vocab_block],
                                      gh._Frozen(model)))
              for a in range(0, table.shape[0], vocab_block)]
    return np.concatenate(blocks, axis=-1)
