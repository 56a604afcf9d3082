"""Mixture of gated experts of the full width, one chip's share of it.

An expert is a SwiGLU block, ``W_down (silu(h W_gate) * (h W_up))``, three
matrices. A shared expert is data of the layer (``shared_width``; 0: none, as
in ``sdar_moe`` and ``lfm2_moe``): one more SwiGLU block of that width,
``shared_gate`` / ``shared_up`` / ``shared_down``, that reads the SAME normed
input as router and experts and is added to their sum before the layer
returns (``granitemoehybrid`` with experts), under the scope ``moe.shared``.
The router is data (``router``), scoring every expert of the layer
(``n_routed``) in float32:

- ``"softmax"``: ``p = softmax(h W_r)``, the ``top_k`` largest, weighted by
  their probabilities, renormalised to sum to one where ``norm_topk`` says
  so: no selection bias, no scaling (the Qwen3-MoE layer, which ``sdar_moe``
  keeps);
- ``"sigmoid_bias"``: :func:`~.latent_moe.route`, ``s = sigmoid(h W_r)``, the
  ``top_k`` largest of ``s + b`` (``b`` the layer's ``router_bias``, which
  moves the choice and never a weight), weighted by ``s`` over ``sum +
  topk_eps`` where ``norm_topk``, times ``scale`` (``lfm2_moe``).

As :class:`~.latent_moe.LatentMoE` the layer is TOLD which experts it holds,
``experts_held = (first, count)``: it routes over all ``n_routed`` and sums
only the assignments that fall on its own experts. With ``count ==
n_routed`` it is the whole layer; the partial sums of the chips of an
expert-parallel layer add up to it. Prefill and block step go through one
plan and one grouped kernel (``ops/moe/grouped_ffn.py``, the gated form).
"""

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability import scope
from ..ops.moe.grouped_ffn import grouped_experts
from .latent_moe import route


def route_softmax(h, w_router, top_k: int, norm: bool):
    """``h`` (T, d). Returns the chosen experts ``idx`` (T, k) int32 and their
    weights (T, k) float32: softmax over all experts in float32, the ``top_k``
    largest, renormalised over the chosen where ``norm``."""
    p = jax.nn.softmax(jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


class GatedMoE(nn.Module):
    d_model: int
    n_routed: int
    top_k: int
    expert_width: int
    norm_topk: bool
    experts_held: Tuple[int, int]
    dtype: Any
    init_std: float
    out_std: float
    router: str = "softmax"
    scale: float = 1.0            # the sigmoid_bias router's
    topk_eps: float = 1e-20
    shared_width: int = 0         # the gated shared expert's; 0 = none

    @nn.compact
    def __call__(self, h, valid: Optional[jnp.ndarray] = None):
        """``h`` (b, t, d) normed input; ``valid`` (b, t) bool marks real
        tokens (padding is routed nowhere). Returns the layer's output and
        ``(assignments on held experts, distinct held experts touched)``."""
        first, count = self.experts_held
        d, f, dt = self.d_model, self.expert_width, self.dtype
        init = nn.initializers.normal(self.init_std)
        w_r = self.param("router", init, (d, self.n_routed), jnp.float32)
        if self.router == "sigmoid_bias":
            # seeded as the latent mixture seeds it: non-zero, small beside
            # the scores' spread
            b_r = self.param("router_bias", nn.initializers.normal(0.01),
                             (self.n_routed,), jnp.float32)
        w_gate = self.param("experts_gate", init, (count, d, f), jnp.float32)
        w_up = self.param("experts_up", init, (count, d, f), jnp.float32)
        w_down = self.param("experts_down", nn.initializers.normal(self.out_std),
                            (count, f, d), jnp.float32)
        if self.shared_width:
            s_gate = self.param("shared_gate", init, (d, self.shared_width), jnp.float32)
            s_up = self.param("shared_up", init, (d, self.shared_width), jnp.float32)
            s_down = self.param("shared_down", nn.initializers.normal(self.out_std),
                                (self.shared_width, d), jnp.float32)
        b_, t, _ = h.shape
        with scope("moe.router"):
            x = h.reshape(b_ * t, d).astype(dt)
            if self.router == "sigmoid_bias":
                idx, w = route(x, w_r, b_r, self.top_k, self.scale, self.norm_topk,
                               self.topk_eps)
            else:
                idx, w = route_softmax(x, w_r, self.top_k, self.norm_topk)
        with scope("moe.experts"):
            args = (w_up.astype(dt), w_down.astype(dt), jax.nn.silu,
                    None if valid is None else valid.reshape(-1), w_gate.astype(dt))
        out, stats = grouped_experts(x, idx, w, first, count, w_r.shape[1], *args)
        if self.shared_width:
            with scope("moe.shared"):
                mid = jax.nn.silu(jnp.dot(x, s_gate.astype(dt),
                                          preferred_element_type=jnp.float32)) \
                    * jnp.dot(x, s_up.astype(dt), preferred_element_type=jnp.float32)
                # one rounding of the sum to the stream's type, not one a term
                out = out + jnp.dot(mid.astype(dt), s_down.astype(dt),
                                    preferred_element_type=jnp.float32)
        with scope("moe.rows"):
            return out.astype(dt).reshape(b_, t, d), stats
