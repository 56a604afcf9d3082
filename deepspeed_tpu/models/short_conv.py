"""Gated short convolution (LFM2's ``conv`` operator) for serving.

One mixer, for a normed input ``x`` (b, t, d): ``[B | C | u] = x W_in``
(``W_in``: d x 3d, no bias); ``v = B * u``; a causal depthwise convolution of
``K`` taps over ``v`` with NO activation and no bias,

    c_t = sum_{j < K} w[j] * v_{t - (K - 1) + j}        (zeros before position 0)

and ``out = (C * c) W_out``. What a sequence keeps between tokens is the last
``K - 1`` products ``v``: ``{"conv": (b, K - 1, d)}``, a per-slot state that
does not grow with the sequence and has no recurrence beside the window.

The products are formed in float32 from the projection's output and rounded
to the serving type ONCE, where ``v`` is made: the prefill's convolution and
the decode step's read the same rounded ``v``, whether it comes from this
call or from the state.
"""

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..observability import scope
from .mamba2 import last_inputs


def short_conv(v, w, state=None):
    """``v`` (b, t, d) float32, ``w`` (K, d) with ``w[K-1]`` on the current
    input, ``state`` (b, K-1, d) the products before position 0 (zeros when
    None). Returns ``c`` (b, t, d) and the window it was computed over,
    ``(b, K - 1 + t, d)``."""
    K = w.shape[0]
    b_, t, d = v.shape
    if state is None:
        state = jnp.zeros((b_, K - 1, d), v.dtype)
    ext = jnp.concatenate([state.astype(v.dtype), v], axis=1)
    return sum(ext[:, j:j + t, :] * w[j] for j in range(K)), ext


class ShortConvMixer(nn.Module):
    """The mixer on a normed input ``(b, t, d)``; see the module docstring.

    ``cache`` None: full sequence, nothing kept. ``cache`` given and t > 1
    (prefill; ``seq_lens`` (b,) real lengths of right-padded rows): the state
    a row leaves is the last ``K - 1`` products before its TRUE length.
    ``cache`` given and t == 1 (decode): one-token update of ``{"conv"}``."""
    d_model: int
    conv_kernel: int
    dtype: Any
    init_std: float
    out_std: float

    @nn.compact
    def __call__(self, x, cache=None, seq_lens: Optional[jnp.ndarray] = None):
        d, K = self.d_model, self.conv_kernel
        b_, t, _ = x.shape
        w_in = self.param("in_proj", nn.initializers.normal(self.init_std),
                          (d, 3 * d), jnp.float32)
        conv_w = self.param("conv_w", nn.initializers.normal(K ** -0.5), (K, d),
                            jnp.float32)
        w_out = self.param("out_proj", nn.initializers.normal(self.out_std),
                           (d, d), jnp.float32)

        with scope("sconv.in"):
            proj = (x.astype(self.dtype) @ w_in.astype(self.dtype)).astype(jnp.float32)
            gate = proj[..., d:2 * d]                                   # C
            v = (proj[..., :d] * proj[..., 2 * d:]).astype(self.dtype)  # B * u

        new_cache = None
        with scope("sconv.conv"):
            c, ext = short_conv(v.astype(jnp.float32), conv_w.astype(jnp.float32),
                                None if cache is None or t > 1 else cache["conv"])
            if cache is not None and t == 1:
                new_cache = {"conv": ext[:, 1:].astype(cache["conv"].dtype)}
            elif cache is not None:
                lens = (jnp.full((b_,), t, jnp.int32) if seq_lens is None
                        else seq_lens)
                new_cache = {"conv": last_inputs(v, lens, K).astype(cache["conv"].dtype)}
        with scope("sconv.out"):
            out = (gate * c).astype(self.dtype) @ w_out.astype(self.dtype)
        return out, new_cache
