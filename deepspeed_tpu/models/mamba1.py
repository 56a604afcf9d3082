"""Mamba-1 mixer (the selective state space of Gu & Dao, arXiv 2312.00752) for
serving.

One mixer, for a normed input ``u`` (b, t, d): ``[x | z] = u W_in`` (``c`` =
``d_inner`` channels each, no bias); ``x <- silu(conv_causal(x) + b_c)``
(depthwise, ``K`` taps); ``[r | B | C] = x W_x`` (``dt_rank``, ``n``, ``n``);
``dt = softplus(r W_dt + b_dt)`` a channel; ``A = -exp(A_log)``, one decay a
channel AND state lane; the recurrence of ``ops/ssm/selective_scan.py``

    S_t = exp(dt_t A) S_{t-1} + B_t (x) (dt_t x_t)        y_t = C_t . S_t + D x_t

and ``out = (y * silu(z)) W_out``. ``y``, the scan's output with its ``D``
term and BEFORE the gate, is handed back beside the output: the memory a
gated memory unit of a later layer reads (SambaY, arXiv 2507.06607).

What a sequence keeps between tokens: the last ``K - 1`` inputs of the
convolution (serving type) and the state ``(n, c)`` in float32, the channels
on the lanes: ``{"conv": (b, K - 1, c), "ssm": (b, n, c)}``. ``A_log`` is kept
``(n, c)`` for the same reason (the family's checkpoints have it ``(c, n)``).
The recurrence and everything elementwise before it are float32; the four
projections run in the serving type.
"""

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability import scope
from ..ops.ssm import selective_scan, selective_step
from .mamba2 import causal_conv, last_inputs


def project(a, w, dtype):
    """``a @ w`` with both operands in the serving type, as float32 that holds
    values of the serving type: the product is accumulated in float32 and
    rounded by an explicit ``reduce_precision``. An ``astype`` to the serving
    type and back the compiler may fuse away (it keeps the accumulator's
    excess precision where the consumer is fused into the product), and
    whether it does depends on the program around the step: the serving
    chunk and ``engine.generate``'s loop then round the same step differently
    (on the chip 1 in ~6 of a layer's outputs moved by one bfloat16 step in
    some layers behind a Mamba-1 mixer: PERF.md section 6, PR 59), and a
    random stand-in's near ties turn that into other tokens."""
    out = jnp.dot(a.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32)
    return serving_values(out, dtype)


def serving_values(x, dtype):
    """float32 ``x`` rounded to the values of ``dtype``, by an op the compiler
    keeps (:func:`project` says why); ``x`` itself where that is float32."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _dt_bias_init(key, shape, dtype):
    """``softplus(b_dt)`` log-uniform over the channels in [1e-3, 1e-1]
    (Mamba-1's own init, spread and not drawn): a state that neither vanishes
    nor explodes over a few thousand tokens."""
    dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), shape[0]))
    return jnp.log(jnp.expm1(dt)).astype(dtype)


def _a_log_init(key, shape, dtype):
    """``A = -(1 .. n)`` down the state lanes of every channel (S4D-real)."""
    n, c = shape
    return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                            (n, c)).astype(dtype)


class Mamba1Mixer(nn.Module):
    """The mixer on a normed input ``(b, t, d)``; returns ``(out, new cache,
    memory)``, the memory ``(b, t, c)`` float32. ``cache`` None: full sequence,
    nothing kept. ``cache`` given and t > 1 (prefill; ``seq_lens`` (b,) real
    lengths of right-padded rows): the state is computed from zero and
    returned. ``cache`` given and t == 1 (decode): one-token update."""
    d_model: int
    d_inner: int
    state_size: int
    dt_rank: int
    conv_kernel: int
    dtype: Any
    init_std: float
    out_std: float

    @nn.compact
    def __call__(self, u, cache=None, seq_lens: Optional[jnp.ndarray] = None):
        c, n, rank, K = self.d_inner, self.state_size, self.dt_rank, self.conv_kernel
        b_, t, _ = u.shape
        init = nn.initializers.normal(self.init_std)
        w_in = self.param("in_proj", init, (self.d_model, 2 * c), jnp.float32)
        conv_w = self.param("conv_w", nn.initializers.normal(K ** -0.5), (K, c),
                            jnp.float32)
        conv_b = self.param("conv_b", init, (c,), jnp.float32)
        w_x = self.param("x_proj", init, (c, rank + 2 * n), jnp.float32)
        w_dt = self.param("dt_proj", nn.initializers.normal(rank ** -0.5), (rank, c),
                          jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (c,), jnp.float32)
        A_log = self.param("A_log", _a_log_init, (n, c), jnp.float32)
        D = self.param("D", nn.initializers.ones, (c,), jnp.float32)
        w_out = self.param("out_proj", nn.initializers.normal(self.out_std),
                           (c, self.d_model), jnp.float32)

        with scope("ssm.in"):
            proj = project(u, w_in, self.dtype)
            x, z = proj[..., :c], proj[..., c:]
            A = -jnp.exp(A_log.astype(jnp.float32))
            Df = D.astype(jnp.float32)
            cw, cb = conv_w.astype(jnp.float32), conv_b.astype(jnp.float32)

        decode = cache is not None and t == 1
        with scope("ssm.conv"):
            xc = causal_conv(x, cw, cb, cache["conv"] if decode else None)
        with scope("ssm.select"):
            sel = project(serving_values(xc, self.dtype), w_x, self.dtype)
            Bm, Cm = sel[..., rank:rank + n], sel[..., rank + n:]
            dt = jax.nn.softplus(project(sel[..., :rank], w_dt, self.dtype)
                                 + dt_bias.astype(jnp.float32))      # (b, t, c)
            if not decode and seq_lens is not None:
                real = jnp.arange(t)[None, :] < seq_lens[:, None]
                dt = jnp.where(real[..., None], dt, 0.0)

        new_cache = None
        if decode:
            with scope("ssm.update"):
                y, ssm = selective_step(cache["ssm"], xc[:, 0], dt[:, 0], A,
                                        Bm[:, 0], Cm[:, 0], Df)
                y = y[:, None]
            with scope("ssm.conv"):
                new_cache = {
                    "conv": jnp.concatenate(
                        [cache["conv"][:, 1:], x.astype(cache["conv"].dtype)], axis=1),
                    "ssm": ssm}
        else:
            with scope("ssm.update"):
                y, ssm = selective_scan(xc, dt, A, Bm, Cm, Df)
            if cache is not None:
                with scope("ssm.conv"):
                    lens = (jnp.full((b_,), t, jnp.int32) if seq_lens is None
                            else seq_lens)
                    new_cache = {
                        "conv": last_inputs(x, lens, K).astype(cache["conv"].dtype),
                        "ssm": ssm}
        with scope("ssm.out"):
            gated = serving_values(y * jax.nn.silu(z), self.dtype)
            out = project(gated, w_out, self.dtype).astype(self.dtype)
        return out, new_cache, y
