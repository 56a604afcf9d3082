"""Mamba-2 mixer (state-space duality, Dao & Gu, arXiv 2405.21060) for serving.

One mixer = ``in_proj`` -> [z | xBC | dt]; a causal depthwise convolution over
xBC, then SiLU; split into x (heads x head_dim), B and C (groups x state);
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t        y_t = C_t . h_t + D x_t

``y = RMSNorm_groups(y * silu(z))``; ``out_proj``. Two forms of the recurrence:

- :func:`ssd_chunked` (prefill): the chunked form of the paper's listing 1 —
  inside a chunk the quadratic "attention-like" product, between chunks a
  scan over per-chunk states. A right-padded prompt passes ``dt = 0`` at its
  padding: ``exp(0 A) = 1`` and ``0 B x = 0``, so padding neither decays the
  state nor feeds it, and the state after the bucket is the state after the
  last real token.
- :func:`ssm_step` (decode): the one-token update of a ``(b, h, p, n)`` state.

Everything of the recurrence is float32 at highest matmul precision (the
state is kept in float32; the products are a few GFLOP a prompt); the two
projections run in the serving type.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability import scope
from ..ops.ssm import ssm_step

HI = jax.lax.Precision.HIGHEST


def causal_conv(xbc, w, b, conv_state=None):
    """Depthwise causal convolution, kernel ``K = w.shape[0]``, then SiLU.

    ``xbc`` (b, t, c) float32; ``w`` (K, c) with ``w[K-1]`` on the current
    input; ``conv_state`` (b, K-1, c) the inputs before position 0 (zeros
    when None)."""
    K = w.shape[0]
    b_, t, c = xbc.shape
    if conv_state is None:
        conv_state = jnp.zeros((b_, K - 1, c), xbc.dtype)
    ext = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
    y = sum(ext[:, k:k + t, :] * w[k] for k in range(K)) + b
    return jax.nn.silu(y)


def last_inputs(xbc, seq_lens, K: int):
    """The ``K-1`` inputs before position ``seq_lens`` of each row (zeros
    before position 0): the conv state a right-padded prompt leaves."""
    b_, t, c = xbc.shape
    pos = seq_lens[:, None] - (K - 1) + jnp.arange(K - 1)[None]      # (b, K-1)
    got = jnp.take_along_axis(xbc, jnp.clip(pos, 0, t - 1)[..., None], axis=1)
    return jnp.where((pos >= 0)[..., None], got, 0.0)


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """Chunked scan. ``x`` (b, t, h, p), ``dt`` (b, t, h) (after softplus;
    0 at padding), ``A`` (h,) negative, ``B``/``C`` (b, t, g, n), ``D`` (h,).
    From a zero state. Returns ``y`` (b, t, h, p) and the state after
    position t-1, ``(b, h, p, n)``. All float32."""
    b_, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    r = h // g
    Q = min(chunk, t)
    pad = (-t) % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    c = (t + pad) // Q
    xg = x.reshape(b_, c, Q, g, r, p)
    dtg = dt.reshape(b_, c, Q, g, r)
    Bc = B.reshape(b_, c, Q, g, n)
    Cc = C.reshape(b_, c, Q, g, n)
    a_cs = jnp.cumsum(dtg * A.reshape(g, r), axis=2)                 # (b,c,Q,g,r)
    xdt = xg * dtg[..., None]
    # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(a_cs_i - a_cs_j) dt_j x_j
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    seg = a_cs[:, :, :, None] - a_cs[:, :, None, :]                  # (b,c,i,j,g,r)
    L = jnp.where(tri[None, None, :, :, None, None], jnp.exp(
        jnp.where(tri[None, None, :, :, None, None], seg, 0.0)), 0.0)
    CB = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc, precision=HI)
    y = jnp.einsum("bcijg,bcijgr,bcjgrp->bcigrp", CB, L, xdt, precision=HI)
    # each chunk's own contribution to the state at its end, and its decay
    to_end = jnp.exp(a_cs[:, :, -1:] - a_cs)                         # (b,c,Q,g,r)
    states = jnp.einsum("bcjgn,bcjgr,bcjgrp->bcgrpn", Bc, to_end, xdt,
                        precision=HI)
    decay = jnp.exp(a_cs[:, :, -1])                                  # (b,c,g,r)
    s0 = jnp.zeros((b_, g, r, p, n), jnp.float32)

    def carry(s, inp):
        st, dc = inp
        return s * dc[..., None, None] + st, s

    last, before = jax.lax.scan(
        carry, s0, (states.transpose(1, 0, 2, 3, 4, 5), decay.transpose(1, 0, 2, 3)))
    before = before.transpose(1, 0, 2, 3, 4, 5)                      # (b,c,g,r,p,n)
    y = y + jnp.einsum("bcign,bcgrpn,bcigr->bcigrp", Cc, before, jnp.exp(a_cs),
                       precision=HI)
    y = y.reshape(b_, t + pad, h, p)[:, :t] + x[:, :t] * D[:, None]
    return y, last.reshape(b_, h, p, n)


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``RMSNorm`` over each of ``groups`` equal slices of the last axis of
    ``y * silu(z)``, then the weight (Mamba-2's gated norm, gate first)."""
    y = y * jax.nn.silu(z)
    shape = y.shape
    yg = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(shape) * weight


class Mamba2Mixer(nn.Module):
    """The mixer on a normed input ``(b, t, d)``; see the module docstring.

    ``cache`` None: full sequence, nothing kept. ``cache`` given and t > 1
    (prefill; ``seq_lens`` (b,) real lengths of right-padded rows): the state
    is computed from zero and returned. ``cache`` given and t == 1 (decode):
    one-token update of ``{"conv": (b, K-1, c), "ssm": (b, h, p, n)}``."""
    d_model: int
    num_heads: int
    head_dim: int
    state_size: int
    n_groups: int
    conv_kernel: int
    chunk_size: int
    eps: float
    dtype: Any
    init_std: float
    out_std: float

    @nn.compact
    def __call__(self, x, cache=None, seq_lens: Optional[jnp.ndarray] = None):
        h, p, n, g, K = (self.num_heads, self.head_dim, self.state_size,
                         self.n_groups, self.conv_kernel)
        d_in = h * p
        conv_dim = d_in + 2 * g * n
        b_, t, _ = x.shape
        init = nn.initializers.normal(self.init_std)
        w_in = self.param("in_proj", init, (self.d_model, d_in + conv_dim + h),
                          jnp.float32)
        conv_w = self.param("conv_w", nn.initializers.normal(K ** -0.5),
                            (K, conv_dim), jnp.float32)
        conv_b = self.param("conv_b", nn.initializers.normal(self.init_std),
                            (conv_dim,), jnp.float32)
        # dt in [1e-3, 1e-1] after softplus and A in [-16, -1], as Mamba-2
        # initialises them (spread over the heads, not drawn)
        dt_bias = self.param(
            "dt_bias", lambda k, s, d: jnp.log(jnp.expm1(jnp.exp(jnp.linspace(
                jnp.log(1e-3), jnp.log(1e-1), s[0])))).astype(d), (h,), jnp.float32)
        A_log = self.param("A_log", lambda k, s, d: jnp.log(
            jnp.linspace(1.0, 16.0, s[0])).astype(d), (h,), jnp.float32)
        D = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        norm_w = self.param("norm_w", nn.initializers.ones, (d_in,), jnp.float32)
        w_out = self.param("out_proj", nn.initializers.normal(self.out_std),
                           (d_in, self.d_model), jnp.float32)

        with scope("ssm.in"):
            proj = x.astype(self.dtype) @ w_in.astype(self.dtype)
            z = proj[..., :d_in].astype(jnp.float32)
            xbc = proj[..., d_in:d_in + conv_dim].astype(jnp.float32)
            dt = jax.nn.softplus(proj[..., d_in + conv_dim:].astype(jnp.float32)
                                 + dt_bias.astype(jnp.float32))      # (b, t, h)
            A = -jnp.exp(A_log.astype(jnp.float32))
            Df = D.astype(jnp.float32)
            cw, cb = conv_w.astype(jnp.float32), conv_b.astype(jnp.float32)

        new_cache = None
        if cache is not None and t == 1:
            with scope("ssm.conv"):
                conv = causal_conv(xbc, cw, cb, cache["conv"])[:, 0]     # (b, c)
                xs = conv[:, :d_in].reshape(b_, h, p)
                Bm = conv[:, d_in:d_in + g * n].reshape(b_, g, n)
                Cm = conv[:, d_in + g * n:].reshape(b_, g, n)
            with scope("ssm.update"):
                y, ssm = ssm_step(cache["ssm"], xs, dt[:, 0], A, Bm, Cm, Df)
                y = y.reshape(b_, 1, d_in)
            with scope("ssm.conv"):
                new_cache = {
                    "conv": jnp.concatenate(
                        [cache["conv"][:, 1:], xbc.astype(cache["conv"].dtype)],
                        axis=1),
                    "ssm": ssm}
        else:
            with scope("ssm.conv"):
                if seq_lens is not None:
                    real = jnp.arange(t)[None, :] < seq_lens[:, None]    # (b, t)
                    dt = jnp.where(real[..., None], dt, 0.0)
                conv = causal_conv(xbc, cw, cb)
                xs = conv[..., :d_in].reshape(b_, t, h, p)
                Bm = conv[..., d_in:d_in + g * n].reshape(b_, t, g, n)
                Cm = conv[..., d_in + g * n:].reshape(b_, t, g, n)
            with scope("ssm.update"):
                y, ssm = ssd_chunked(xs, dt, A, Bm, Cm, Df, self.chunk_size)
                y = y.reshape(b_, t, d_in)
            if cache is not None:
                with scope("ssm.conv"):
                    lens = (jnp.full((b_,), t, jnp.int32) if seq_lens is None
                            else seq_lens)
                    new_cache = {
                        "conv": last_inputs(xbc, lens, K).astype(cache["conv"].dtype),
                        "ssm": ssm}
        with scope("ssm.out"):
            y = gated_group_norm(y, z, norm_w.astype(jnp.float32), g, self.eps)
            out = y.astype(self.dtype) @ w_out.astype(self.dtype)
        return out, new_cache
