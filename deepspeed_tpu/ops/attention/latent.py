"""Latent attention (DeepSeek-V2's MLA as ``sarvam_mla`` keeps it): the rotary
frequencies of ``deepseek_yarn``, the rotation of the shared rotary part, and
the ABSORBED one-token attention over cached latent rows.

A token's cached row is ``[c (kv_lora_rank) | k^r (rope lanes) | zero lanes]``
(``ops/paged_attention.latent_row_lanes`` wide): the normed latent every
head's keys AND values are expanded from, and the one rotary key all heads
share. Decode never expands them: a head's query is taken through its key
expansion first (``q~ = W_uk^T q^n``, done by the caller), so its score
against a row is ``[q~ ; q^r] . row`` and its output ``W_uv (sum_j p_j
row_j[:rank])``: two products a head against ONE row a token, whatever the
number of heads. A prefill expands keys and values for its prompt and runs the
flash kernel (``models/causal_lm.py``).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .decode import NEG_INF, live_block


def yarn_mscale(factor: float, mscale: float) -> float:
    """``m(s, a) = 0.1 a ln s + 1`` for ``s > 1``, else 1."""
    return 0.1 * float(mscale) * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies of ``deepseek_yarn``: per frequency
    the blend of the original ``base^(-2i/dim)`` and the interpolated one
    (``/ factor``) by a linear ramp between the dimensions that make
    ``beta_fast`` and ``beta_slow`` rotations over ``original_max`` positions
    (the family's ``yarn_find_correction_range`` / ``yarn_linear_ramp_mask``):
    the fast dimensions keep their frequency, the slow ones are stretched."""
    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def rotate_pairs(x, positions, inv_freq, mscale: float = 1.0):
    """``x (..., t, h, 2n)`` rotated at ``positions (..., t)``: lanes ``(2i,
    2i + 1)`` are a pair turned by ``positions * inv_freq[i]``, as the family's
    code pairs them. The result is laid out de-interleaved (the pairs' first
    lanes, then their second), which is that code's layout too; a query and a
    key rotated here meet lane for lane, and nothing else reads the lanes."""
    n = x.shape[-1] // 2
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (n, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    ang = positions[..., None, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def latent_decode_attention(q, rows, cache_len, scale: float):
    """One-token absorbed attention over the batch's LIVE latent rows.

    ``q (b, h, w)``: every head's ``[q~ ; q^r ; 0]`` in the cache's type;
    ``rows (b, 1, T, w)``: the cached rows (keys and values at once);
    ``cache_len (b,)``: the rows each sequence sees. Returns ``(b, h, w)``
    float32, ``sum_j p_j row_j`` a head: the caller keeps the latent's lanes.

    The cache is walked in blocks of ``live_block(T)`` rows up to the batch's
    longest sequence under an online softmax, as
    :func:`~.decode.decode_attention_live` walks keys and values, and for its
    reason a sequence's result is bit-equal whatever the trip count and the
    cap (a block past its length adds exact zeros). Both products take the
    rows as stored (bf16 on the chip: the MXU's rate) and accumulate in
    float32; the probabilities are rounded to the rows' type for the second,
    as the flash kernel rounds them for its values."""
    b, h, w = q.shape
    T = rows.shape[2]
    B = live_block(T)
    rows = rows[:, 0]
    place = jnp.arange(B, dtype=cache_len.dtype)

    def body(j, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(rows, j * B, B, axis=1)     # (b, B, w)
        s = jnp.einsum("bhw,btw->bht", q, blk,
                       preferred_element_type=jnp.float32) * scale
        seen = (j * B + place)[None, None, :] < cache_len[:, None, None]
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bht,btw->bhw", p.astype(blk.dtype), blk,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    blocks = jnp.minimum((jnp.max(cache_len) + B - 1) // B, T // B)
    m0 = jnp.full((b, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h), jnp.float32)
    acc0 = jnp.zeros((b, h, w), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, blocks, body, (m0, l0, acc0))
    return acc / jnp.where(l > 0, l, 1.0)[..., None]
