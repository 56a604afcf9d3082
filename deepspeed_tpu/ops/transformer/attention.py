"""Attention ops with switchable implementations.

The training-side analogue of the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu`` + strided-batch-gemm attention in
``csrc/transformer/ds_transformer_cuda.cpp``): on TPU the baseline is plain XLA einsum+softmax
(which the compiler fuses and tiles onto the MXU); the ``flash``/``ring`` implementations are
Pallas kernels (``ops/attention/``) selected by name so models stay implementation-agnostic.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, mask: Optional[jnp.ndarray] = None,
                  softmax_scale: Optional[float] = None,
                  dropout_rate: float = 0.0,
                  dropout_rng=None) -> jnp.ndarray:
    """Reference multi-head attention.

    Shapes: q/k/v ``(batch, seq, heads, head_dim)`` → out ``(batch, seq, heads, head_dim)``.
    Softmax runs in fp32 regardless of input dtype (the reference's attn_softmax kernels do the
    same for fp16 inputs).
    """
    *_, t, h, d = q.shape
    s = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t)
        logits = jnp.where(causal_mask[None, None], logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        # mask: (batch, s) padding mask or (batch, 1, t, s) full mask
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        logits = jnp.where(mask.astype(bool), logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


# Minimum sequence length for the Pallas flash kernel under ``auto``. Since the
# grid-pipelined rewrite (K/V streamed through the grid's innermost dim, online-softmax
# carry in VMEM scratch) flash wins at EVERY measured length — v5e, GPT-2-shaped
# b*t=8192 h=12 d=64 bf16, fwd: 2.6x at 1024 / 8.6x at 4096; fwd+bwd: 2.8x at 1024 /
# 6.3x at 4096 (see tests/unit/ops/test_flash_crossover.py) — so the kernel floor only
# excludes degenerate tiny shapes where block padding dominates. What causality skips
# there: up to 1024 tokens a head (or the 128 // d_head heads of a lane tile, which the
# kernels read in place from (b, t, h*d)) is ONE kernel block, so nothing is skipped
# between blocks; inside it the kernel leaves out the 512-row (forward) or 256-column (backward)
# sub-tiles above the diagonal — none at 256-512 tokens forward, a quarter of the square
# at 1024 forward, 37.5 % backward — and masks only the sub-tiles on the diagonal. Whole
# blocks above the diagonal are skipped from 2048 tokens on (``ops/attention/flash.py``).
FLASH_MIN_SEQ = 256


def flash_eligible(t: int) -> bool:
    """Kernel-eligibility rule shared by every flash-vs-XLA dispatch site:
    t % 128 != 0 degrades ``_block_sizes`` to tiny MXU-starved blocks, and below
    ``FLASH_MIN_SEQ`` block padding dominates — those shapes stay on XLA."""
    return t >= FLASH_MIN_SEQ and t % 128 == 0


def resolves_to_flash(impl, t: int) -> bool:
    """Whether attention ``impl`` at sequence length ``t`` is the Pallas flash kernel:
    ``"flash"`` always, ``"auto"`` on a TPU at a ``flash_eligible`` length."""
    return impl == "flash" or (impl == "auto" and jax.default_backend() == "tpu"
                               and flash_eligible(t))


def flash_reads_fused_qkv(impl, t: int, n_head: int, head_dim: int,
                          dropout_rate: float = 0.0) -> bool:
    """Whether a fused q | k | v projection goes to the flash kernels as ONE operand
    (``ops/attention/flash.py: flash_attention_qkv``) instead of being split: the
    attention is the kernel with nothing it hands to XLA (dropout), the heads lie in
    whole lane tiles, and no tensor axis shards them (a contiguous shard of the fused
    lanes mixes q, k and v)."""
    if dropout_rate > 0.0 or not resolves_to_flash(impl, t):
        return False
    from ...parallel.mesh import AXIS_TENSOR, get_global_mesh
    from ..attention.flash import heads_a_block
    mesh = get_global_mesh()
    return heads_a_block(n_head, head_dim) > 0 and (
        mesh is None or mesh.size(AXIS_TENSOR) == 1)


def _auto_attention(q, k, v, **kw):
    if flash_eligible(q.shape[1]):
        from ..attention.flash import flash_attention
        return flash_attention(q, k, v, **kw)
    return xla_attention(q, k, v, **kw)


def get_attention_impl(name: str = "xla"):
    """Resolve an attention implementation by name:
    ``auto`` | ``xla`` | ``flash`` | ``ring`` | ``ulysses`` (or a pre-bound callable).

    ``auto`` on a real TPU backend dispatches by sequence length — the Pallas flash
    kernel from ``FLASH_MIN_SEQ`` up (it beats XLA at all measured lengths), XLA below;
    elsewhere always XLA (on CPU the Pallas kernel runs in interpreter mode, which is
    orders of magnitude slower — fine for kernel unit tests, wrong as a default).
    """
    if callable(name):
        return name  # pre-bound impl (e.g. make_sparse_attention_impl(config))
    if name == "auto":
        if jax.default_backend() != "tpu":
            return xla_attention
        return _auto_attention
    if name == "xla":
        return xla_attention
    if name == "flash":
        from ..attention.flash import flash_attention
        return flash_attention
    if name == "ring":
        from ..attention.ring import ring_attention
        return ring_attention
    if name == "ulysses":
        from ..attention.ulysses import ulysses_attention
        return ulysses_attention
    raise ValueError(f"Unknown attention impl {name!r}")
