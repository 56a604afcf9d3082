"""Gather-fused MoE FFN for the decode hot loop.

TPU-native equivalent of the reference's dedicated MoE inference ops
(``deepspeed/ops/transformer/inference/moe_inference.py:463`` — gating + selected-expert
FFN in the per-token decode path). A decode step carries one token per sequence, so the
FFN touches exactly ``n = b*k`` expert slices of the stacked ``(e, d, f)`` weights. The
naive ``w1[idx]`` gather materialises an HBM copy of those slices (gather read + write +
matmul re-read = 3× weight traffic — measured 68% of dense decode tok/s at 125M/8e);
this kernel instead selects each token's expert block in the ``BlockSpec`` index maps
(scalar-prefetched indices), so the chosen expert's weights stream from HBM into the
matmul exactly once.

Grid ``(n, f_blocks)``: for token ``i`` and hidden block ``j``,
``h_j = act(x_i @ w1[idx_i, :, j] + b1[idx_i, j])`` then ``y_i += h_j @ w2[idx_i, j, :]``
— the second matmul folds the f-blocked partial sums into the output, so nothing of size
``f`` ever lands in HBM.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.device import pallas_interpret as _interpret


def _kernel(idx_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, *, act):
    # x/b1/b2/o ride a singleton middle axis so their (1, dim) tails satisfy the
    # TPU block-shape rule (last two dims divide (8, 128) or equal the array's)
    j = pl.program_id(1)
    x = x_ref[0]                                                 # (1, d)
    h = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
    h = act(h + b1_ref[0].astype(jnp.float32))                   # (1, bf)
    part = jnp.dot(h.astype(w2_ref.dtype), w2_ref[0],
                   preferred_element_type=jnp.float32)           # (1, d)

    @pl.when(j == 0)
    def _():
        o_ref[0] = part + b2_ref[0].astype(jnp.float32)

    @pl.when(j > 0)
    def _():
        o_ref[0] += part


def _pick_block_f(d: int, f: int) -> int:
    # two weight blocks of (d, bf) resident + Pallas double-buffering; keep under ~8MB
    for bf in (512, 256, 128):
        if f % bf == 0 and 2 * 2 * d * bf * 2 <= 8 * 2 ** 20:
            return bf
    return 0


def moe_decode_ffn_xla(x, idx, w1, b1, w2, b2, act) -> jnp.ndarray:
    """Reference path: gather the selected experts' weights, then matmul.

    ``x``: (n, d) tokens; ``idx``: (n,) expert ids; stacked weights ``w1`` (e, d, f),
    ``b1`` (e, f), ``w2`` (e, f, d), ``b2`` (e, d). Returns (n, d) float32."""
    cdtype = x.dtype
    h = jnp.einsum("nm,nmf->nf", x, w1[idx].astype(cdtype)) + \
        b1[idx].astype(cdtype)
    out = jnp.einsum("nf,nfm->nm", act(h), w2[idx].astype(cdtype)) + \
        b2[idx].astype(cdtype)
    return out.astype(jnp.float32)


def _gather_expert(w, idx, cdtype):
    """Select per-token expert slices of a stacked weight that may be a quant
    node. Quantized: the HBM gather reads int8 (or packed int4) bytes — 2-4x
    less weight traffic than gathering bf16 — and the dequant runs on the
    small gathered ``(n, ...)`` slice, where XLA fuses it into the consuming
    einsum's operand read."""
    from ..quantizer.quant import (dequantize_grouped, is_quant_node,
                                   node_bits, node_qs, unpack_int4)
    if not is_quant_node(w):
        return w[idx].astype(cdtype)
    q, s = node_qs(w)
    qg, sg = q[idx], s[idx]
    if node_bits(w) == 4:
        qg = unpack_int4(qg, s.shape[-2])
    return dequantize_grouped(qg, sg).astype(cdtype)


def moe_decode_ffn_quant(x, idx, w1, b1, w2, b2, act) -> jnp.ndarray:
    """Selected-expert FFN over (possibly) quantized stacked expert weights.

    Same contract as :func:`moe_decode_ffn_xla` except ``w1``/``w2`` may be
    quant nodes (``ops/quantizer`` engine-tree leaves); ``b1``/``b2`` are
    always fp. Per-expert grouped scales ride the gather, so numerics equal
    dequantize-then-gather exactly."""
    cdtype = x.dtype
    h = jnp.einsum("nm,nmf->nf", x, _gather_expert(w1, idx, cdtype)) + \
        b1[idx].astype(cdtype)
    out = jnp.einsum("nf,nfm->nm", act(h), _gather_expert(w2, idx, cdtype)) + \
        b2[idx].astype(cdtype)
    return out.astype(jnp.float32)


def moe_decode_ffn(x, idx, w1, b1, w2, b2, act) -> jnp.ndarray:
    """Selected-expert FFN: (n, d) tokens → (n, d) float32 (combine weights applied by
    the caller). Falls back to the XLA gather path when shapes don't block cleanly."""
    n, d = x.shape
    e, _, f = w1.shape
    bf = _pick_block_f(d, f)
    if _interpret() and bf == 0:
        bf = f                    # interpret mode has no tiling constraints
    if bf == 0 or (d % 128 != 0 and not _interpret()):
        return moe_decode_ffn_xla(x, idx, w1, b1, w2, b2, act)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, f // bf),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j, idx_ref: (i, 0, 0)),
            pl.BlockSpec((1, d, bf), lambda i, j, idx_ref: (idx_ref[i], 0, j)),
            pl.BlockSpec((1, 1, bf), lambda i, j, idx_ref: (idx_ref[i], 0, j)),
            pl.BlockSpec((1, bf, d), lambda i, j, idx_ref: (idx_ref[i], j, 0)),
            pl.BlockSpec((1, 1, d), lambda i, j, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, j, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), jnp.float32),
        grid_spec=grid_spec,
        name="moe_decode_ffn",
        interpret=_interpret(),
    )(idx.astype(jnp.int32), x[:, None, :], w1, b1[:, None, :], w2,
      b2[:, None, :])
    return out[:, 0, :]
