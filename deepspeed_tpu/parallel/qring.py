"""Fused quantized collective-matmul ring — dequant-GEMM inside the ppermute
ring with an intN + error-feedback wire payload.

PR 5's Pallas dequant-matmul (``ops/quantizer/fused_matmul.py``) and PR 3's
chunked collective matmuls (``parallel/overlap.py``) deliberately did not
compose: quantized row-parallel was monolithic-psum only, so TP decode over
quantized weights paid full fp wire time with zero compute/comm overlap. This
module is the composition (the fused computation-collective idiom of arXiv
2305.06942 with EQuARX, arXiv 2506.17615, as the quantized-wire precedent):

- the per-chunk GEMM is the fused dequant-matmul over the shard's WHOLE
  packed weight slab (int8 or nibble-packed int4, per-group scales sharded
  with their k rows, so each rank dequants locally — group boundaries never
  cross the wire; only fp accumulator chunks do, which is why the ring can
  now re-slice freely);
- the ring payload itself is quantized: intN chunks (``chunk_bits`` in
  {4, 8, 16}) with per-block absmax scales, under the same error-feedback
  contract as ``comm/compressed.py`` — ``transmitted + new_error == chunk +
  error`` exactly per hop, non-finite values zeroed BEFORE the cast
  (overflow-gated), residual carried ACROSS ring steps within a dispatch.

EF residual lifecycle in serving: a decode dispatch is ONE transmission, so
:func:`quant_row_parallel_apply` starts every dispatch from a zero residual
and discards the returned one — the "residual reset on load" contract of the
DP gradient sync is therefore satisfied trivially (``load_checkpoint`` →
``_place_params`` re-quantizes; no stale wire state can survive it), and
bit-exact request retry (the serving contract) is preserved because no state
leaks between dispatches. Callers that DO iterate transmissions (the EF
convergence smoke in ``tests/unit/parallel/test_qring.py``) thread
``residual`` through repeated calls and get the cumulative-transmission EF
guarantee back.

Wire-bytes model (per worker, one dispatch; cross-checked exactly by the
``analysis/collectives.py`` schema pass — the recorded span, the closed form
:func:`analysis.collectives.qring_wire_bytes`, and the jaxpr ppermute-operand
sum must all agree to the byte):

    hops x intn_wire_nbytes(m_blk * n_dir, quant_block, chunk_bits)

with ``m_blk = m / W`` rows per ring chunk, ``n_dir = n`` (unidirectional,
``W - 1`` hops) or ``n / 2`` (bidirectional, ``2 (W - 1)`` half-width hops).
At tp=4 / int8 wire / block=256 that is ~0.25x the fp32 ring's bytes.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..comm.compressed import (intn_blockwise_compress,
                               intn_blockwise_decompress, intn_wire_nbytes)
from ..observability import scope
from ..utils.comms_logging import record_collective
from ..utils.jax_compat import shard_map
from .mesh import AXIS_TENSOR, get_global_mesh
from .overlap import OverlapConfig, _ring_perm


def _wire_hop(chunk, residual, axis_name, perm, wire_bits: Optional[int],
              block: int):
    """One quantized ring hop: EF-compress the fp accumulator chunk, ship
    carrier + scales, decompress on arrival. Returns ``(received fp chunk,
    new residual)``; ``wire_bits=None`` is the fp (lossless) wire used for
    exact ground-truthing."""
    if wire_bits is None:
        return jax.lax.ppermute(chunk, axis_name, perm), residual
    flat = chunk.reshape(-1) + residual
    # overflow gate (same contract as comm/compressed.py): a single inf/nan
    # must not poison the intN cast or the residual — it is zeroed on the
    # wire, and the caller's own (never-wired) partial keeps local semantics
    flat = jnp.where(jnp.isfinite(flat), flat, 0.0)
    payload, scales = intn_blockwise_compress(flat, block, wire_bits)
    new_residual = flat - intn_blockwise_decompress(
        payload, scales, flat.shape[0], block, wire_bits)
    payload = jax.lax.ppermute(payload, axis_name, perm)
    scales = jax.lax.ppermute(scales, axis_name, perm)
    received = intn_blockwise_decompress(
        payload, scales, flat.shape[0], block, wire_bits)
    return received.reshape(chunk.shape), new_residual


def _chunk_gemm(x, q, scales, bits: int, groups: int, m_blk: int,
                interpret: Optional[bool]):
    """Per-ring-chunk GEMM closure over one (column slice of a) quant slab.

    Fused backend: the Pallas dequant-matmul streams the packed slab per
    chunk. Otherwise the per-group dequant is hoisted HERE, once per trace,
    OUTSIDE the ring steps — the loop-invariance contract the qring lint
    lane pins (a per-step dequant would re-materialise the fp weight W
    times and regrow the hot-path HBM read the quant store exists to
    shrink)."""
    from ..ops.quantizer.fused_matmul import (_block_config, _interpret,
                                              fused_backend_active,
                                              quantized_matmul)
    from ..ops.quantizer.quant import dequantize_grouped, unpack_int4
    k = x.shape[1]
    n = scales.shape[-1]
    interp = _interpret() if interpret is None else interpret
    group = k // groups
    if fused_backend_active() and \
            _block_config(m_blk, k, n, bits, group, interp) is not None:
        def gemm(rows):
            return quantized_matmul(rows, q, scales, bits=bits,
                                    out_dtype=jnp.float32, interpret=interp)
        return gemm
    w = dequantize_grouped(unpack_int4(q, groups) if bits == 4 else q, scales)

    def gemm(rows):
        return jnp.dot(rows.astype(jnp.float32), w,
                       preferred_element_type=jnp.float32)
    return gemm


@scope("comm.fused_quant_matmul_reduce_scatter")
def fused_quant_matmul_reduce_scatter(x, q, scales, axis_name, *,
                                      bits: int = 8,
                                      wire_bits: Optional[int] = 8,
                                      quant_block: int = 256,
                                      bidirectional: bool = True,
                                      residual=None, interpret=None,
                                      site=None) -> Tuple[jnp.ndarray,
                                                          jnp.ndarray]:
    """``psum_scatter(x @ dequant(q, scales), dim 0, tiled)`` as a
    dequant-GEMM / accumulate ring with a quantized wire payload.

    ``x``: ``(m, k_loc)`` local activation slice (``m`` divisible by the axis
    size); ``q``/``scales``: THIS shard's weight slab (int8 ``(k_loc, n)`` or
    packed int4 ``(k_loc/2, n)``; f32 ``(k_loc/group, n)``). Returns
    ``(out (m/W, n) f32, new_residual (m/W * n,) f32)``.

    Ring structure mirrors ``overlap.chunked_matmul_reduce_scatter`` (each
    ICI hop hides under the next block's dequant-GEMM); each hop additionally
    EF-quantizes the travelling accumulator via :func:`_wire_hop`. The
    residual a rank carries follows its SEND slot across the W-1 steps (EF
    across ring steps); pass ``residual`` to chain dispatches, or None for
    the serving fresh-per-dispatch contract. ``wire_bits=None`` keeps the
    wire fp (bit-identical hops; last-ulp vs the monolithic psum, summation
    order only).
    """
    W = jax.lax.psum(1, axis_name)
    m, k = x.shape
    groups, n = scales.shape[-2], scales.shape[-1]
    if W == 1:
        gemm = _chunk_gemm(x, q, scales, bits, groups, m, interpret)
        res = residual if residual is not None \
            else jnp.zeros((m * n,), jnp.float32)
        return gemm(x), res
    if m % W != 0:
        # must survive python -O: dynamic_slice CLAMPS out-of-range block
        # starts, so an unguarded ragged m would silently double-sum rows
        raise ValueError(
            f"fused_quant_matmul_reduce_scatter: m={m} not divisible by "
            f"axis size {W} — pad rows first (see quant_row_parallel_apply)")
    idx = jax.lax.axis_index(axis_name)
    m_blk = m // W
    if residual is None:
        residual = jnp.zeros((m_blk * n,), jnp.float32)
    bidir = bidirectional and n % 2 == 0
    n_dir = n // 2 if bidir else n
    hop_bytes = (m_blk * n_dir * 4 if wire_bits is None
                 else intn_wire_nbytes(m_blk * n_dir, quant_block, wire_bits))
    if site is not None:
        record_collective(site, "reduce_scatter",
                          (W - 1) * (2 if bidir else 1) * hop_bytes, W,
                          overlapped=True)

    def rows(b):
        return jax.lax.dynamic_slice(x, (b * m_blk, 0), (m_blk, k))

    if not bidir:
        gemm = _chunk_gemm(x, q, scales, bits, groups, m_blk, interpret)
        perm = _ring_perm(W, 1)
        acc = gemm(rows((idx - 1) % W))
        r = residual
        for s in range(1, W):
            acc, r = _wire_hop(acc, r, axis_name, perm, wire_bits, quant_block)
            acc = acc + gemm(rows((idx - 1 - s) % W))
        return acc, r

    # bidirectional: column halves travel opposite ring directions (both ICI
    # links busy at half the per-step payload); the packed int4 layout splits
    # cleanly on n — packing is along k, so no group is re-sliced
    h = n // 2
    hq = q.shape[-1] // 2
    gemm_a = _chunk_gemm(x, q[:, :hq], scales[:, :h], bits, groups, m_blk,
                         interpret)
    gemm_b = _chunk_gemm(x, q[:, hq:], scales[:, h:], bits, groups, m_blk,
                         interpret)
    r_a, r_b = residual[:m_blk * h], residual[m_blk * h:]
    perm_f, perm_b = _ring_perm(W, 1), _ring_perm(W, -1)
    acc_a = gemm_a(rows((idx - 1) % W))
    acc_b = gemm_b(rows((idx + 1) % W))
    for s in range(1, W):
        acc_a, r_a = _wire_hop(acc_a, r_a, axis_name, perm_f, wire_bits,
                               quant_block)
        acc_a = acc_a + gemm_a(rows((idx - 1 - s) % W))
        acc_b, r_b = _wire_hop(acc_b, r_b, axis_name, perm_b, wire_bits,
                               quant_block)
        acc_b = acc_b + gemm_b(rows((idx + 1 + s) % W))
    return jnp.concatenate([acc_a, acc_b], axis=1), \
        jnp.concatenate([r_a, r_b])


@scope("comm.fused_quant_allgather_matmul")
def fused_quant_allgather_matmul(x, q, scales, axis_name, *, bits: int = 8,
                                 wire_bits: Optional[int] = 8,
                                 quant_block: int = 256,
                                 bidirectional: bool = True, residual=None,
                                 interpret=None, site=None
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``all_gather(x, axis=0, tiled) @ dequant(q, scales)`` as a ppermute
    ring with a quantized activation payload.

    ``x``: ``(m_loc, k)`` row block; ``q``/``scales``: the LOCAL column
    slice of the quant slab. Returns ``((W*m_loc, n_loc) f32,
    new_residual (m_loc*k,) f32)``.

    Unlike the reduce-scatter ring (whose accumulator changes at every hop
    and must be re-quantized), each origin's chunk here is compressed ONCE and the
    CARRIER is forwarded verbatim — quantization error is one-shot per
    origin, never compounded per hop, and every rank (the origin included)
    GEMMs the dequantized chunk so the replicated output stays identical
    across ranks. EF applies at the origin's single compression.
    """
    W = jax.lax.psum(1, axis_name)
    m_loc, k = x.shape
    groups, n = scales.shape[-2], scales.shape[-1]
    if residual is None:
        residual = jnp.zeros((m_loc * k,), jnp.float32)
    gemm = _chunk_gemm(x, q, scales, bits, groups, m_loc, interpret)
    if W == 1:
        return gemm(x.astype(jnp.float32)), residual
    hop_bytes = (m_loc * k * 4 if wire_bits is None
                 else intn_wire_nbytes(m_loc * k, quant_block, wire_bits))
    if site is not None:
        # W-1 full-chunk hops total whichever direction split is used
        record_collective(site, "all_gather", (W - 1) * hop_bytes, W,
                          overlapped=True)
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((W * m_loc, n), jnp.float32)

    def write(out, block, src):
        return jax.lax.dynamic_update_slice(out, gemm(block), (src * m_loc, 0))

    if wire_bits is None:
        xf = x.astype(jnp.float32)
        if not bidirectional:
            cur = xf
            for s in range(W):
                out = write(out, cur, (idx - s) % W)
                if s != W - 1:
                    cur = jax.lax.ppermute(cur, axis_name, _ring_perm(W, 1))
            return out, residual
        fwd = bwd = xf
        out = write(out, xf, idx)
        for s in range(1, W // 2 + 1):
            fwd = jax.lax.ppermute(fwd, axis_name, _ring_perm(W, 1))
            out = write(out, fwd, (idx - s) % W)
            if s <= (W - 1) // 2:
                bwd = jax.lax.ppermute(bwd, axis_name, _ring_perm(W, -1))
                out = write(out, bwd, (idx + s) % W)
        return out, residual

    flat = x.reshape(-1).astype(jnp.float32) + residual
    flat = jnp.where(jnp.isfinite(flat), flat, 0.0)
    payload, pscales = intn_blockwise_compress(flat, quant_block, wire_bits)
    own = intn_blockwise_decompress(payload, pscales, m_loc * k, quant_block,
                                    wire_bits)
    new_residual = flat - own
    own = own.reshape(m_loc, k)

    def hop(carrier, step):
        p, sc = carrier
        perm = _ring_perm(W, step)
        p = jax.lax.ppermute(p, axis_name, perm)
        sc = jax.lax.ppermute(sc, axis_name, perm)
        blk = intn_blockwise_decompress(p, sc, m_loc * k, quant_block,
                                        wire_bits).reshape(m_loc, k)
        return (p, sc), blk

    out = write(out, own, idx)
    if not bidirectional:
        cur = (payload, pscales)
        for s in range(1, W):
            cur, blk = hop(cur, 1)
            out = write(out, blk, (idx - s) % W)
        return out, new_residual
    fwd = bwd = (payload, pscales)
    for s in range(1, W // 2 + 1):
        fwd, blk = hop(fwd, 1)
        out = write(out, blk, (idx - s) % W)
        if s <= (W - 1) // 2:
            bwd, blk = hop(bwd, -1)
            out = write(out, blk, (idx + s) % W)
    return out, new_residual


# ------------------------------------------- GSPMD-callable serving wrapper
def quant_row_parallel_apply(x, q, scales, *, bits: int, dtype,
                             mesh, batch_axes, cfg: OverlapConfig,
                             interpret=None, site: str = "tp.row_dense"):
    """Quantized row-parallel dense through the fused quantized ring — the
    quant-node analogue of ``overlap.row_parallel_dense_apply`` (same row
    padding, same ``site``/``site + ".gather"`` span convention, so the two
    rings' spans line up column-for-column).

    The ring's wire width and scale block come from the engine's
    ``comm_overlap`` config (``chunk_bits``/``quant_block``); the EF residual
    is freshly zero each dispatch and the returned one discarded (see module
    docstring for why serving resets rather than persists it). Bias handling
    stays with the caller (``quant_dense_apply``)."""
    b, t, k = x.shape
    n = scales.shape[-1]
    tp = mesh.size(AXIS_TENSOR)
    bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) if batch_axes \
        else 1
    m_loc = (b // bsz) * t
    pad = (-m_loc) % tp
    # decomposed allreduce = quantized reduce-scatter ring (span recorded by
    # the primitive under ``site``) + tiled all-gather of the small serve-
    # dtype row blocks, recorded here — same shape math as the fp path
    record_collective(site + ".gather", "all_gather",
                      (tp - 1) * ((m_loc + pad) // tp) * n
                      * jnp.dtype(dtype).itemsize, tp, overlapped=False)

    def body(x_l, q_l, s_l):
        bl, tl, kl = x_l.shape
        x2 = x_l.reshape(bl * tl, kl)
        if pad:
            x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        y_loc, _ = fused_quant_matmul_reduce_scatter(
            x2, q_l, s_l, AXIS_TENSOR, bits=bits, wire_bits=cfg.chunk_bits,
            quant_block=cfg.quant_block, bidirectional=cfg.bidirectional,
            interpret=interpret, site=site)
        y = jax.lax.all_gather(y_loc.astype(dtype), AXIS_TENSOR, axis=0,
                               tiled=True)
        if pad:
            y = y[:bl * tl]
        return y.reshape(bl, tl, -1)

    bspec = batch_axes or None
    return shard_map(
        body, mesh=mesh.mesh, axis_names=set(batch_axes) | {AXIS_TENSOR},
        in_specs=(P(bspec, None, AXIS_TENSOR), P(AXIS_TENSOR, None),
                  P(AXIS_TENSOR, None)),
        out_specs=P(bspec, None, None), check_vma=False)(x, q, scales)
