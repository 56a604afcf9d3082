"""Fused dequant-matmul kernels — the weight-streaming quantized decode hot path.

The r5 north-star bench shows 7B decode is weight-bandwidth-bound (~14.1 GB of
HBM weight reads per step). Grouped int8/int4 storage only pays off if the
QUANTIZED bytes are what actually streams from HBM: dequantizing a whole weight
tree inside the compiled decode body re-materialises bf16 weights per step and
the hot-path read never shrinks. These Pallas kernels fuse dequantization into
the matmul instead (the TPU-native analogue of the reference's
``csrc/quantization/dequantize.cu`` + fused inference GEMMs): quantized weight
blocks are pipelined HBM→VMEM (the same double-buffered streaming idiom as
``ops/attention/decode.py`` — here via the grid pipeline, since weight blocks
are static-shaped), dequantized in-register against their per-group scales, and
accumulated in fp32.

Two block regimes behind one kernel:

- decode GEMV / skinny GEMM (``m <= SKINNY_M``): one row-block, wide ``n``
  blocks — every weight byte is read exactly once per step;
- prefill GEMM: ``m`` additionally blocked so activations tile VMEM.

int4 uses the per-group split-half packed layout of ``quant.pack_int4`` (two
nibbles per byte; unpack = shift + concat, no interleave), for a 4x weight-read
reduction vs bf16.

``quant_dense_apply`` is the model-facing entry: it takes a quant NODE
(``{__int8_q__|__int4_q__, *_scale__}``, the engine's parameter-tree leaf
format), handles (b, t, k) activations, TP sharding (column- or row-parallel
via shard_map — Pallas is opaque to GSPMD, same reason ``_sharded_decode``
wraps the decode-attention kernel), and falls back to an XLA dequant+matmul
whenever the fused path is ineligible (non-TPU backend unless forced, ragged
shapes, non-divisible shards).
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...utils.device import pallas_interpret as _interpret
from .quant import (dequantize_node, is_quant_node, node_bits,
                    node_logical_shape, node_qs)

# below this row count the matmul is a GEMV/skinny GEMM: keep one m block and
# spend VMEM on wide n blocks (weight streaming dominates)
SKINNY_M = 256


def force_fused() -> bool:
    """Test hook: route engine-level paths through the fused (interpret-mode)
    kernels on a non-TPU backend."""
    return os.environ.get("DS_TPU_WQ_FORCE_FUSED") == "1"


def fused_backend_active() -> bool:
    """Fused kernels stream on a real TPU; everywhere else they only run when
    forced (tests) — the XLA fallback with hoisted dequant is faster on CPU."""
    return jax.default_backend() == "tpu" or force_fused()


# ------------------------------------------------------------------- kernel
def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, *, bits: int, group: int):
    """One (bm, bn) output block, accumulating over the k grid dim.

    x_ref: (bm, bk) activations; q_ref: (bk, bn) int8 or (bk//2, bn) packed
    int4; s_ref: (bk//group, bn) f32 scales; o_ref: (bm, bn) f32.
    """
    kb = pl.program_id(2)
    x = x_ref[...]
    s = s_ref[...]
    gb, bn = s.shape
    if bits == 8:
        qg = q_ref[...].reshape(gb, group, bn).astype(jnp.float32)
    else:
        # per-group split-half layout: low nibbles are the group's first half,
        # high nibbles the second — unpack is a concat, no interleave. Shifts
        # run in int32 (arithmetic >> sign-extends the nibbles).
        pg = q_ref[...].reshape(gb, group // 2, bn).astype(jnp.int32)
        lo = (pg << 28) >> 28
        hi = pg >> 4
        qg = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    w = (qg * s[:, None, :]).reshape(gb * group, bn).astype(x.dtype)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kb == 0)
    def _():
        o_ref[...] = acc

    @pl.when(kb > 0)
    def _():
        o_ref[...] += acc


def _pick_block(dim: int, candidates) -> int:
    for c in candidates:
        if dim % c == 0:
            return c
    return 0


def _block_config(m: int, k: int, n: int, bits: int, group: int, interpret: bool):
    """(bm, bk, bn) or None when the shape can't tile the compiled kernel.
    Interpret mode (tests/tiny models) runs whole-array blocks — no alignment
    constraints there."""
    if interpret:
        return m, k, n
    if k % group:
        return None
    bn = _pick_block(n, (512, 256, 128))
    # k blocks must cover whole scale groups; target ~512 rows so an int8
    # (bk, bn) block is <= 256 KB and the grid pipeline double-buffers cheaply
    bk = 0
    for c in (1024, 512, 256, 128):
        if c % group == 0 and k % c == 0:
            bk = c
            break
    if bk == 0 and k == group:
        bk = k
    if bits == 4 and bk % 2:
        return None
    if not bn or not bk:
        return None
    # m never gates eligibility: the wrapper zero-pads rows up to bm
    bm = m if m <= SKINNY_M else 256
    return bm, bk, bn


def quantized_matmul(x, q, scales, *, bits: int = 8, out_dtype=None,
                     interpret=None) -> jnp.ndarray:
    """``x (m, k) @ dequant(q, scales) -> (m, n)`` with in-register dequant.

    ``q``: int8 ``(k, n)`` (bits=8) or packed ``(k//2, n)`` (bits=4);
    ``scales``: f32 ``(k//g, n)``. Accumulates f32; returns ``out_dtype``
    (default: x.dtype). Falls back to the XLA dequant+matmul when the shape
    cannot tile the compiled kernel.
    """
    m, k = x.shape
    groups, n = scales.shape
    group = k // groups
    out_dtype = out_dtype or x.dtype
    interp = _interpret() if interpret is None else interpret
    cfg = _block_config(m, k, n, bits, group, interp)
    if cfg is None:
        return quantized_matmul_xla(x, q, scales, bits=bits, out_dtype=out_dtype)
    bm, bk, bn = cfg
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = ((m + pad) // bm, n // bn, k // bk)
    kq = bk if bits == 8 else bk // 2
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, bits=bits, group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((kq, bn), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, kb: (kb, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), jnp.float32),
        name=f"qmm_int{bits}",
        interpret=interp,
    )(x, q, scales)
    return out[:m].astype(out_dtype)


def quantized_matmul_xla(x, q, scales, *, bits: int = 8, out_dtype=None):
    """XLA reference/fallback: dequantize (fused by XLA into the consumer's
    operand read) then matmul. Ground truth for the kernel parity tests."""
    from .quant import dequantize_grouped, unpack_int4
    out_dtype = out_dtype or x.dtype
    if bits == 4:
        q = unpack_int4(q, scales.shape[-2])
    w = dequantize_grouped(q, scales)
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


# ------------------------------------------------------------ bytes accounting
def node_weight_bytes(node) -> int:
    """HBM bytes the fused kernel streams for one full pass over a quant node
    (each weight/scale block is read exactly once): quantized payload + scales.
    This is the kernel's own block accounting — ``weight_stream_report`` sums
    it into the modeled bytes-per-step figure."""
    q, s = node_qs(node)
    return int(np.prod(q.shape)) * q.dtype.itemsize + \
        int(np.prod(s.shape)) * s.dtype.itemsize


def dense_weight_bytes(shape, dtype) -> int:
    return int(np.prod(shape)) * jnp.dtype(dtype).itemsize


# --------------------------------------------------------------- model entry
def _tp_aligned(node, k: int, n: int, tp: int, parallel: str) -> bool:
    """Can the quant node shard-map cleanly over ``tp`` shards? Column splits
    n; row splits k — which for int4 must also split whole packed groups."""
    q, s = node_qs(node)
    if parallel == "column":
        return n % tp == 0 and s.shape[-1] % tp == 0
    groups = s.shape[-2]
    return k % tp == 0 and groups % tp == 0 and q.shape[-2] % tp == 0


def quant_dense_apply(x, node, bias, dtype, *, parallel: str = "column",
                      site: str = "wq.dense"):
    """Dense ``y = x @ W + b`` where ``W`` is a quant node.

    ``x``: (b, t, k_logical) activations ((m, k) also accepted); ``parallel``:
    "column" (qkv/fc_in — kernel sharded ``P(None, tensor)``) or "row"
    (o_proj/fc_out — kernel sharded ``P(tensor, None)``). Row-parallel with
    an active ``comm_overlap`` config routes through the fused quantized
    ring (``parallel/qring.py``): dequant-GEMM per ring step, intN wire
    payload — retiring the PR-5 "does NOT compose with the comm_overlap
    ring" carve-out. Overlap off (or ring-ineligible shapes) keeps the
    monolithic psum.

    Fused path: TPU backend (or forced), shapes tile, shards divide. Fallback:
    XLA dequant+matmul — GSPMD shards the dequant+matmul and inserts the psum,
    so numerics and sharding stay correct for any shape.
    """
    bits = node_bits(node)
    q, s = node_qs(node)
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None]
    b, t, k = x.shape
    n = q.shape[-1]

    from ...parallel.mesh import AXIS_TENSOR, BATCH_AXES, get_global_mesh
    mesh = get_global_mesh()
    tp = mesh.size(AXIS_TENSOR) if mesh is not None else 1
    groups = s.shape[-2]
    interp = _interpret()
    use_fused = fused_backend_active()
    if use_fused and tp > 1:
        use_fused = _tp_aligned(node, k, n, tp, parallel)
    if use_fused:
        # eligibility is probed on the PER-SHARD shapes the shard_map body
        # will actually run — a shape that tiles globally but not per-shard
        # would otherwise pass here and then fall back to the XLA dequant
        # inside every compiled decode step
        k_loc = k // tp if (tp > 1 and parallel == "row") else k
        n_loc = n // tp if (tp > 1 and parallel == "column") else n
        use_fused = _block_config(
            b * t, k_loc, n_loc, bits, k // groups, interp) is not None

    # row-parallel + active overlap config: the fused quantized ring replaces
    # the monolithic psum. The ring wires fp accumulator CHUNKS (never the
    # packed payload), so its only alignment demands are the fp ring's own
    # (k and groups divide tp; rows pad) — it does not require the Pallas
    # kernel to tile (the ring hoists an XLA dequant once per trace instead).
    use_qring = False
    cfg_ov = None
    if parallel == "row" and tp > 1 and _tp_aligned(node, k, n, tp, "row"):
        from ...parallel.overlap import (_overlap_dense_eligible,
                                         get_overlap_config)
        cfg_ov = get_overlap_config()
        use_qring = _overlap_dense_eligible(mesh, b, t, k, cfg_ov)[0]

    if not use_fused and not use_qring:
        if fused_backend_active():
            # trace-time (once per compile): the audit said quantized, but
            # this site is streaming bf16 — say so instead of silently
            # regressing the hot path
            from ...utils.logging import log_dist
            log_dist(f"weight_quant[{site}]: fused kernel ineligible "
                     f"(m={b * t} k={k} n={n} bits={bits} tp={tp} "
                     f"parallel={parallel}) — XLA dequant fallback on this "
                     "projection", ranks=[0])
        if parallel == "row" and tp > 1:
            # GSPMD inserts the row-parallel allreduce around the fallback
            # matmul too — record it so bytes_on_wire doesn't undercount on
            # exactly the degraded-path topologies worth watching
            from ...utils.comms_logging import record_collective
            record_collective(site + ".monolithic", "all_reduce",
                              b * t * n * jnp.dtype(dtype).itemsize, tp,
                              overlapped=False)
        w = dequantize_node(node).astype(dtype)
        y = x.astype(dtype) @ w
        if squeeze:
            y = y[:, 0]
        return y if bias is None else y + bias.astype(dtype)

    x = x.astype(dtype)
    if mesh is not None:
        batch_axes = tuple(ax for ax in BATCH_AXES if mesh.size(ax) > 1)
        bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) \
            if batch_axes else 1
        if batch_axes and b % bsz:
            batch_axes, bsz = (), 1
    else:
        batch_axes = ()
    # the bare kernel call is only safe when NOTHING is sharded: Pallas is
    # opaque to GSPMD (the reason _sharded_decode wraps the decode-attention
    # kernel), so a dp>1/tp=1 mesh must still go through the shard_map below
    # (tensor axis of size 1 degenerates cleanly) or batch-sharded
    # activations get replicated around the opaque call
    if mesh is None or (tp <= 1 and not batch_axes):
        y = quantized_matmul(x.reshape(b * t, k), q, s, bits=bits,
                             out_dtype=dtype, interpret=interp).reshape(b, t, n)
        if squeeze:
            y = y[:, 0]
        return y if bias is None else y + bias.astype(dtype)

    from ...utils.comms_logging import record_collective
    from ...utils.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P
    bspec = batch_axes or None

    if parallel == "column":
        def body(x_l, q_l, s_l):
            bl, tl, kl = x_l.shape
            return quantized_matmul(
                x_l.reshape(bl * tl, kl), q_l, s_l, bits=bits,
                out_dtype=dtype, interpret=interp).reshape(bl, tl, -1)

        y = shard_map(
            body, mesh=mesh.mesh, axis_names=set(batch_axes) | {AXIS_TENSOR},
            in_specs=(P(bspec, None, None), P(None, AXIS_TENSOR),
                      P(None, AXIS_TENSOR)),
            out_specs=P(bspec, None, AXIS_TENSOR), check_vma=False)(x, q, s)
    elif use_qring:
        # row-parallel + comm_overlap: fused quantized ring (dequant-GEMM per
        # ring step, intN + error-feedback wire payload) + tiled all-gather —
        # the quantized analogue of row_parallel_dense_apply's decomposed
        # allreduce, span-recorded under the same site names
        from ...parallel.qring import quant_row_parallel_apply
        y = quant_row_parallel_apply(
            x, q, s, bits=bits, dtype=dtype, mesh=mesh,
            batch_axes=batch_axes, cfg=cfg_ov, interpret=interp, site=site)
    else:
        # row-parallel: each shard multiplies its k slice of the quantized
        # kernel (fp32 accumulation inside the kernel), then ONE monolithic
        # psum of the serve-dtype partial — same wire dtype, numerics, and
        # bytes accounting as the fp RowParallelDense monolithic path
        if tp > 1:
            record_collective(site + ".monolithic", "all_reduce",
                              b * t * n * jnp.dtype(dtype).itemsize, tp,
                              overlapped=False)

        def body(x_l, q_l, s_l):
            bl, tl, kl = x_l.shape
            part = quantized_matmul(
                x_l.reshape(bl * tl, kl), q_l, s_l, bits=bits,
                out_dtype=dtype, interpret=interp)
            return jax.lax.psum(part, AXIS_TENSOR).reshape(bl, tl, -1)

        y = shard_map(
            body, mesh=mesh.mesh, axis_names=set(batch_axes) | {AXIS_TENSOR},
            in_specs=(P(bspec, None, AXIS_TENSOR), P(AXIS_TENSOR, None),
                      P(AXIS_TENSOR, None)),
            out_specs=P(bspec, None, None), check_vma=False)(x, q, s)
        y = y.astype(dtype)
    if squeeze:
        y = y[:, 0]
    return y if bias is None else y + bias.astype(dtype)
