"""Flash attention — Pallas TPU kernel, forward + backward.

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu`` ``attn_softmax``/``softmax_backward`` + the strided
batch gemms in ``csrc/transformer/ds_transformer_cuda.cpp``): one kernel computes the whole
attention block with online softmax, never materialising the (t × t) score matrix in HBM —
the memory behaviour the reference approximates with kernel fusion, taken to its fixed point.

Algorithm: flash attention v2 tiling with the K/V loop folded into the GRID's innermost
dimension: the Pallas TPU pipeline then streams K/V blocks HBM→VMEM with automatic
double-buffering (copy of block ``k+1`` overlaps compute on block ``k``), and the online
softmax carry (m, l, acc) lives in VMEM scratch across grid steps. VMEM holds only
one q block + two k/v blocks + carry — independent of sequence length, so there is NO
sequence-length guard: 128k tokens stream exactly like 1k.

Causality skips work at BLOCK granularity by index-map clamping: kv blocks entirely above
the diagonal map to the previous block index, which the pipeline recognises (no HBM
re-copy) while ``pl.when`` skips their compute — ~2× effective speedup for causal without
a second grid.

Backward recomputes probabilities blockwise from the saved logsumexp (dq kernel gridded
over q blocks × kv blocks, dk/dv kernel over kv blocks × q blocks) — no stored attention
matrix, matching the activation-memory profile that makes long sequences feasible.

On CPU (tests) kernels run in interpreter mode automatically.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P
from ...utils.device import pallas_interpret as _interpret
from ...utils.jax_compat import shard_map

NEG_INF = -1e30


def _block_sizes(t: int, block_q: int, block_k: int):
    bq = min(block_q, t)
    bk = min(block_k, t)
    while t % bq:
        bq //= 2
    while t % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def _causal_k_hi(q_idx, bq, bk):
    """Last kv-block index (inclusive) intersecting the causal triangle of q block."""
    return ((q_idx + 1) * bq - 1) // bk


def _causal_q_lo(k_idx, bq, bk):
    """First q-block index intersecting the causal triangle of kv block."""
    return (k_idx * bk) // bq


def _k_index_map(causal, bq, bk):
    """kv-block index map: under causality, blocks above the diagonal clamp to the
    last needed block — same index as the previous grid step, so the pipeline skips
    the copy while ``pl.when`` skips the compute. Shared by fwd and bwd-dq so the
    two cannot drift."""
    def k_index(i, j, kb):
        if causal:
            return (i, jnp.minimum(kb, _causal_k_hi(j, bq, bk)), 0)
        return (i, kb, 0)
    return k_index


def _q_index_map(causal, bq, bk, extra_dims=0):
    """q/lse-block index map for the dkv kernel: q blocks strictly above the causal
    diagonal clamp forward to the first contributing block (no copy, no compute)."""
    tail = (0,) * (1 + extra_dims)

    def q_index(i, kb, qb):
        if causal:
            return (i, jnp.maximum(qb, _causal_q_lo(kb, bq, bk))) + tail
        return (i, qb) + tail
    return q_index


# ----------------------------------------------------------------------- forward kernel
def _fwd_kernel(*refs, scale, causal, use_alibi, nk, bq, bk, t_valid):
    if use_alibi:
        q_ref, k_ref, v_ref, slopes_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        slopes_ref = None
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    active = kb * bk < t_valid
    if causal:
        active = jnp.logical_and(active, kb <= _causal_k_hi(j, bq, bk))

    @pl.when(active)
    def _compute():
        # matmuls take the INPUT dtype (bf16 inputs hit the MXU's native rate —
        # an f32 upcast here would halve matmul throughput) and accumulate f32
        q = q_ref[0]                                           # (bq, d)
        k_blk = k_ref[0]                                       # (bk, d)
        v_blk = v_ref[0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if use_alibi:
            # per-head additive bias slope*(col-row) — 0 on the diagonal, negative
            # below (alibi distance penalty; masked positions are overwritten next)
            s = s + slopes_ref[0, 0, 0] * (cols - rows).astype(jnp.float32)
        mask = cols < t_valid
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[0]                                      # (8, bq) broadcast rows
        m_row = m_prev[0]                                      # (bq,)
        m_new = jnp.maximum(m_row, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_row - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_scr[0][0] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[None, :, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]
        m_scr[...] = jnp.broadcast_to(m_new[None, None, :], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[None, None, :], l_scr.shape)

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_scr[0][0]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[0] / l_safe[:, None]).astype(o_ref.dtype)
        # lse stored (bh, nq, 8, bq): TPU block tiling needs the last two dims
        # (sublane, lane) aligned; the 8 duplicate sublanes keep the layout legal
        lse = (m_scr[0][0] + jnp.log(l_safe)).astype(jnp.float32)
        lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def _flash_fwd(q3, k3, v3, slopes3, scale, causal, block_q, block_k, t_valid):
    """q3/k3/v3: (bh, t, d) padded to block multiples; slopes3: per-(b·h) alibi
    slopes broadcast to (bh, 8, 128) for lane alignment, or None.
    Returns (o3, lse (bh, t))."""
    bh, t, d = q3.shape
    bq, bk = _block_sizes(t, block_q, block_k)
    nq, nk = t // bq, t // bk
    grid = (bh, nq, nk)
    use_alibi = slopes3 is not None

    k_index = _k_index_map(causal, bq, bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               use_alibi=use_alibi, nk=nk, bq=bq, bk=bk,
                               t_valid=t_valid)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
        pl.BlockSpec((1, bk, d), k_index),
        pl.BlockSpec((1, bk, d), k_index),
    ]
    args = [q3, k3, v3]
    if use_alibi:
        in_specs.append(pl.BlockSpec((1, 8, 128), lambda i, j, kb: (i, 0, 0)))
        args.append(slopes3)
    o3, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, 8, bq), lambda i, j, kb: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, nq, 8, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 8, bq), jnp.float32),      # m (rows dup'd over sublanes)
            pltpu.VMEM((1, 8, bq), jnp.float32),      # l
            pltpu.VMEM((1, bq, d), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="flash_fwd",
        interpret=_interpret(),
    )(*args)
    return o3, lse[:, :, 0, :].reshape(bh, t)


# ---------------------------------------------------------------------- backward kernels
def _bwd_dq_kernel(*refs, scale, causal, use_alibi, nk, bq, bk, t_valid):
    if use_alibi:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        slopes_ref = None
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    active = kb * bk < t_valid
    if causal:
        active = jnp.logical_and(active, kb <= _causal_k_hi(j, bq, bk))

    @pl.when(active)
    def _compute():
        # input-dtype matmuls, f32 accumulation (same policy as the forward —
        # bf16 inputs keep the MXU at its native rate AND make the recomputed s
        # bit-identical to the s the forward derived lse from)
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if use_alibi:
            s = s + slopes_ref[0, 0, 0] * (cols - rows).astype(jnp.float32)
        mask = cols < t_valid
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                      # true probs
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(k_blk.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[0].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, use_alibi, nq, bq, bk, t_valid):
    if use_alibi:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        slopes_ref = None
    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    active = kb * bk < t_valid
    if causal:
        active = jnp.logical_and(active, qb >= _causal_q_lo(kb, bq, bk))

    @pl.when(active)
    def _compute():
        # input-dtype matmuls, f32 accumulation (see _bwd_dq_kernel)
        k_blk = k_ref[0]                          # (bk, d)
        v_blk = v_ref[0]
        q_blk = q_ref[0]                          # (bq, d)
        do_blk = do_ref[0]
        lse_blk = lse_ref[0, 0, 0]                # (bq,)
        delta_blk = delta_ref[0, 0, 0]
        s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if use_alibi:
            s = s + slopes_ref[0, 0, 0] * (cols - rows).astype(jnp.float32)
        mask = cols < t_valid
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_blk[:, None])
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]
        dp = jax.lax.dot_general(do_blk, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_blk[:, None]) * scale).astype(q_blk.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[0].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[0].astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, o3, lse, do3, slopes3, scale, causal, block_q, block_k,
               t_valid):
    bh, t, d = q3.shape
    bq, bk = _block_sizes(t, block_q, block_k)
    nq, nk = t // bq, t // bk
    use_alibi = slopes3 is not None
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)  # (bh, t)
    lse_b = jnp.broadcast_to(lse.reshape(bh, nq, 1, bq), (bh, nq, 8, bq))
    delta_b = jnp.broadcast_to(delta.reshape(bh, nq, 1, bq), (bh, nq, 8, bq))

    k_index = _k_index_map(causal, bq, bk)
    dq_in_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
        pl.BlockSpec((1, bk, d), k_index),
        pl.BlockSpec((1, bk, d), k_index),
        pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
        pl.BlockSpec((1, 1, 8, bq), lambda i, j, kb: (i, j, 0, 0)),
        pl.BlockSpec((1, 1, 8, bq), lambda i, j, kb: (i, j, 0, 0)),
    ]
    dq_args = [q3, k3, v3, do3, lse_b, delta_b]
    if use_alibi:
        dq_in_specs.append(pl.BlockSpec((1, 8, 128), lambda i, j, kb: (i, 0, 0)))
        dq_args.append(slopes3)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          use_alibi=use_alibi, nk=nk, bq=bq, bk=bk, t_valid=t_valid),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((1, bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(*dq_args)

    q_index = _q_index_map(causal, bq, bk)
    lse_index = _q_index_map(causal, bq, bk, extra_dims=1)
    dkv_in_specs = [
        pl.BlockSpec((1, bq, d), q_index),
        pl.BlockSpec((1, bk, d), lambda i, kb, qb: (i, kb, 0)),
        pl.BlockSpec((1, bk, d), lambda i, kb, qb: (i, kb, 0)),
        pl.BlockSpec((1, bq, d), q_index),
        pl.BlockSpec((1, 1, 8, bq), lse_index),
        pl.BlockSpec((1, 1, 8, bq), lse_index),
    ]
    dkv_args = [q3, k3, v3, do3, lse_b, delta_b]
    if use_alibi:
        dkv_in_specs.append(pl.BlockSpec((1, 8, 128), lambda i, kb, qb: (i, 0, 0)))
        dkv_args.append(slopes3)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          use_alibi=use_alibi, nq=nq, bq=bq, bk=bk, t_valid=t_valid),
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, kb, qb: (i, kb, 0)),
            pl.BlockSpec((1, bk, d), lambda i, kb, qb: (i, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v3.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bk, d), jnp.float32),
                        pltpu.VMEM((1, bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(*dkv_args)
    return dq, dk, dv


# --------------------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q3, k3, v3, slopes3, scale, causal, use_alibi, block_q, block_k):
    t_valid = q3.shape[1]
    o3, _ = _flash_fwd(q3, k3, v3, slopes3 if use_alibi else None, scale, causal,
                       block_q, block_k, t_valid)
    return o3


def _flash_core_fwd(q3, k3, v3, slopes3, scale, causal, use_alibi, block_q, block_k):
    t_valid = q3.shape[1]
    o3, lse = _flash_fwd(q3, k3, v3, slopes3 if use_alibi else None, scale, causal,
                         block_q, block_k, t_valid)
    return o3, (q3, k3, v3, o3, lse, slopes3)


def _flash_core_bwd(scale, causal, use_alibi, block_q, block_k, res, do3):
    q3, k3, v3, o3, lse, slopes3 = res
    t_valid = q3.shape[1]
    dq, dk, dv = _flash_bwd(q3, k3, v3, o3, lse, do3,
                            slopes3 if use_alibi else None, scale, causal,
                            block_q, block_k, t_valid)
    # alibi slopes are a fixed schedule, not trained — zero cotangent
    return dq, dk, dv, jnp.zeros_like(slopes3)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)

_DUMMY_SLOPES = np.zeros((1, 8, 128), np.float32)


def _slopes3(alibi_slopes, b, h):
    """(h,) per-head slopes → (b*h, 8, 128) f32 (value duplicated for TPU lane
    alignment; the kernel reads element [0, 0, 0] of each head's block)."""
    s = jnp.tile(jnp.asarray(alibi_slopes, jnp.float32), b)       # bh = bi*h + hi
    return jnp.broadcast_to(s[:, None, None], (b * h, 8, 128))


def flash_attention_local(q4, k4, v4, causal: bool = True,
                          softmax_scale: Optional[float] = None,
                          alibi_slopes: Optional[jnp.ndarray] = None,
                          block_q: int = 1024, block_k: int = 1024):
    """Per-shard kernel invocation with NO mesh dispatch — for callers already inside a
    ``shard_map`` manual region (e.g. the TP pipeline stage_fn), where the public
    :func:`flash_attention`'s own shard_map wrapper would illegally nest."""
    lb, lt, lh, ld = q4.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(ld))
    use_alibi = alibi_slopes is not None
    slopes3 = (_slopes3(alibi_slopes, lb, lh) if use_alibi
               else jnp.asarray(_DUMMY_SLOPES))

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(lb * lh, lt, ld)

    o3 = _flash_core(to3(q4), to3(k4), to3(v4), slopes3, scale, causal, use_alibi,
                     block_q, block_k)
    return o3.reshape(lb, lh, lt, ld).transpose(0, 2, 1, 3)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, mask: Optional[jnp.ndarray] = None,
                    softmax_scale: Optional[float] = None,
                    dropout_rate: float = 0.0, dropout_rng=None,
                    alibi_slopes: Optional[jnp.ndarray] = None,
                    block_q: int = 1024, block_k: int = 1024) -> jnp.ndarray:
    """Drop-in replacement for ``xla_attention``: q/k/v ``(b, t, h, d)`` → ``(b, t, h, d)``.

    ``alibi_slopes`` (h,) adds the per-head alibi distance bias ``slope*(col-row)``
    inside the kernel (BLOOM; reference fuses the same bias into its attn_softmax
    kernel, ``softmax_kernels.cu``) — no (h, t, s) bias tensor is ever materialised.

    Falls back to the XLA path for features the kernel does not cover (arbitrary masks,
    attention dropout, cross-attention with different kv length). There is no
    sequence-length guard: K/V blocks stream through the grid pipeline, so VMEM use is
    O(block) regardless of t.
    """
    from ..transformer.attention import xla_attention
    if mask is not None or dropout_rate > 0.0 or q.shape[1] != k.shape[1]:
        if alibi_slopes is not None:
            raise NotImplementedError(
                "alibi_slopes is kernel-only: combine it with mask/dropout/"
                "cross-attention via the model-level XLA bias path instead")
        return xla_attention(q, k, v, causal=causal, mask=mask,
                             softmax_scale=softmax_scale,
                             dropout_rate=dropout_rate, dropout_rng=dropout_rng)
    b, t, h, d = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(d))
    use_alibi = alibi_slopes is not None

    def local(q4, k4, v4, slopes=None):
        return flash_attention_local(q4, k4, v4, causal=causal, softmax_scale=scale,
                                     alibi_slopes=slopes,
                                     block_q=block_q, block_k=block_k)

    # A pallas_call is opaque to the SPMD partitioner: under a sharded mesh it would force a
    # full rematerialisation. Run the kernel per-shard with shard_map over the batch (and TP
    # head) axes instead — sequence stays unsharded here (ring_attention owns the seq axis).
    from ...parallel.mesh import BATCH_AXES, AXIS_TENSOR, get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None:
        batch_axes = tuple(ax for ax in BATCH_AXES if mesh.size(ax) > 1)
        bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) if batch_axes else 1
        tp = mesh.size(AXIS_TENSOR)
        use_tp = tp > 1 and h % tp == 0
        manual = set(batch_axes) | ({AXIS_TENSOR} if use_tp else set())
        if manual and b % max(bsz, 1) == 0:
            spec = P(batch_axes or None, None, AXIS_TENSOR if use_tp else None, None)
            if use_alibi:
                # slopes shard over the head (TP) axis: each shard sees its heads'
                sspec = P(AXIS_TENSOR if use_tp else None)
                mapped = shard_map(
                    lambda q4, k4, v4, s: local(q4, k4, v4, s),
                    mesh=mesh.mesh, axis_names=manual,
                    in_specs=(spec,) * 3 + (sspec,), out_specs=spec,
                    check_vma=False)
                return mapped(q, k, v, jnp.asarray(alibi_slopes, jnp.float32))
            mapped = shard_map(local, mesh=mesh.mesh, axis_names=manual,
                                   in_specs=(spec,) * 3, out_specs=spec,
                                   check_vma=False)
            return mapped(q, k, v)
    return local(q, k, v, jnp.asarray(alibi_slopes, jnp.float32) if use_alibi
                 else None)
