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

Causality skips work at two granularities. Between grid blocks, by index-map clamping:
kv blocks entirely above the diagonal map to the previous block index, which the pipeline
recognises (no HBM re-copy) while ``pl.when`` skips their compute. Inside a block the
diagonal crosses — at the default 1024/1024 blocks every sequence of up to 1024 tokens is
ONE such block a head, so the grid-level skip never engages there — the kernels walk the
block in strips (``FWD_STRIP``, ``BWD_STRIP``) and form only the sub-tiles on or below the
diagonal: 75 % of the block forward and 62.5 % backward at 1024 tokens, against the 50 % a
perfect skip would reach. Only the sub-tiles ON the diagonal pay iota/compare/select; every
tile and every grid block below it is unmasked arithmetic.

Backward recomputes probabilities blockwise from the saved logsumexp (dq kernel gridded
over q blocks × kv blocks, dk/dv kernel over kv blocks × q blocks, the latter forming its
scores keys-by-queries so no tile is transposed) — no stored attention matrix, matching
the activation-memory profile that makes long sequences feasible.

On CPU (tests) kernels run in interpreter mode automatically.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P
from ...utils.device import pallas_interpret as _interpret
from ...utils.jax_compat import shard_map

NEG_INF = -1e30
# ``checkpoint_name`` tags of the forward rule's two residuals that come out of no
# matmul: the kernel's output (b*h, t, d) and its log-sum-exp (b*h, t), float32
FLASH_OUT_NAME = "flash_out"
FLASH_LSE_NAME = "flash_lse"


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


def _below_diagonal(q_idx, k_idx, bq, bk):
    """Whether every score of grid block (q_idx, k_idx) is visible: its last key
    column is no later than its first q row."""
    return (k_idx + 1) * bk - 1 <= q_idx * bq


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


# Rows (fwd, dq) or key columns (dkv) of the strips a block resident in VMEM is walked in.
# Swept on a v5e over 128/256/512 at (288, 1024, 64) bf16 and at 2k/4k tokens (PERF.md
# section 6, PR 26). A shorter strip leaves out more of a block the diagonal crosses
# (56 / 62.5 / 75 % of it is formed) but its matmuls feed the MXU worse. Backward: 256
# (128 is level, 512 is 15-19 % slower). Forward: 256 and 512 are level at one block a head
# (0.756 / 0.774 ms), and 512 is the only one that beats whole-block updates where rows
# carry their running max across kv blocks (4k tokens: 2.87 ms against 3.16 at 256).
FWD_STRIP = 512
BWD_STRIP = 256


def _rect_strips(whole: int, other: int, sb: int, masked: bool):
    """A block as strips of ``sb`` along one side, each against the whole other
    side: ``(start, [(0, other, masked)])``."""
    for start in range(0, whole, sb):
        yield start, [(0, other, masked)]


def _tri_strips(b: int, sb: int, by_cols: bool):
    """The lower triangle of a ``b x b`` block whose diagonal starts at its origin,
    as ``(start, [(start2, size2, masked), ...])``: strips of ``sb`` q rows, each
    against the unmasked key columns left of its diagonal tile and then that tile —
    or, ``by_cols``, strips of ``sb`` key columns, each against its diagonal tile
    and then the unmasked q rows below. Only the diagonal tiles need the mask; the
    tiles above them are never formed."""
    for s0 in range(0, b, sb):
        diag = (s0, sb, True)
        if by_cols:
            yield s0, [diag] + ([(s0 + sb, b - s0 - sb, False)] if s0 + sb < b else [])
        else:
            yield s0, ([(0, s0, False)] if s0 else []) + [diag]


def _strip_size(whole: int, strip: int) -> int:
    return strip if whole % strip == 0 else whole


def causal_work_share(t: int, block_q: int = 1024, block_k: int = 1024,
                      causal: bool = True, backward: bool = False) -> float:
    """Share of the ``t x t`` score square the forward (or each backward) kernel
    forms: 1.0 = all of it; the causal triangle itself tends to 0.5. Counted from
    the same strips the kernels walk, so it cannot drift from them."""
    if not causal:
        return 1.0
    bq, bk = _block_sizes(t, block_q, block_k)
    sb = _strip_size(bq, BWD_STRIP if backward else FWD_STRIP)
    crossed_block = sum(sb * nc for _, parts in _tri_strips(bq, sb, False)
                        for _, nc, _ in parts) if bq == bk else bq * bk
    formed = 0
    for j in range(t // bq):
        for kb in range(_causal_k_hi(j, bq, bk) + 1):
            formed += bq * bk if _below_diagonal(j, kb, bq, bk) else crossed_block
    return formed / float(t * t)


def _dot(a, b, contract):
    """Input-dtype matmul, f32 accumulation (bf16 inputs hit the MXU's native
    rate — an f32 upcast would halve matmul throughput)."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _scores(rows, cols, scale, slope, off, masked, key_axis, mask_block=1):
    """Scaled scores ``rows @ cols^T`` of one tile; keys run along ``key_axis`` of
    the result and ``off`` = (first q row) - (first key column) in sequence
    positions. The alibi term ``slope * (key - query)`` rides every tile; iota,
    compare and select are spent only where ``masked`` says the diagonal crosses
    the tile. ``mask_block`` > 1 is the block-causal mask: a key is seen when its
    block of that many positions is not later than the query's, so a query sees
    up to the end of its own block; tiles start on block boundaries (every tile
    size is a multiple of the block), so the query's place in its block is its
    row's in the tile."""
    s = _dot(rows, cols, (1, 1)) * scale
    if slope is None and not masked:
        return s
    key = jax.lax.broadcasted_iota(jnp.int32, s.shape, key_axis)
    query = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - key_axis)
    dist = key - query - off
    if slope is not None:
        # 0 on the diagonal, negative below (alibi distance penalty)
        s = s + slope * dist.astype(jnp.float32)
    if masked and mask_block > 1:
        reach = mask_block - 1 - jax.lax.rem(query, mask_block)
        s = jnp.where(dist <= reach, s, NEG_INF)
    elif masked:
        s = jnp.where(dist <= 0, s, NEG_INF)
    return s


def _walk(causal, q_idx, k_idx, nq, bq, bk, by_cols, sb, strip, commit):
    """Run grid block (q_idx, k_idx) in strips of ``sb`` q rows, or of key columns
    where ``by_cols``: ``strip(start, size, parts, base_off)`` gives one strip's
    contribution as a tuple of arrays with ``size`` leading rows, and
    ``commit(start, size, *arrays)`` takes it. A block below the diagonal (or any
    block of a call that is not causal) is walked whole and unmasked; of a block
    the diagonal crosses only the lower triangle is, and only its diagonal tiles
    are masked; a block above it is skipped. The strips share no state and every
    strip is formed before the first is committed, so none waits on a buffer
    another wrote and the compiler overlaps one strip's matmuls with another's
    softmax. ``base_off`` = (block's first q row) - (block's first key column):
    0, and static, in a crossed block of equal sides."""
    whole, other = (bk, bq) if by_cols else (bq, bk)
    sb = _strip_size(whole, sb)
    base_off = q_idx * bq - k_idx * bk

    def run(strips, off):
        outs = [(start, strip(start, sb, parts, off)) for start, parts in strips]
        for start, out in outs:
            commit(start, sb, *out)

    if not causal:
        run(_rect_strips(whole, other, sb, False), base_off)
        return
    below = _below_diagonal(q_idx, k_idx, bq, bk)
    crossed = jnp.logical_and(jnp.logical_not(below),
                              k_idx <= _causal_k_hi(q_idx, bq, bk))
    # at one block a head no block lies below the diagonal: carry no code for one
    if bk - 1 <= (nq - 1) * bq:
        pl.when(below)(lambda: run(_rect_strips(whole, other, sb, False), base_off))

    @pl.when(crossed)
    def _crossed():
        if bq == bk:
            run(_tri_strips(bq, sb, by_cols), 0)
        else:
            # the diagonal enters at an offset only the grid step knows: mask it all
            run(_rect_strips(whole, other, sb, True), base_off)


# ----------------------------------------------------------------------- forward kernel
def _fwd_kernel(*refs, scale, causal, use_alibi, nq, nk, bq, bk, mask_block=1):
    q_ref, k_ref, v_ref = refs[:3]
    slopes_ref = refs[3] if use_alibi else None
    o_ref, lse_ref, *scratch = refs[4 if use_alibi else 3:]
    j = pl.program_id(1)
    kb = pl.program_id(2)
    if nk > 1:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(kb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def strip(r0, nr, parts, base_off):
        """Online-softmax step of q rows [r0, r0+nr) over the key columns in
        ``parts``: (new running max, row sum and unnormalised output of these
        columns against it), the statistics as (nr, 1) columns — one max for the
        strip, not one per tile."""
        slope = slopes_ref[0, 0, 0] if use_alibi else None
        q = q_ref[0, r0:r0 + nr, :]
        ss = [_scores(q, k_ref[0, c0:c0 + nc, :], scale, slope,
                      base_off + r0 - c0, masked, 1, mask_block)
              for c0, nc, masked in parts]
        # one kv block holds every key of its rows: no running max to start from
        m = None if nk == 1 else m_scr[r0:r0 + nr, :]
        for s in ss:
            s_max = s.max(axis=-1, keepdims=True)
            m = s_max if m is None else jnp.maximum(m, s_max)
        l = acc = None
        for s, (c0, nc, _) in zip(ss, parts):
            v = v_ref[0, c0:c0 + nc, :]
            p = jnp.exp(s - m)
            pv = _dot(p.astype(v.dtype), v, (1, 0))
            p_sum = p.sum(axis=-1, keepdims=True)
            l = p_sum if l is None else l + p_sum
            acc = pv if acc is None else acc + pv
        return m, l, acc

    def write(r0, nr, m, l, acc):
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, r0:r0 + nr, :] = (acc / l_safe).astype(o_ref.dtype)
        # lse stored (bh, nq, 8, bq), rows along lanes: TPU block tiling needs the
        # last two dims (sublane, lane) aligned; the 8 duplicate sublanes keep the
        # layout legal
        lse = (m + jnp.log(l_safe))[:, 0]
        lse_ref[0, 0, :, r0:r0 + nr] = jnp.broadcast_to(lse[None, :], (8, nr))

    if nk == 1:
        _walk(causal, j, kb, nq, bq, bk, False, FWD_STRIP, strip, write)
        return

    def carry(r0, nr, m, l, acc):
        """Rescale the rows' running sums to their new max and add the strip's."""
        rows = slice(r0, r0 + nr)
        alpha = jnp.exp(m_scr[rows, :] - m)
        acc_scr[rows, :] = alpha * acc_scr[rows, :] + acc
        l_scr[rows, :] = alpha * l_scr[rows, :] + l
        m_scr[rows, :] = m

    _walk(causal, j, kb, nq, bq, bk, False, FWD_STRIP, strip, carry)

    @pl.when(kb == nk - 1)
    def _finalize():
        write(0, bq, m_scr[...], l_scr[...], acc_scr[...])


def _flash_fwd(q3, k3, v3, slopes3, scale, causal, block_q, block_k, mask_block=1):
    """q3/k3/v3: (bh, t, d); slopes3: per-(b·h) alibi slopes broadcast to
    (bh, 8, 128) for lane alignment, or None. Returns (o3, lse (bh, t))."""
    bh, t, d = q3.shape
    bq, bk = _block_sizes(t, block_q, block_k)
    nq, nk = t // bq, t // bk
    grid = (bh, nq, nk)
    use_alibi = slopes3 is not None

    k_index = _k_index_map(causal, bq, bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               use_alibi=use_alibi, nq=nq, nk=nk, bq=bq, bk=bk,
                               mask_block=mask_block)
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
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((bq, 1), jnp.float32),         # m
            pltpu.VMEM((bq, 1), jnp.float32),         # l
            pltpu.VMEM((bq, d), jnp.float32),         # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="flash_fwd",
        interpret=_interpret(),
    )(*args)
    return o3, lse[:, :, 0, :].reshape(bh, t)


# ---------------------------------------------------------------------- backward kernels
def _summed(out_refs, scratch, scales, step, n_steps, walk):
    """Run ``walk(commit)`` and sum the f32 contributions it commits over the
    ``n_steps`` grid steps of the innermost axis; write them, scaled, on the last.
    With one step there is nothing to sum: each strip's contribution is its rows
    of the result."""
    def store(r0, nr, *xs):
        for ref, x, c in zip(out_refs, xs, scales):
            ref[0, r0:r0 + nr, :] = (x if c == 1.0 else x * c).astype(ref.dtype)

    if n_steps == 1:
        walk(store)
        return

    @pl.when(step == 0)
    def _init():
        for scr in scratch:
            scr[...] = jnp.zeros_like(scr)

    def add(r0, nr, *xs):
        for scr, x in zip(scratch, xs):
            scr[r0:r0 + nr, :] += x

    walk(add)

    @pl.when(step == n_steps - 1)
    def _finalize():
        store(0, out_refs[0].shape[1], *(scr[...] for scr in scratch))


def _bwd_dq_kernel(*refs, scale, causal, use_alibi, nq, nk, bq, bk, mask_block=1):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    slopes_ref = refs[6] if use_alibi else None
    dq_ref, *scratch = refs[7 if use_alibi else 6:]
    j = pl.program_id(1)
    kb = pl.program_id(2)

    def strip(r0, nr, parts, base_off):
        # the recomputed s is bit-identical to the s the forward derived lse from:
        # same operands, same matmul policy
        slope = slopes_ref[0, 0, 0] if use_alibi else None
        q = q_ref[0, r0:r0 + nr, :]
        do = do_ref[0, r0:r0 + nr, :]
        lse = lse_ref[0, 0, 0, r0:r0 + nr][:, None]
        delta = delta_ref[0, 0, 0, r0:r0 + nr][:, None]
        dq = None
        for c0, nc, masked in parts:
            k = k_ref[0, c0:c0 + nc, :]
            v = v_ref[0, c0:c0 + nc, :]
            s = _scores(q, k, scale, slope, base_off + r0 - c0, masked, 1,
                        mask_block)
            p = jnp.exp(s - lse)                               # true probs
            dp = _dot(do, v, (1, 1))
            # ds without its factor ``scale``: applied to the (bq, d) result
            ds = (p * (dp - delta)).astype(k.dtype)
            part = _dot(ds, k, (1, 0))
            dq = part if dq is None else dq + part
        return (dq,)

    _summed((dq_ref,), scratch, (scale,), kb, nk, lambda commit: _walk(
        causal, j, kb, nq, bq, bk, False, BWD_STRIP, strip, commit))


def _bwd_dkv_kernel(*refs, scale, causal, use_alibi, nq, bq, bk, mask_block=1):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    slopes_ref = refs[6] if use_alibi else None
    dk_ref, dv_ref, *scratch = refs[7 if use_alibi else 6:]
    kb = pl.program_id(1)
    qb = pl.program_id(2)

    def strip(c0, nc, parts, base_off):
        """dk, dv of key columns [c0, c0+nc) from the q rows in ``parts``. Scores are
        formed keys-by-queries, so lse and delta (rows along lanes) broadcast as they
        are stored and every matmul takes its operands as they lie — no transpose of
        a (keys x queries) tile."""
        slope = slopes_ref[0, 0, 0] if use_alibi else None
        k = k_ref[0, c0:c0 + nc, :]
        v = v_ref[0, c0:c0 + nc, :]
        dk = dv = None
        for r0, nr, masked in parts:
            q = q_ref[0, r0:r0 + nr, :]
            do = do_ref[0, r0:r0 + nr, :]
            lse = lse_ref[0, 0, 0:1, r0:r0 + nr]               # (1, nr)
            delta = delta_ref[0, 0, 0:1, r0:r0 + nr]
            st = _scores(k, q, scale, slope, base_off + r0 - c0, masked, 0,
                         mask_block)
            pt = jnp.exp(st - lse)                             # (nc, nr)
            dpt = _dot(v, do, (1, 1))
            dst = (pt * (dpt - delta)).astype(q.dtype)         # see _bwd_dq_kernel
            dv_part = _dot(pt.astype(do.dtype), do, (1, 0))
            dk_part = _dot(dst, q, (1, 0))
            dv = dv_part if dv is None else dv + dv_part
            dk = dk_part if dk is None else dk + dk_part
        return dk, dv

    _summed((dk_ref, dv_ref), scratch, (scale, 1.0), qb, nq, lambda commit: _walk(
        causal, qb, kb, nq, bq, bk, True, BWD_STRIP, strip, commit))


def _flash_bwd(q3, k3, v3, o3, lse, do3, slopes3, scale, causal, block_q, block_k,
               mask_block=1):
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
                          use_alibi=use_alibi, nq=nq, nk=nk, bq=bq, bk=bk,
                          mask_block=mask_block),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        scratch_shapes=[] if nk == 1 else [pltpu.VMEM((bq, d), jnp.float32)],
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
                          use_alibi=use_alibi, nq=nq, bq=bq, bk=bk,
                          mask_block=mask_block),
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
        scratch_shapes=[] if nq == 1 else [pltpu.VMEM((bk, d), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(*dkv_args)
    return dq, dk, dv


# --------------------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(q3, k3, v3, slopes3, scale, causal, use_alibi, block_q, block_k,
                mask_block=1):
    o3, _ = _flash_fwd(q3, k3, v3, slopes3 if use_alibi else None, scale, causal,
                       block_q, block_k, mask_block)
    return o3


def _flash_core_fwd(q3, k3, v3, slopes3, scale, causal, use_alibi, block_q, block_k,
                    mask_block=1):
    o3, lse = _flash_fwd(q3, k3, v3, slopes3 if use_alibi else None, scale, causal,
                         block_q, block_k, mask_block)
    # the two residuals no matmul gives back: a remat policy that names them
    # (models/gpt2.py, "dots") keeps them and the backward runs no second forward
    o3 = checkpoint_name(o3, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return o3, (q3, k3, v3, o3, lse, slopes3)


def _flash_core_bwd(scale, causal, use_alibi, block_q, block_k, mask_block, res, do3):
    q3, k3, v3, o3, lse, slopes3 = res
    dq, dk, dv = _flash_bwd(q3, k3, v3, o3, lse, do3,
                            slopes3 if use_alibi else None, scale, causal,
                            block_q, block_k, mask_block)
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
                          block_q: int = 1024, block_k: int = 1024,
                          mask_block: int = 1):
    """Per-shard kernel invocation with NO mesh dispatch — for callers already inside a
    ``shard_map`` manual region (e.g. the TP pipeline stage_fn), where the public
    :func:`flash_attention`'s own shard_map wrapper would illegally nest."""
    lb, lt, lh, ld = q4.shape
    if mask_block > 1 and (not causal or lt % mask_block
                           or min(_block_sizes(lt, block_q, block_k)) % mask_block):
        raise ValueError(
            f"mask_block={mask_block} is the block-causal mask: it needs causal=True "
            f"and a sequence ({lt}) and tiles that whole blocks divide")
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(ld))
    use_alibi = alibi_slopes is not None
    slopes3 = (_slopes3(alibi_slopes, lb, lh) if use_alibi
               else jnp.asarray(_DUMMY_SLOPES))

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(lb * lh, lt, ld)

    o3 = _flash_core(to3(q4), to3(k4), to3(v4), slopes3, scale, causal, use_alibi,
                     block_q, block_k, mask_block)
    return o3.reshape(lb, lh, lt, ld).transpose(0, 2, 1, 3)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, mask: Optional[jnp.ndarray] = None,
                    softmax_scale: Optional[float] = None,
                    dropout_rate: float = 0.0, dropout_rng=None,
                    alibi_slopes: Optional[jnp.ndarray] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    mask_block: int = 1) -> jnp.ndarray:
    """Drop-in replacement for ``xla_attention``: q/k/v ``(b, t, h, d)`` → ``(b, t, h, d)``.

    ``alibi_slopes`` (h,) adds the per-head alibi distance bias ``slope*(col-row)``
    inside the kernel (BLOOM; reference fuses the same bias into its attn_softmax
    kernel, ``softmax_kernels.cu``) — no (h, t, s) bias tensor is ever materialised.

    Falls back to the XLA path for features the kernel does not cover (arbitrary masks,
    attention dropout, cross-attention with different kv length). ``mask_block`` > 1
    (with ``causal``) is the block-causal mask of generation by diffusion over blocks:
    key ``j`` is seen by query ``i`` iff ``j // mask_block <= i // mask_block``; only the
    tiles on the diagonal change, so what causality skips stays skipped. There is no
    sequence-length guard: K/V blocks stream through the grid pipeline, so VMEM use is
    O(block) regardless of t.
    """
    from ..transformer.attention import xla_attention
    if mask is not None or dropout_rate > 0.0 or q.shape[1] != k.shape[1]:
        if alibi_slopes is not None or mask_block > 1:
            raise NotImplementedError(
                "mask_block is kernel-only" if mask_block > 1 else
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
                                     block_q=block_q, block_k=block_k,
                                     mask_block=mask_block)

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
