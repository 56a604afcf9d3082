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
the activation-memory profile that makes long sequences feasible. ``delta``, the rows'
sums of ``do * o`` a head, is made in no XLA op: the dq kernel reads ``o`` beside ``do``
under q's blocks, forms the float32 product and sum strip by strip, uses the column and
writes it out as its second result; the dk/dv kernel, called after it, reads that array.

Operand layout. A ``(b, t, h, d)`` array is, bit for bit, ``(b, t, h*d)``: the kernels take
q, k, v (and write o, dq, dk, dv) in that flat layout, where the projection before them wrote
it and the one after them reads it, and pick a head by a block index on the LAST axis
(grid ``(b, lane groups, q blocks, kv blocks)``), not by a transpose before the call.
Which head shapes take which branch (:func:`heads_a_block`):

- ``d % 128 == 0`` (BLOOM, the hybrid, SDAR): a lane group is one head, the body as below;
- ``d < 128``, ``128 % d == 0`` and ``h % (128 // d) == 0`` (GPT-2: d 64, 12 heads): a lane
  group is 128 lanes = ``128 // d`` heads. The body takes them in turn: the operand of a
  contraction over lanes is masked to the head's ``d`` lanes (zeros add nothing; the MXU
  passes are those of the half-filled contraction), and of a product whose RESULT has the
  128 lanes the head's are kept. No tile is sliced inside a lane tile, none is transposed;
- any other head size, or a head count the lane tile does not divide: the transposing
  ``(b*h, t, d)`` call, which is the same specs with ``b -> b*h`` and one lane group.

:func:`flash_attention_qkv` reads a fused projection ``(b, t, 3*h*d)`` = q | k | v as ONE
operand: the same index maps with a lane offset (k at group ``G + g``, v at ``2G + g``), so
no split copies the three out. ``lse`` (the forward's result, spread over 8 sublanes by the
one XLA op in front of the backward kernels) and ``delta`` (the dq kernel's, written that
way) are ``(rows, heads, q blocks, 8, bq)``: rows along lanes, a row set a head.

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
# ``checkpoint_name`` tags of the forward rule's residuals that come out of no matmul:
# the kernel's output, in the layout of its operands ((b, t, h*d) flat, (b*h, t, d)
# else), its log-sum-exp (rows, heads in the lanes, t), float32, and the fused
# projection (b, t, 3*h*d) it read, bias and all (a policy that kept the projection's
# matmul output instead would add the bias again in every layer's backward)
FLASH_OUT_NAME = "flash_out"
FLASH_LSE_NAME = "flash_lse"
FLASH_QKV_NAME = "flash_qkv"


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


def _window_k_lo(q_idx, bq, bk, window):
    """First kv-block index a q block's band reaches: query ``i`` sees the
    ``window`` keys ``(i - window, i]``, so the block's first row sees none
    before ``q_idx * bq - (window - 1)``."""
    return jnp.maximum(q_idx * bq - (window - 1), 0) // bk


def _inside_band(q_idx, k_idx, bq, bk, window):
    """Whether every score of grid block (q_idx, k_idx) lies inside the band:
    below the diagonal, and its first key no earlier than what the block's
    LAST q row still sees."""
    return jnp.logical_and(_below_diagonal(q_idx, k_idx, bq, bk),
                           k_idx * bk >= (q_idx + 1) * bq - window)


def heads_a_block(h: int, d: int) -> int:
    """Heads one kernel block holds where ``h`` heads of ``d`` lie flat in the lanes
    ``(b, t, h*d)``: 1 where a head is whole lane tiles, ``128 // d`` where heads fill
    one tile, 0 where lane blocks cannot address a head (the ``(b*h, t, d)`` call)."""
    if d % 128 == 0:
        return 1
    if 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d
    return 0


def _lane_groups(lanes: int, d: int):
    """(block width, lane groups) of an operand whose heads of ``d`` span ``lanes``:
    one head a block where a head is whole tiles or the only one, else 128 lanes."""
    width = d if d % 128 == 0 or lanes == d else 128
    return width, lanes // width


# Grids are (rows, lane group, outer block, inner block): outer = q block and inner =
# kv block in fwd and dq, the other way round in dkv. ``off`` is the operand's first
# lane group: 0, or where k and v start in a fused q | k | v projection.
def _outer_map(off=0):
    return lambda i, g, a, c: (i, a, off + g)


def _stat_map(i, g, a, c):
    """lse / delta (rows, heads, q blocks, 8, bq) in fwd and dq."""
    return (i, g, a, 0, 0)


def _slopes_map(fold):
    """alibi slopes (h, 8, 128) by the heads of lane group ``g``; ``fold`` = h where
    the heads are folded into the rows (``i = bi*h + hi``, one group), else 1."""
    return lambda i, g, a, c: (i % fold + g, 0, 0)


def _k_index_map(causal, bq, bk, off=0, window=None):
    """kv-block index map: under causality, blocks above the diagonal clamp to the
    last needed block — same index as the previous grid step, so the pipeline skips
    the copy while ``pl.when`` skips the compute. Shared by fwd and bwd-dq so the
    two cannot drift. Under a ``window`` the blocks wholly behind the band clamp
    forward to the first block it reaches, likewise."""
    def k_index(i, g, j, kb):
        if causal:
            kb = jnp.minimum(kb, _causal_k_hi(j, bq, bk))
        if window is not None:
            kb = jnp.maximum(kb, _window_k_lo(j, bq, bk, window))
        return (i, kb, off + g)
    return k_index


def _q_index_map(causal, bq, bk, off=None):
    """q-block index map for the dkv kernel, or (``off`` None) its lse/delta one: q
    blocks strictly above the causal diagonal clamp forward to the first contributing
    block (no copy, no compute)."""
    def q_index(i, g, kb, qb):
        if causal:
            qb = jnp.maximum(qb, _causal_q_lo(kb, bq, bk))
        return (i, g, qb, 0, 0) if off is None else (i, qb, off + g)
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


def _head_lanes(x, hh, d):
    """``x`` (rows, lanes) with every lane outside head ``hh``'s ``d`` zeroed: as an
    operand of a contraction over the lanes it gives that head's product alone.
    ``x`` itself where the block is one head."""
    if x.shape[-1] == d:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= hh * d) & (lane < (hh + 1) * d), x, jnp.zeros_like(x))


def _by_head(xs, d, width):
    """(rows, width) whose lanes of head ``hh`` are ``xs[hh]``'s, each (rows, width)
    or a (rows, 1) column; ``xs[0]`` itself where the block is one head."""
    out = xs[-1]
    if len(xs) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], width), 1)
        for hh in range(len(xs) - 2, -1, -1):
            out = jnp.where(lane < (hh + 1) * d, xs[hh], out)
    return out


def _scores(rows, cols, scale, slope, off, masked, key_axis, mask_block=1,
            window=None):
    """Scaled scores ``rows @ cols^T`` of one tile; keys run along ``key_axis`` of
    the result and ``off`` = (first q row) - (first key column) in sequence
    positions. The alibi term ``slope * (key - query)`` rides every tile; iota,
    compare and select are spent only where ``masked`` says the diagonal crosses
    the tile. ``mask_block`` > 1 is the block-causal mask: a key is seen when its
    block of that many positions is not later than the query's, so a query sees
    up to the end of its own block; tiles start on block boundaries (every tile
    size is a multiple of the block), so the query's place in its block is its
    row's in the tile. ``window``: a key more than ``window - 1`` positions
    behind its query is not seen either (the band's other edge)."""
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
    elif masked and window is not None:
        s = jnp.where(jnp.logical_and(dist <= 0, dist > -window), s, NEG_INF)
    elif masked:
        s = jnp.where(dist <= 0, s, NEG_INF)
    return s


def _walk(causal, q_idx, k_idx, nq, bq, bk, by_cols, sb, strip, commit, window=None):
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
    0, and static, in a crossed block of equal sides. Under a ``window`` (causal)
    a block wholly inside the band is walked unmasked, one that either edge of the
    band crosses is masked all over, and one behind the band is skipped as one
    above the diagonal is (a row that sees no key of a crossed block carries
    nothing on from it: its running max stays ``NEG_INF`` there, and the block of
    its own diagonal, which comes after, rescales what it summed by exactly 0)."""
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
    if window is not None:
        inside = _inside_band(q_idx, k_idx, bq, bk, window)
        reached = jnp.logical_and(k_idx >= _window_k_lo(q_idx, bq, bk, window),
                                  k_idx <= _causal_k_hi(q_idx, bq, bk))
        if bq + bk - 1 <= window:                 # else no block can be inside
            pl.when(inside)(lambda: run(_rect_strips(whole, other, sb, False),
                                        base_off))
        pl.when(jnp.logical_and(reached, jnp.logical_not(inside)))(
            lambda: run(_rect_strips(whole, other, sb, True), base_off))
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
def _fwd_kernel(*refs, d, scale, causal, use_alibi, nq, nk, bq, bk, mask_block=1,
                window=None):
    q_ref, k_ref, v_ref = refs[:3]
    slopes_ref = refs[3] if use_alibi else None
    o_ref, lse_ref, *scratch = refs[4 if use_alibi else 3:]
    width = o_ref.shape[-1]
    # the heads of a block, by the queries' lanes: the output's are the same
    # but where values are narrower or wider than queries and keys (one head)
    heads = range(q_ref.shape[-1] // d)
    j = pl.program_id(2)
    kb = pl.program_id(3)
    if nk > 1:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(kb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def strip(r0, nr, parts, base_off):
        """Online-softmax step of q rows [r0, r0+nr) over the key columns in
        ``parts``, a head of the block at a time: (new running max and row sum of
        these columns against it, a list entry a head, as (nr, 1) columns — one max
        for the strip, not one per tile; unnormalised output, every head in its
        lanes)."""
        q = q_ref[0, r0:r0 + nr, :]
        ms, ls, accs = [], [], []
        for hh in heads:
            slope = slopes_ref[hh, 0, 0] if use_alibi else None
            qh = _head_lanes(q, hh, d)
            ss = [_scores(qh, k_ref[0, c0:c0 + nc, :], scale, slope,
                          base_off + r0 - c0, masked, 1, mask_block, window)
                  for c0, nc, masked in parts]
            # one kv block holds every key of its rows: no running max to start from
            m = None if nk == 1 else m_scr[hh, r0:r0 + nr, :]
            for s in ss:
                s_max = s.max(axis=-1, keepdims=True)
                m = s_max if m is None else jnp.maximum(m, s_max)
            l = acc = None
            for s, (c0, nc, _) in zip(ss, parts):
                v = v_ref[0, c0:c0 + nc, :]
                p = jnp.exp(s - m)
                pv = _dot(p.astype(v.dtype), v, (1, 0))     # every head's lanes of v
                p_sum = p.sum(axis=-1, keepdims=True)
                l = p_sum if l is None else l + p_sum
                acc = pv if acc is None else acc + pv
            ms.append(m)
            ls.append(l)
            accs.append(acc)
        return ms, ls, _by_head(accs, d, width)

    def write(r0, nr, ms, ls, acc):
        ls = [jnp.where(l > 0, l, 1.0) for l in ls]
        o_ref[0, r0:r0 + nr, :] = (acc / _by_head(ls, d, width)).astype(o_ref.dtype)
        # lse stored (rows, heads, nq, 8, bq), q rows along lanes: TPU block tiling
        # needs the last two dims (sublane, lane) aligned; the 8 duplicate sublanes
        # keep the layout legal
        for hh in heads:
            lse = (ms[hh] + jnp.log(ls[hh]))[:, 0]
            lse_ref[0, hh, 0, :, r0:r0 + nr] = jnp.broadcast_to(lse[None, :], (8, nr))

    if nk == 1:
        _walk(causal, j, kb, nq, bq, bk, False, FWD_STRIP, strip, write, window)
        return

    def carry(r0, nr, ms, ls, acc):
        """Rescale the rows' running sums to their new max and add the strip's."""
        rows = slice(r0, r0 + nr)
        alphas = []
        for hh in heads:
            alpha = jnp.exp(m_scr[hh, rows, :] - ms[hh])
            l_scr[hh, rows, :] = alpha * l_scr[hh, rows, :] + ls[hh]
            m_scr[hh, rows, :] = ms[hh]
            alphas.append(alpha)
        acc_scr[rows, :] = _by_head(alphas, d, width) * acc_scr[rows, :] + acc

    _walk(causal, j, kb, nq, bq, bk, False, FWD_STRIP, strip, carry, window)

    @pl.when(kb == nk - 1)
    def _finalize():
        write(0, bq, [m_scr[hh] for hh in heads], [l_scr[hh] for hh in heads],
              acc_scr[...])


def _compiler_params(hpb: int):
    """Several heads a block keep their intermediates live side by side and a (bq, 1)
    column of running max and sum each, a lane tile wide in VMEM: float32 operands
    pass the 16 MiB default from 2k tokens on (19.6 MiB at four heads of 32)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=32 * 2 ** 20 if hpb > 1 else None)


def _flash_fwd(q, k, v, slopes, fused, d, scale, causal, block_q, block_k,
               mask_block=1, window=None):
    """q/k/v: (rows, t, lanes) holding ``lanes // d`` heads of ``d`` each — (b, t, h*d)
    flat or (b*h, t, d) — or, ``fused``, ONE (b, t, 3*lanes) array passed three times
    and read at q | k | v's lane offsets. ``slopes``: alibi slopes (h, 8, 128), the
    value duplicated for lane alignment, or None. Returns (o (rows, t, lanes),
    lse (rows, heads, t)). ``v`` may hold heads of another width than q's and k's
    ``d`` (:func:`flash_attention_local` says when): a lane group is then one head in
    all three, ``o`` has v's lanes. ``window`` (causal, forward only): query ``i``
    sees keys ``(i - window, i]``; None leaves the call as it was."""
    rows, t, lanes = q.shape
    lanes //= 3 if fused else 1
    width, groups = _lane_groups(lanes, d)
    hpb = width // d
    v_lanes = v.shape[-1] // (3 if fused else 1)
    v_width = v_lanes // groups          # == width but for values of another width
    bq, bk = _block_sizes(t, block_q, block_k)
    nq, nk = t // bq, t // bk
    use_alibi = slopes is not None

    kernel = functools.partial(_fwd_kernel, d=d, scale=scale, causal=causal,
                               use_alibi=use_alibi, nq=nq, nk=nk, bq=bq, bk=bk,
                               mask_block=mask_block,
                               **({} if window is None else {"window": window}))
    in_specs = [
        pl.BlockSpec((1, bq, width), _outer_map()),
        pl.BlockSpec((1, bk, width),
                     _k_index_map(causal, bq, bk, fused * groups, window)),
        pl.BlockSpec((1, bk, v_width),
                     _k_index_map(causal, bq, bk, 2 * fused * groups, window)),
    ]
    args = [q, k, v]
    if use_alibi:
        in_specs.append(pl.BlockSpec((hpb, 8, 128),
                                     _slopes_map(slopes.shape[0] * d // lanes)))
        args.append(slopes)
    o, lse = pl.pallas_call(
        kernel,
        grid=(rows, groups, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, v_width), _outer_map()),
            pl.BlockSpec((1, hpb, 1, 8, bq), _stat_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, t, v_lanes), q.dtype),
            jax.ShapeDtypeStruct((rows, lanes // d, nq, 8, bq), jnp.float32),
        ],
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((hpb, bq, 1), jnp.float32),    # m
            pltpu.VMEM((hpb, bq, 1), jnp.float32),    # l
            pltpu.VMEM((bq, v_width), jnp.float32),   # acc
        ],
        compiler_params=_compiler_params(hpb),
        name="flash_fwd",
        interpret=_interpret(),
    )(*args)
    return o, lse[:, :, :, 0, :].reshape(rows, lanes // d, t)


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


def _head_row_sums(x, hh, d):
    """Sums of ``x`` (rows, lanes) over head ``hh``'s ``d`` lanes, a (rows, 1) column."""
    return _head_lanes(x, hh, d).sum(axis=-1, keepdims=True)


def _bwd_dq_kernel(*refs, d, scale, causal, use_alibi, nq, nk, bq, bk, mask_block=1):
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = refs[:6]
    slopes_ref = refs[6] if use_alibi else None
    dq_ref, delta_ref, *scratch = refs[7 if use_alibi else 6:]
    width = dq_ref.shape[-1]
    heads = range(width // d)
    j = pl.program_id(2)
    kb = pl.program_id(3)

    def strip(r0, nr, parts, base_off):
        # the recomputed s is bit-identical to the s the forward derived lse from:
        # same operands, same matmul policy
        q = q_ref[0, r0:r0 + nr, :]
        do = do_ref[0, r0:r0 + nr, :]
        # delta, the rows' sums of do * o a head: float32 product, float32 sum
        do_o = do.astype(jnp.float32) * o_ref[0, r0:r0 + nr, :].astype(jnp.float32)
        dqs, deltas = [], []
        for hh in heads:
            slope = slopes_ref[hh, 0, 0] if use_alibi else None
            qh = _head_lanes(q, hh, d)
            doh = _head_lanes(do, hh, d)
            lse = lse_ref[0, hh, 0, 0, r0:r0 + nr][:, None]
            delta = _head_row_sums(do_o, hh, d)
            dq = None
            for c0, nc, masked in parts:
                k = k_ref[0, c0:c0 + nc, :]
                v = v_ref[0, c0:c0 + nc, :]
                s = _scores(qh, k, scale, slope, base_off + r0 - c0, masked, 1,
                            mask_block)
                p = jnp.exp(s - lse)                           # true probs
                dp = _dot(doh, v, (1, 1))
                # ds without its factor ``scale``: applied to the (bq, lanes) result
                ds = (p * (dp - delta)).astype(k.dtype)
                part = _dot(ds, k, (1, 0))                     # every head's lanes of k
                dq = part if dq is None else dq + part
            dqs.append(dq)
            deltas.append(delta)
        return _by_head(dqs, d, width), deltas

    def walk(commit):
        def take(r0, nr, dq, deltas):
            # delta goes out for the dkv kernel as the forward stores lse: rows along
            # lanes, 8 duplicate sublanes. Its block does not move along the kv axis,
            # and every kv block a q block visits (its first is block 0, causal or
            # not) writes the same values
            for hh in heads:
                delta_ref[0, hh, 0, :, r0:r0 + nr] = jnp.broadcast_to(
                    deltas[hh][:, 0][None, :], (8, nr))
            commit(r0, nr, dq)

        _walk(causal, j, kb, nq, bq, bk, False, BWD_STRIP, strip, take)

    _summed((dq_ref,), scratch, (scale,), kb, nk, walk)


def _bwd_dkv_kernel(*refs, d, scale, causal, use_alibi, nq, bq, bk, mask_block=1):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    slopes_ref = refs[6] if use_alibi else None
    dk_ref, dv_ref, *scratch = refs[7 if use_alibi else 6:]
    width = dk_ref.shape[-1]
    kb = pl.program_id(2)
    qb = pl.program_id(3)

    def strip(c0, nc, parts, base_off):
        """dk, dv of key columns [c0, c0+nc) from the q rows in ``parts``. Scores are
        formed keys-by-queries, so lse and delta (rows along lanes) broadcast as they
        are stored and every matmul takes its operands as they lie — no transpose of
        a (keys x queries) tile."""
        k = k_ref[0, c0:c0 + nc, :]
        v = v_ref[0, c0:c0 + nc, :]
        dks, dvs = [], []
        for hh in range(width // d):
            slope = slopes_ref[hh, 0, 0] if use_alibi else None
            kh = _head_lanes(k, hh, d)
            vh = _head_lanes(v, hh, d)
            dk = dv = None
            for r0, nr, masked in parts:
                q = q_ref[0, r0:r0 + nr, :]
                do = do_ref[0, r0:r0 + nr, :]
                lse = lse_ref[0, hh, 0, 0:1, r0:r0 + nr]       # (1, nr)
                delta = delta_ref[0, hh, 0, 0:1, r0:r0 + nr]
                st = _scores(kh, q, scale, slope, base_off + r0 - c0, masked, 0,
                             mask_block)
                pt = jnp.exp(st - lse)                         # (nc, nr)
                dpt = _dot(vh, do, (1, 1))
                dst = (pt * (dpt - delta)).astype(q.dtype)     # see _bwd_dq_kernel
                dv_part = _dot(pt.astype(do.dtype), do, (1, 0))
                dk_part = _dot(dst, q, (1, 0))
                dv = dv_part if dv is None else dv + dv_part
                dk = dk_part if dk is None else dk + dk_part
            dks.append(dk)
            dvs.append(dv)
        return _by_head(dks, d, width), _by_head(dvs, d, width)

    _summed((dk_ref, dv_ref), scratch, (scale, 1.0), qb, nq, lambda commit: _walk(
        causal, qb, kb, nq, bq, bk, True, BWD_STRIP, strip, commit))


def _flash_bwd(q, k, v, o, lse, do, slopes, fused, d, scale, causal, block_q,
               block_k, mask_block=1):
    """Operands as :func:`_flash_fwd`'s; ``o``/``do`` (rows, t, lanes), ``lse`` (rows,
    heads, t). Returns dq, dk, dv, each (rows, t, lanes). ``flash_bwd_dq`` reads ``o``
    beside ``do``, makes ``delta`` (the rows' sums of ``do * o`` a head) from the two and
    hands it to ``flash_bwd_dkv`` as its second result: no XLA op stands between the
    forward's residuals and the two kernels but ``lse``'s broadcast over 8 sublanes."""
    rows, t, lanes = o.shape
    width, groups = _lane_groups(lanes, d)
    hpb, heads = width // d, lanes // d
    bq, bk = _block_sizes(t, block_q, block_k)
    nq, nk = t // bq, t // bk
    use_alibi = slopes is not None
    stat_shape = (rows, heads, nq, 8, bq)
    lse_b = jnp.broadcast_to(lse.reshape(rows, heads, nq, 1, bq), stat_shape)
    k_off, v_off = fused * groups, 2 * fused * groups
    slopes_spec, slopes_arg = [], []
    if use_alibi:
        slopes_spec = [pl.BlockSpec((hpb, 8, 128),
                                    _slopes_map(slopes.shape[0] * d // lanes))]
        slopes_arg = [slopes]
    params = dict(d=d, scale=scale, causal=causal, use_alibi=use_alibi, nq=nq, bq=bq,
                  bk=bk, mask_block=mask_block)
    q_block, k_block = (1, bq, width), (1, bk, width)
    stat_block = (1, hpb, 1, 8, bq)
    sds = jax.ShapeDtypeStruct((rows, t, lanes), o.dtype)
    compiler_params = _compiler_params(hpb)

    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **params),
        grid=(rows, groups, nq, nk),
        in_specs=[
            pl.BlockSpec(q_block, _outer_map()),
            pl.BlockSpec(k_block, _k_index_map(causal, bq, bk, k_off)),
            pl.BlockSpec(k_block, _k_index_map(causal, bq, bk, v_off)),
            pl.BlockSpec(q_block, _outer_map()),
            pl.BlockSpec(q_block, _outer_map()),
            pl.BlockSpec(stat_block, _stat_map),
        ] + slopes_spec,
        out_specs=[pl.BlockSpec(q_block, _outer_map()),
                   pl.BlockSpec(stat_block, _stat_map)],
        out_shape=[sds, jax.ShapeDtypeStruct(stat_shape, jnp.float32)],
        scratch_shapes=[] if nk == 1 else [pltpu.VMEM((bq, width), jnp.float32)],
        compiler_params=compiler_params,
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, do, o, lse_b, *slopes_arg)

    stat_index = _q_index_map(causal, bq, bk)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **params),
        grid=(rows, groups, nk, nq),
        in_specs=[
            pl.BlockSpec(q_block, _q_index_map(causal, bq, bk, 0)),
            pl.BlockSpec(k_block, _outer_map(k_off)),
            pl.BlockSpec(k_block, _outer_map(v_off)),
            pl.BlockSpec(q_block, _q_index_map(causal, bq, bk, 0)),
            pl.BlockSpec(stat_block, stat_index),
            pl.BlockSpec(stat_block, stat_index),
        ] + slopes_spec,
        out_specs=[pl.BlockSpec(k_block, _outer_map())] * 2,
        out_shape=[sds, sds],
        scratch_shapes=[] if nq == 1 else [pltpu.VMEM((bk, width), jnp.float32)] * 2,
        compiler_params=compiler_params,
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(q, k, v, do, lse_b, delta, *slopes_arg)
    return dq, dk, dv


# --------------------------------------------------------------------------- public op
def _make_core(fused: bool):
    """The differentiable kernel call: ``core(q, k, v, slopes, *static)``, or, ``fused``,
    ``core(qkv, slopes, *static)`` with q | k | v along the lanes of one array, whose
    gradient is dq | dk | dv likewise. ``static`` = (d, scale, causal, use_alibi,
    block_q, block_k, mask_block)."""
    n = 1 if fused else 3

    def run(*args):
        qkv, slopes, (d, scale, causal, use_alibi, *blocks) = args[:n], args[n], args[n + 1:]
        return _flash_fwd(*(qkv * 3 if fused else qkv), slopes if use_alibi else None,
                          fused, d, scale, causal, *blocks)

    @functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(n + 1, n + 8)))
    def core(*args):
        return run(*args)[0]

    def core_fwd(*args):
        if fused:
            # the operand as the kernels read it: kept under its name, the backward
            # reads it too and adds no bias to the projection's matmul output again
            args = (checkpoint_name(args[0], FLASH_QKV_NAME), *args[1:])
        o, lse = run(*args)
        # the two residuals no matmul gives back: a remat policy that names them
        # (models/gpt2.py, "dots") keeps them and the backward runs no second forward
        o = checkpoint_name(o, FLASH_OUT_NAME)
        lse = checkpoint_name(lse, FLASH_LSE_NAME)
        return o, (args[:n], o, lse, args[n])

    def core_bwd(d, scale, causal, use_alibi, block_q, block_k, mask_block, res, do):
        qkv, o, lse, slopes = res
        grads = _flash_bwd(*(qkv * 3 if fused else qkv), o, lse, do,
                           slopes if use_alibi else None, fused, d, scale, causal,
                           block_q, block_k, mask_block)
        if fused:
            # dq | dk | dv as the sum of the three padded to the projection's lanes: XLA
            # fuses that into the operand reads of the projection's backward, where a
            # concatenate whose operands are all results of multi-result kernel calls
            # (flash_bwd_dq's second is delta) is written out first, 1 ms a step of the
            # 125M's at (24, 1024, 2304) (PERF.md section 6, PR 56)
            lanes = grads[0].shape[-1]
            dq, dk, dv = (jnp.pad(g, ((0, 0), (0, 0), (i * lanes, (2 - i) * lanes)))
                          for i, g in enumerate(grads))
            grads = (dq + dk + dv,)
        # alibi slopes are a fixed schedule, not trained — zero cotangent
        return (*grads, jnp.zeros_like(slopes))

    core.defvjp(core_fwd, core_bwd)
    return core


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_windowed(q, k, v, static):
    """The forward kernel under a window, ``static`` = (d, scale, block_q, block_k,
    window): forward only, and the backward says so by name."""
    d, scale, block_q, block_k, window = static
    return _flash_fwd(q, k, v, None, False, d, scale, True, block_q, block_k, 1,
                      window)[0]


def _flash_windowed_fwd(q, k, v, static):
    raise NotImplementedError(
        "flash_attention(window=...) is forward only: the backward kernels walk "
        "the causal triangle and would count keys behind the band")


_flash_windowed.defvjp(_flash_windowed_fwd, lambda static, res, do: None)

_flash_core = _make_core(fused=False)
_flash_core_qkv = _make_core(fused=True)

_DUMMY_SLOPES = np.zeros((1, 8, 128), np.float32)


def _slopes_tiles(alibi_slopes):
    """(h,) per-head slopes → (h, 8, 128) f32 (value duplicated for TPU lane
    alignment; the kernel reads element [0, 0] of each head's tile)."""
    s = jnp.asarray(alibi_slopes, jnp.float32)
    return jnp.broadcast_to(s[:, None, None], s.shape + (8, 128))


def flash_attention_local(q4, k4, v4, causal: bool = True,
                          softmax_scale: Optional[float] = None,
                          alibi_slopes: Optional[jnp.ndarray] = None,
                          block_q: int = 1024, block_k: int = 1024,
                          mask_block: int = 1, window: Optional[int] = None):
    """Per-shard kernel invocation with NO mesh dispatch — for callers already inside a
    ``shard_map`` manual region (e.g. the TP pipeline stage_fn), where the public
    :func:`flash_attention`'s own shard_map wrapper would illegally nest."""
    lb, lt, lh, ld = q4.shape
    dv = v4.shape[-1]
    if window is not None:
        if (not causal or window < 1 or alibi_slopes is not None or mask_block > 1
                or dv != ld or not heads_a_block(lh, ld)):
            raise NotImplementedError(
                f"window={window} is the causal band (i - window, i] of heads that lie "
                "in whole lane tiles: without alibi, without mask_block, values as wide "
                "as keys")
        scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(ld))
        o = _flash_windowed(*(x.reshape(lb, lt, lh * ld) for x in (q4, k4, v4)),
                            (ld, scale, block_q, block_k, int(window)))
        return o.reshape(lb, lt, lh, ld)
    if dv != ld and (ld % 128 or dv % 128 or alibi_slopes is not None):
        raise NotImplementedError(
            f"values of {dv} lanes beside queries and keys of {ld}: both must be "
            "whole 128-lane tiles (pad queries and keys with zero lanes), without "
            "alibi")
    if mask_block > 1 and (not causal or lt % mask_block
                           or min(_block_sizes(lt, block_q, block_k)) % mask_block):
        raise ValueError(
            f"mask_block={mask_block} is the block-causal mask: it needs causal=True "
            f"and a sequence ({lt}) and tiles that whole blocks divide")
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(ld))
    use_alibi = alibi_slopes is not None
    static = (ld, scale, causal, use_alibi, block_q, block_k, mask_block)
    slopes = _slopes_tiles(alibi_slopes) if use_alibi else jnp.asarray(_DUMMY_SLOPES)
    if dv != ld:
        # values narrower or wider than queries and keys (a latent layer's expanded
        # heads): the forward kernel alone, a head a lane group in every operand; no
        # gradient is defined (serving prefill only)
        o, _ = _flash_fwd(q4.reshape(lb, lt, lh * ld), k4.reshape(lb, lt, lh * ld),
                          v4.reshape(lb, lt, lh * dv), None, False, ld, scale, causal,
                          block_q, block_k, mask_block)
        return o.reshape(lb, lt, lh, dv)
    if heads_a_block(lh, ld):
        # bitcasts: the kernels pick the head in their index maps
        o = _flash_core(*(x.reshape(lb, lt, lh * ld) for x in (q4, k4, v4)), slopes,
                        *static)
        return o.reshape(lb, lt, lh, ld)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(lb * lh, lt, ld)

    o3 = _flash_core(to3(q4), to3(k4), to3(v4), slopes, *static)
    return o3.reshape(lb, lh, lt, ld).transpose(0, 2, 1, 3)


def _per_shard(local, arrays, slopes=None, heads: int = 0):
    """``local(*arrays[, slopes])`` on each shard of the global mesh's batch axes
    (axis 0 of the arrays) and, where ``heads`` (their axis 2) divide by it, of its
    tensor axis; the plain call without such a mesh. A pallas_call is opaque to the
    SPMD partitioner: under a sharded mesh it would force a full rematerialisation —
    sequence stays unsharded here (ring_attention owns the seq axis)."""
    from ...parallel.mesh import BATCH_AXES, AXIS_TENSOR, get_global_mesh
    extra = () if slopes is None else (slopes,)
    mesh = get_global_mesh()
    if mesh is not None:
        batch_axes = tuple(ax for ax in BATCH_AXES if mesh.size(ax) > 1)
        bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) if batch_axes else 1
        tp = mesh.size(AXIS_TENSOR)
        use_tp = heads > 0 and tp > 1 and heads % tp == 0
        manual = set(batch_axes) | ({AXIS_TENSOR} if use_tp else set())
        if manual and arrays[0].shape[0] % bsz == 0:
            tail = (AXIS_TENSOR if use_tp else None, None) if heads else (None,)
            spec = P(batch_axes or None, None, *tail)
            # slopes shard over the head (TP) axis: each shard sees its heads'
            sspec = (P(AXIS_TENSOR if use_tp else None),) * len(extra)
            mapped = shard_map(local, mesh=mesh.mesh, axis_names=manual,
                               in_specs=(spec,) * len(arrays) + sspec, out_specs=spec,
                               check_vma=False)
            return mapped(*arrays, *extra)
    return local(*arrays, *extra)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, mask: Optional[jnp.ndarray] = None,
                    softmax_scale: Optional[float] = None,
                    dropout_rate: float = 0.0, dropout_rng=None,
                    alibi_slopes: Optional[jnp.ndarray] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    mask_block: int = 1, window: Optional[int] = None) -> jnp.ndarray:
    """Drop-in replacement for ``xla_attention``: q/k/v ``(b, t, h, d)`` → ``(b, t, h, d)``.

    ``alibi_slopes`` (h,) adds the per-head alibi distance bias ``slope*(col-row)``
    inside the kernel (BLOOM; reference fuses the same bias into its attn_softmax
    kernel, ``softmax_kernels.cu``) — no (h, t, s) bias tensor is ever materialised.

    Falls back to the XLA path for features the kernel does not cover (arbitrary masks,
    attention dropout, cross-attention with different kv length). ``mask_block`` > 1
    (with ``causal``) is the block-causal mask of generation by diffusion over blocks:
    key ``j`` is seen by query ``i`` iff ``j // mask_block <= i // mask_block``; only the
    tiles on the diagonal change, so what causality skips stays skipped. ``window``
    (with ``causal``; forward only): query ``i`` sees the ``window`` keys ``(i - window,
    i]``, itself and the ``window - 1`` before it; the kv blocks wholly behind the band
    are skipped as those above the diagonal are, the blocks an edge of the band crosses
    are masked. There is no
    sequence-length guard: K/V blocks stream through the grid pipeline, so VMEM use is
    O(block) regardless of t.
    """
    from ..transformer.attention import xla_attention
    if mask is not None or dropout_rate > 0.0 or q.shape[1] != k.shape[1]:
        if alibi_slopes is not None or mask_block > 1 or window is not None:
            raise NotImplementedError(
                "window is kernel-only" if window is not None else
                "mask_block is kernel-only" if mask_block > 1 else
                "alibi_slopes is kernel-only: combine it with mask/dropout/"
                "cross-attention via the model-level XLA bias path instead")
        return xla_attention(q, k, v, causal=causal, mask=mask,
                             softmax_scale=softmax_scale,
                             dropout_rate=dropout_rate, dropout_rng=dropout_rng)
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / float(np.sqrt(q.shape[-1])))

    def local(q4, k4, v4, slopes=None):
        return flash_attention_local(q4, k4, v4, causal=causal, softmax_scale=scale,
                                     alibi_slopes=slopes,
                                     block_q=block_q, block_k=block_k,
                                     mask_block=mask_block, window=window)

    return _per_shard(local, (q, k, v),
                      None if alibi_slopes is None
                      else jnp.asarray(alibi_slopes, jnp.float32), heads=q.shape[2])


def flash_attention_qkv(qkv: jnp.ndarray, n_head: int, causal: bool = True) -> jnp.ndarray:
    """Attention over a fused projection ``(b, t, 3*h*d)`` = q | k | v along the last
    axis (GPT-2's ``c_attn``) → ``(b, t, h*d)``, what ``jnp.split`` +
    :func:`flash_attention` give, with the kernels reading q, k and v out of the one
    array by lane offsets in their index maps. For head shapes the flat layout holds
    (:func:`heads_a_block`); heads are not sharded over the tensor axis."""
    d = qkv.shape[-1] // (3 * n_head)
    if not heads_a_block(n_head, d):
        raise ValueError(f"{n_head} heads of {d} do not lie in whole lane tiles: split "
                         "q, k, v and call flash_attention")
    static = (d, 1.0 / float(np.sqrt(d)), causal, False, 1024, 1024, 1)
    return _per_shard(
        lambda x: _flash_core_qkv(x, jnp.asarray(_DUMMY_SLOPES), *static), (qkv,))
