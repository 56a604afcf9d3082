"""Fused decode attention with KV cache — the inference hot loop.

TPU-native equivalent of the reference's ``softmax_context`` inference kernel
(``csrc/transformer/inference/csrc/softmax.cu`` + KV-cache layout in ``transform.cu``, bound as
``softmax_context`` in ``pt_binding.cpp``): one kernel computes a single decode step's
attention over the cache with online softmax, masked by the per-sequence cache length —
no (T,) score materialisation in HBM, no dynamic shapes (the cache is a fixed-capacity buffer).

The cache is stored HEAD-MAJOR in rows of whole lane tiles, ``(b, h_kv / r, T, r * d)`` (``r`` KV
heads a row: ``ops/paged_attention.heads_per_row``, the row rule's one home; ``r`` = 1 from d 128),
the layout transformation of ``transform.cu``: a row's cache block is contiguous and the matmuls
batch on the MXU. Grouped queries (``h_kv <= h``) and a row's ``r`` heads are one matmul's rows.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.device import pallas_interpret as _interpret

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, *, block_k, scale, n_lens=1):
    """q_ref: (1, hk, g, d) VMEM; k/v_hbm: (b, hk, T, d) in HBM (DMA'd blockwise);
    len_ref: scalar-prefetch (b,), or (b, n_lens) where a group's ``g`` rows are
    ``n_lens`` equal runs, each with a length of its own. Double-buffered DMA
    overlaps cache reads with compute — the cache never fits VMEM (the reason the
    reference streams its KV cache too)."""
    i = pl.program_id(0)
    L = row_len = len_ref[i] if n_lens == 1 else len_ref[i, 0]
    q = q_ref[0].astype(jnp.float32)            # (hk, g, d)
    hk, g, d = q.shape
    if n_lens > 1:
        # the blocks are read up to the longest run's length, once for all runs
        run = jax.lax.broadcasted_iota(jnp.int32, (hk, g, block_k), 1) // (g // n_lens)
        for j in range(1, n_lens):
            L = jnp.maximum(L, len_ref[i, j])
            row_len = jnp.where(run >= j, len_ref[i, j], row_len)
    nk = pl.cdiv(L, block_k)                    # dynamic: only touch valid cache blocks

    def scoped(k_buf, v_buf, ksem, vsem):
        def k_dma(slot, kb):
            return pltpu.make_async_copy(
                k_hbm.at[i, :, pl.ds(kb * block_k, block_k), :], k_buf.at[slot],
                ksem.at[slot])

        def v_dma(slot, kb):
            return pltpu.make_async_copy(
                v_hbm.at[i, :, pl.ds(kb * block_k, block_k), :], v_buf.at[slot],
                vsem.at[slot])

        k_dma(0, 0).start()
        v_dma(0, 0).start()

        def body(kb, carry):
            m, l, acc = carry
            slot = jax.lax.rem(kb, 2)
            nxt = jax.lax.rem(kb + 1, 2)

            @pl.when(kb + 1 < nk)
            def _():
                k_dma(nxt, kb + 1).start()
                v_dma(nxt, kb + 1).start()

            k_dma(slot, kb).wait()
            v_dma(slot, kb).wait()
            k_blk = k_buf[slot].astype(jnp.float32)   # (hk, bk, d)
            v_blk = v_buf[slot].astype(jnp.float32)
            # (hk, g, d) x (hk, bk, d) -> (hk, g, bk), batched over kv heads
            s = jax.lax.dot_general(
                q, k_blk,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (hk, g, block_k), 2)
            s = jnp.where(cols < row_len, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            # (hk, g, bk) x (hk, bk, d) -> (hk, g, d)
            acc_new = acc * alpha[..., None] + jax.lax.dot_general(
                p, v_blk,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((hk, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((hk, g), jnp.float32)
        acc0 = jnp.zeros((hk, g, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc / l_safe[..., None]).astype(o_ref.dtype)

    pl.run_scoped(
        scoped,
        k_buf=pltpu.VMEM((2, hk, block_k, d), k_hbm.dtype),
        v_buf=pltpu.VMEM((2, hk, block_k, d), v_hbm.dtype),
        ksem=pltpu.SemaphoreType.DMA((2,)),
        vsem=pltpu.SemaphoreType.DMA((2,)),
    )


def _own_lanes(r: int):
    """(r, 1, r, 1) bool over (head j of a row, its queries, lane block j', lane):
    the lanes that are head j's own."""
    return jnp.eye(r, dtype=bool).reshape(r, 1, r, 1)


def pack_queries(q, r: int, rows: int):
    """Queries for a cache whose rows hold ``r`` KV heads side by side
    (``ops/paged_attention.heads_per_row``): ``q`` ``(..., h, d)`` ->
    ``(..., h, r * d)``, the query heads of KV head ``p * r + j`` lying in lanes
    ``[j * d, (j + 1) * d)`` and exact zeros elsewhere, so that a row's ``r * h
    / (rows * r)`` query heads attend the row together and each one's scores
    are its own head's. ``rows`` = ``h_kv / r``, the cache's. The softmax
    scale stays the MODEL's ``1 / sqrt(d)``: the caller takes it before.

    Both helpers are a broadcast, a select and (back) a sum of ``r`` terms of
    which ``r - 1`` are zero: exact, and plain enough for every backend. The
    form with lane-offset slices and a ``stack`` read the wrong lanes for
    every head but a row's first once compiled for the TPU (libtpu 0.0.34; the
    CPU gave the right ones: PERF.md section 6, PR 42), which
    ``ops/kernel_checks.py: check_decode`` now holds on the chip."""
    if r == 1:
        return q
    *lead, h, d = q.shape
    q = q.reshape(*lead, rows, r, h // (rows * r), 1, d)
    return jnp.where(_own_lanes(r), q, 0).reshape(*lead, h, r * d)


def unpack_outputs(o, r: int, rows: int):
    """The inverse on the attention's output: of ``o`` ``(..., h, r * d)`` a
    query head of KV head ``p * r + j`` keeps lanes ``[j * d, (j + 1) * d)``
    (the others hold its weights over the row's other heads' values)."""
    if r == 1:
        return o
    *lead, h, rd = o.shape
    o = o.reshape(*lead, rows, r, h // (rows * r), r, rd // r)
    return jnp.where(_own_lanes(r), o, 0).sum(axis=-2).reshape(*lead, h, rd // r)


def _row_lens(cache_len, r: int):
    """``cache_len`` for packed queries: a row's rows are its ``r`` heads' runs
    one after another, so ``(b, n)`` lengths repeat ``r`` times; ``(b,)`` stays."""
    return cache_len if r == 1 or cache_len.ndim == 1 else jnp.tile(cache_len, (1, r))


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_len: jnp.ndarray, softmax_scale=None,
                     block_k: int = 128) -> jnp.ndarray:
    """One decode step of attention against the cache.

    q: ``(b, h, d)`` (current position); k_cache/v_cache: ``(b, h_kv / r, T, r * d)``
    head-major fixed-capacity rows (``r`` is read off their width); cache_len: ``(b,)`` valid
    lengths (the current position is already written to the cache). Returns ``(b, h, d)``.

    ``cache_len`` ``(b, n)``: ``n`` lengths a sequence. The ``h / h_kv`` rows of
    every key head are then ``n`` equal runs in a row, run ``j`` seeing rows ``[0,
    cache_len[:, j])`` (a block step of ``n`` blocks a sequence lays its queries
    out so): one call reads the cache once for all runs. With ``(b,)`` the
    kernel is the one-length kernel it was.
    """
    b, h, d = q.shape
    hk, T = k_cache.shape[1], k_cache.shape[2]
    r = k_cache.shape[3] // d
    if not (h % (hk * r) == 0):
        raise AssertionError(f"query heads {h} must be a multiple of kv heads {hk * r}")
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(d))
    if d % 128 != 0 and not _interpret():
        # the choice reads the MODEL's head size: a configuration states which kernels
        # its decode chunk holds (lfm2-8b-a1b.conv32, 32Q/8KV x 64: none for attention),
        # so a d 64 model's packed rows, 128 lanes wide as Mosaic's HBM slices must be,
        # stay in XLA until that route moves (PERF.md section 7)
        return decode_attention_live(q, k_cache, v_cache, cache_len, softmax_scale)
    q = pack_queries(q, r, hk)
    g = h // hk                                 # a row's r heads x their group
    bk = min(block_k, T)
    while T % bk:
        bk //= 2
    q4 = q.reshape(b, hk, g, r * d)
    lens = _row_lens(cache_len.astype(jnp.int32), r)
    n_lens = 1 if lens.ndim == 1 else lens.shape[1]
    if g % n_lens:
        raise AssertionError(f"{g} rows a key head are not {n_lens} equal runs")
    if n_lens == 1:
        lens = lens.reshape(b)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hk, g, r * d), lambda i, lens_ref: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # cache stays in HBM, DMA'd blockwise
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hk, g, r * d), lambda i, lens_ref: (i, 0, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=bk, scale=scale, n_lens=n_lens),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, r * d), q.dtype),
        name="decode_attention",
        interpret=_interpret(),
    )(lens, q4, k_cache, v_cache)
    return unpack_outputs(out.reshape(b, h, r * d), r, hk)


def _rows_and_lens(q, k_cache, cache_len):
    """What both XLA forms start from: the packed queries ``(b, hk, g, r * d)``
    in float32 and every query row's visible length ``(b, g)``."""
    b, h, d = q.shape
    hk = k_cache.shape[1]
    r = k_cache.shape[3] // d
    g = h // hk
    q4 = pack_queries(q, r, hk).reshape(b, hk, g, r * d).astype(jnp.float32)
    cache_len = _row_lens(cache_len.astype(jnp.int32), r)
    if cache_len.ndim == 1:
        cache_len = cache_len[:, None]
    return q4, jnp.repeat(cache_len, g // cache_len.shape[1], axis=1), (r, hk, g)


def _row_bias(row_len, T: int, slopes):
    """What is added to the float32 scores ``(b, hk, g, T)``, as ``(b, 1 or
    hk, g, T)``: ``NEG_INF`` at the rows a query does not see (a score under it
    is ``NEG_INF`` exactly), else 0, or with ALiBi ``slopes`` ``(h,)`` a head's
    slope times the row's distance from the query's own position, the last row
    it sees. Small, and the same for every layer of a step."""
    pos = jnp.arange(T, dtype=jnp.int32)
    seen = row_len[:, None, :, None]                                # (b, 1, g, 1)
    bias = 0.0
    if slopes is not None:
        g = row_len.shape[1]
        bias = (jnp.asarray(slopes, jnp.float32).reshape(1, -1, g, 1)
                * (pos - (seen - 1)).astype(jnp.float32))
    return jnp.where(pos < seen, bias, NEG_INF)


def _seen(s):
    """The scores that are of rows their query sees (:func:`_row_bias`)."""
    return s > 0.5 * NEG_INF


def live_block(T: int) -> int:
    """The rows a block of :func:`decode_attention_live` holds, from the cap
    alone: the largest multiple of 16 (a bf16 tile's rows) that divides ``T``
    and is at most a sixth of it, held between 64 and 256; a cap with no such
    divisor is one block. 96 of 576, 256 of 2048: by the chip (``PERF.md``
    section 6, PR 51: an iteration costs ~2.5 us and its rows ~0.08 us each,
    so small blocks win where most of the cap is empty and large ones where
    the longest sequence fills it)."""
    most = min(max(T // 6, 64), 256, T)
    for rows in range(most // 16 * 16, 0, -16):
        if T % rows == 0:
            return rows
    return T


def live_rows(longest: int, T: int) -> int:
    """The cache rows :func:`decode_attention_live` walks for a batch whose
    longest sequence sees ``longest`` rows: whole blocks, at most the cap."""
    block = live_block(T)
    return min(T, -(-longest // block) * block)


@functools.partial(jax.jit, static_argnames=("softmax_scale", "block"))
def decode_attention_live(q, k_cache, v_cache, cache_len, softmax_scale=None,
                          slopes=None, *, block=None):
    """The served XLA form: one-token attention over the batch's LIVE rows.

    Operands as :func:`decode_attention` (both forms of ``cache_len``, packed
    rows), with optional ALiBi ``slopes`` ``(h,)``. The cache is walked in
    blocks of ``B = live_block(T)`` rows, ``j = 0 .. ceil(max(cache_len) / B) -
    1``: a block of K and V is sliced at its block-aligned row and converted
    to float32 (the whole cap never is), scored, masked by every query's own
    length and folded into an online softmax (running max, sum and output in
    float32, as ``_decode_kernel`` does for its ``block_k``). The trip count
    is the batch's: every sequence's blocks up to the longest one's.

    What the serving parity leans on: a sequence's output is BIT-EQUAL
    whatever the trip count and whatever ``T`` is, for the same ``B``. A
    block wholly past a sequence's length gives it scores of ``NEG_INF``
    under a running max it already has, so ``exp(m - m) = 1`` scales its sum
    and output and exact zeros are added to them. (Not so for a whole-cap
    softmax over a shorter static slice: its reduction's shape changes.)
    A sequence that sees no row at all attends nothing: zeros.

    Jitted, so that a program of many layers traces and lowers the walk ONCE
    and calls it a layer (XLA inlines the calls: the compiled step is the
    same): traced a layer, the ``while``'s body took BLOOM's chunk of 30
    layers 6.5 s more of every set-up on the chip's host (``PERF.md`` section
    6, PR 51). ``softmax_scale`` is therefore a Python number, not an array.

    ``block`` is the tests' (one ``B`` under two caps); callers leave it."""
    b, h, d = q.shape
    T = k_cache.shape[2]
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(d))
    q4, row_len, (r, hk, g) = _rows_and_lens(q, k_cache, cache_len)
    B = live_block(T) if block is None else block
    if T % B:
        raise AssertionError(f"blocks of {B} rows do not tile a cache of {T}")
    bias = _row_bias(row_len, T, slopes)

    def body(j, carry):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k_cache, j * B, B, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(v_cache, j * B, B, axis=2)
        s = (jnp.einsum("bkgd,bktd->bkgt", q4, k_blk.astype(jnp.float32)) * scale
             + jax.lax.dynamic_slice_in_dim(bias, j * B, B, axis=3))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(_seen(s), jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgt,bktd->bkgd", p, v_blk.astype(jnp.float32))
        return m_new, l_new, acc_new

    blocks = jnp.minimum((jnp.max(row_len) + B - 1) // B, T // B)
    m0 = jnp.full((b, hk, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, g), jnp.float32)
    acc0 = jnp.zeros((b, hk, g, r * d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, blocks, body, (m0, l0, acc0))
    o = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return unpack_outputs(o.reshape(b, h, r * d), r, hk).astype(q.dtype)


def decode_attention_xla(q, k_cache, v_cache, cache_len, softmax_scale=None,
                         slopes=None):
    """The plain whole-cap form: the ground truth the kernel's and the served
    form's tests compare against (``ops/kernel_checks.py`` on the chip), never
    served. Same cache rows as the kernel (the module docstring has them),
    the same two forms of ``cache_len``, optional ALiBi ``slopes`` ``(h,)``;
    a sequence that sees no row gets zeros, as from the kernel."""
    b, h, d = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(d))
    q4, row_len, (r, hk, g) = _rows_and_lens(q, k_cache, cache_len)
    s = (jnp.einsum("bkgd,bktd->bkgt", q4, k_cache.astype(jnp.float32)) * scale
         + _row_bias(row_len, k_cache.shape[2], slopes))
    p = jnp.where(_seen(s), jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bkgt,bktd->bkgd", p, v_cache.astype(jnp.float32))
    return unpack_outputs(o.reshape(b, h, r * d), r, hk).astype(q.dtype)
