"""Paged-attention decode: gather K/V blocks by page index inside the kernel.

The paged sibling of ``ops/attention/decode.py`` — vLLM's PagedAttention idiom
done TPU-style. The KV store is one global pool of fixed-size pages per layer,
``{"k": (P, h_kv / r, page, r * d), "v": ...}``; each decode slot owns a **static-shape
page table** row ``(max_pages,)`` of physical page indices (padded with the
null-page sentinel 0 — page 0 is reserved, never allocated, and every row it
could contribute is masked by ``cache_len``). All shapes are static: the page
count ``P``, the per-slot table width and the page size are compile-time
constants, so a slot serving an 8-token prompt and one serving a 500-token
prompt hit the SAME compiled chunk — page-count growth never mints a compile
key (pinned by the analysis sweep's serving lane).

This module is the one place that knows the page layout ``(P, h_kv / r, page,
r * d)``: how many KV heads ``r`` lie side by side in a row of the pages, of
the dense view and of the contiguous cache (:func:`heads_per_row`, the row
rule's one home; :func:`kv_rows` lays a projection's keys or values out so),
pages -> dense rows (:func:`gather_kv_dense`, :func:`pages_to_dense`),
dense rows -> whole pages (:func:`write_dense_pages`), a slot's view rows ->
in-place page slabs (:func:`write_view_rows`) and one row -> (page, offset)
(:func:`paged_cache_update`). The pool's movers
and the serve programs call these; all but the first two take a row's head and
lane extents from their operands, whatever ``r`` made them.

Two implementations, PR-5 style:

- :func:`paged_attention_xla` — ground truth: gather the slot's pages into the
  dense head-major ``(b, h_kv, cap, d)`` view and run the EXACT same masked
  softmax as ``decode_attention_xla``. Because the gathered view is
  element-identical to the contiguous cache ``engine.generate`` decodes over
  (and sliced to exactly ``cap`` rows), greedy decode through this path is
  **bit-identical** to it — the property every serving parity test leans on.
- :func:`paged_attention` — the fused Pallas kernel: grid over slots, K/V
  pages DMA'd HBM→VMEM double-buffered **by page index** (the gather happens
  inside the grid; the dense view is never materialised in HBM), online
  softmax across pages. Used on a real TPU backend;
  ``DS_TPU_PAGED_FORCE_FUSED=1`` routes CPU tests through interpret mode
  (kernel-vs-XLA parity is a test gate, same contract as
  ``DS_TPU_WQ_FORCE_FUSED``).
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import pallas_interpret as _interpret
from .attention.decode import (NEG_INF, decode_attention_xla, pack_queries,
                               unpack_outputs)

FORCE_FUSED_ENV = "DS_TPU_PAGED_FORCE_FUSED"


def fused_paged_active() -> bool:
    """Fused kernel engaged: a real TPU backend, or the env override routing
    CPU tests through interpret mode."""
    if os.environ.get(FORCE_FUSED_ENV, "0") == "1":
        return True
    return jax.default_backend() == "tpu"


def fused_paged_for(head_dim: int) -> bool:
    """Would :func:`paged_attention` dispatch to the fused kernel for this
    head dim? The chunk builder keys its body shape off this — compiling the
    fused body while the per-step dispatcher falls back to XLA would gather
    the dense view EVERY step instead of once per chunk (the exact regression
    the fallback chunk exists to avoid)."""
    return fused_paged_active() and (head_dim % 128 == 0 or _interpret())


# ------------------------------------------------------------- the row rule
def heads_per_row(head_dim: int, kv_heads: int) -> int:
    """How many consecutive KV heads ``r`` a cache row holds side by side:
    pages ``(P, kv_heads / r, page, r * head_dim)``, dense view and contiguous
    cache ``(b, kv_heads / r, T, r * head_dim)``. A row is one whole 128-lane
    tile where the head size divides 128 and is smaller (two heads at 64: the
    same bytes, no padding, where a 64-wide row half-fills every tile it
    touches); 1 from 128 on, where the heads do not come in whole rows, and
    under a tensor-parallel mesh that splits the KV heads finer than a row.
    ``init_cache`` and ``PagedKVPool`` ask; everything downstream reads ``r``
    off its operands (a cache's last extent over the model's head size)."""
    from ..parallel.mesh import AXIS_TENSOR, get_global_mesh
    r = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    mesh = get_global_mesh()
    tp = mesh.size(AXIS_TENSOR) if mesh is not None else 1
    local = kv_heads // tp if kv_heads % tp == 0 else kv_heads
    return r if local % r == 0 else 1


def kv_rows(x, r: int):
    """A projection's keys or values ``(b, t, h_kv, d)`` (after the per-head
    norm and the rotation) as head-major cache rows ``(b, h_kv / r, t, r *
    d)``: a row's ``r`` heads are adjacent in memory already."""
    b, t, hk, d = x.shape
    if r > 1:
        x = x.reshape(b, t, hk // r, r * d)
    return x.transpose(0, 2, 1, 3)


# ------------------------------------------------------------- dense gather
def gather_kv_dense(k_pages, v_pages, page_table, cap: int):
    """Reassemble the dense head-major cache view from pages.

    ``k_pages``/``v_pages``: ``(P, hk, page, d)`` (``hk`` rows of ``d`` lanes,
    here and below: :func:`heads_per_row`); ``page_table``:
    ``(b, max_pages)`` int32. Returns ``(b, hk, cap, d)`` ×2 — rows sliced to
    EXACTLY ``cap`` so downstream attention math (reduction shapes included)
    is identical to a contiguous ``cap``-row cache's, keeping greedy
    bit-exact even when ``cap`` is not a page multiple (pages round it up
    internally)."""
    kp = k_pages[page_table]                       # (b, mp, hk, page, d)
    vp = v_pages[page_table]
    b, mp, hk, ps, d = kp.shape
    k = kp.transpose(0, 2, 1, 3, 4).reshape(b, hk, mp * ps, d)
    v = vp.transpose(0, 2, 1, 3, 4).reshape(b, hk, mp * ps, d)
    return k[:, :, :cap, :], v[:, :, :cap, :]


def pages_to_dense(pages, tbl):
    """One table row's form of :func:`gather_kv_dense`, an array at a time:
    ``pages (P, hk, page, d)`` through ``tbl (n,)`` -> the ``(hk, n * page,
    d)`` rows those pages hold, in table order. The caller slices to the rows
    it wants."""
    hk, d = pages.shape[1], pages.shape[3]
    return pages[tbl].transpose(1, 0, 2, 3).reshape(hk, -1, d)


def write_dense_pages(pages, dense, tbl):
    """The inverse, a layer at a time: overwrite pages ``tbl (n,)`` of
    ``pages {"k", "v"}: (P, hk, page, d)`` with the dense rows ``dense {"k",
    "v"}: (hk, R, d)`` (or the batch of one, ``(1, hk, R, d)``, a prefill
    returns), zero-padded to ``n`` whole pages."""
    n, ps = tbl.shape[0], pages["k"].shape[2]
    blocks = {}
    for key in ("k", "v"):
        x = dense[key][0] if dense[key].ndim == 4 else dense[key]
        hk, R, d = x.shape
        blocks[key] = jnp.pad(x, ((0, 0), (0, n * ps - R), (0, 0))) \
            .reshape(hk, n, ps, d)
    return {key: pages[key].at[tbl].set(
        blocks[key].transpose(1, 0, 2, 3).astype(pages[key].dtype))
        for key in ("k", "v")}


def write_view_rows(pages, view, page_table, start, count, span: int,
                    kv_cap: int):
    """Dense view rows -> the pages, as in-place page slabs: rows ``[start[s],
    start[s] + count[s])`` of every slot ``s``, below ``kv_cap``, and no other.

    ``pages`` and ``view`` are pytrees with the same leaves in the same order
    (the k and v arrays of every layer): ``(P, hk, page, d)`` pages and their
    ``(S, hk, rows, d)`` dense views, ``rows >= kv_cap``; ``page_table (S,
    max_pages)``; ``start``, ``count`` ``(S,)`` with ``count <= span``, the
    static bound that says how many pages a slot's rows can reach: ``n =
    ceil(span / page) + 1`` (a chunk of 8 on pages of 16: two). The page
    positions, page indices and row masks are computed once, ``(S, n)`` at a
    time, and shared by every array. For each of a slot's ``n`` pages an array
    takes the page's rows out of the view with one ``dynamic_slice`` at a
    page-aligned row (the slot index static, no gather), keeps the page's
    present rows wherever the mask says no, and writes the page back with one
    ``dynamic_update_slice``: nothing the shape of the pages is copied or
    re-laid-out (an ``.at[page, :, row, :].set`` scatter makes the TPU
    compiler transpose the whole pool and back, every call). A slot with
    ``count`` 0 and a row at or past ``kv_cap`` rewrite a page with its own
    content, so a shared, released or null page never changes value."""
    leaves, tree = jax.tree_util.tree_flatten(pages)
    ps = leaves[0].shape[2]
    S, mp = page_table.shape
    n = min(-(-span // ps) + 1, mp)
    # rows past the table's last page are at or past kv_cap: clamping the
    # first page back over the table's end loses none that would be written
    pos = (jnp.clip(start // ps, 0, mp - n)[:, None]
           + jnp.arange(n, dtype=start.dtype)[None])              # (S, n)
    pidx = jnp.take_along_axis(page_table, pos, axis=1)           # (S, n)
    row0 = pos * ps
    rows = row0[:, :, None] + jnp.arange(ps, dtype=start.dtype)   # (S, n, page)
    end = jnp.minimum(start + count, kv_cap)[:, None, None]
    keep = ((rows >= start[:, None, None]) & (rows < end))[:, :, None, :, None]
    views = jax.tree_util.tree_leaves(view)
    ragged = -views[0].shape[2] % ps
    if ragged:      # a view that ends inside a page: its last slab's slice
        views = [jnp.pad(vw, ((0, 0), (0, 0), (0, ragged), (0, 0)))   # would slide back
                 for vw in views]
    for s in range(S):
        for i in range(n):
            leaves = _write_slab(leaves, views, s, pidx[s, i], row0[s, i],
                                 keep[s:s + 1, i])
    return tree.unflatten(leaves)


@jax.jit
def _write_slab(pages, views, s, page, row, keep):
    """One page of one slot, in every array: :func:`write_view_rows`'s unit,
    jitted so that a program which writes a thousand of them traces and lowers
    ONE (the compiler inlines the calls: ``s`` is a constant again there)."""
    out = []
    for pg, vw in zip(pages, views):
        at, slab = (page, 0, 0, 0), (1,) + pg.shape[1:]
        new = jax.lax.dynamic_slice(vw, (s, 0, row, 0), slab)
        old = jax.lax.dynamic_slice(pg, at, slab)
        out.append(jax.lax.dynamic_update_slice(
            pg, jnp.where(keep, new.astype(pg.dtype), old), at))
    return out


def paged_attention_xla(q, k_pages, v_pages, page_table, cache_len, cap: int,
                        softmax_scale=None):
    """Ground-truth paged decode attention: dense gather + the contiguous
    cache's exact masked-softmax math (``decode_attention_xla``)."""
    k, v = gather_kv_dense(k_pages, v_pages, page_table, cap)
    return decode_attention_xla(q, k, v, cache_len, softmax_scale)


# ----------------------------------------------------------- cache update
def paged_cache_update(k_pages, v_pages, k_new, v_new, page_table, cache_len):
    """Append one decode step's K/V at each slot's ``cache_len`` position.

    ``k_new``/``v_new``: ``(b, hk, 1, d)``; writes land at physical page
    ``page_table[s, cache_len[s] // page]`` row ``cache_len[s] % page``.
    Per-slot pages are disjoint (allocator invariant), so the batched scatter
    has unique indices."""
    ps = k_pages.shape[2]
    page_pos = cache_len // ps                      # (b,) slot-local page idx
    off = cache_len % ps
    pidx = jnp.take_along_axis(page_table, page_pos[:, None], axis=1)[:, 0]
    k_pages = k_pages.at[pidx, :, off, :].set(
        k_new[:, :, 0, :].astype(k_pages.dtype))
    v_pages = v_pages.at[pidx, :, off, :].set(
        v_new[:, :, 0, :].astype(v_pages.dtype))
    return k_pages, v_pages


# ------------------------------------------------------------ fused kernel
def _paged_decode_kernel(len_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref, *,
                         page: int, max_pages: int, scale):
    """q_ref: (1, hk, g, d) VMEM; k/v_hbm: (P, hk, page, d) pages in HBM.
    len_ref (b,) and table_ref (b * max_pages,) are scalar-prefetch. The DMA
    source block is selected by PAGE INDEX — the gather lives inside the
    grid, double-buffered so page fetches overlap the online-softmax math
    (same pipeline shape as ``ops/attention/decode._decode_kernel``)."""
    i = pl.program_id(0)
    L = len_ref[i]
    q = q_ref[0].astype(jnp.float32)                # (hk, g, d)
    hk, g, d = q.shape
    npg = pl.cdiv(L, page)                          # only touch live pages

    def scoped(k_buf, v_buf, ksem, vsem):
        def k_dma(slot, p):
            pidx = table_ref[i * max_pages + p]
            return pltpu.make_async_copy(k_hbm.at[pidx], k_buf.at[slot],
                                         ksem.at[slot])

        def v_dma(slot, p):
            pidx = table_ref[i * max_pages + p]
            return pltpu.make_async_copy(v_hbm.at[pidx], v_buf.at[slot],
                                         vsem.at[slot])

        k_dma(0, 0).start()
        v_dma(0, 0).start()

        def body(p, carry):
            m, l, acc = carry
            slot = jax.lax.rem(p, 2)
            nxt = jax.lax.rem(p + 1, 2)

            @pl.when(p + 1 < npg)
            def _():
                k_dma(nxt, p + 1).start()
                v_dma(nxt, p + 1).start()

            k_dma(slot, p).wait()
            v_dma(slot, p).wait()
            k_blk = k_buf[slot].astype(jnp.float32)   # (hk, page, d)
            v_blk = v_buf[slot].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_blk, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            cols = p * page + jax.lax.broadcasted_iota(
                jnp.int32, (hk, g, page), 2)
            s = jnp.where(cols < L, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(pr, axis=-1)
            acc_new = acc * alpha[..., None] + jax.lax.dot_general(
                pr, v_blk, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((hk, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((hk, g), jnp.float32)
        acc0 = jnp.zeros((hk, g, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, npg, body, (m0, l0, acc0))
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc / l_safe[..., None]).astype(o_ref.dtype)

    pl.run_scoped(
        scoped,
        k_buf=pltpu.VMEM((2, hk, page, d), k_hbm.dtype),
        v_buf=pltpu.VMEM((2, hk, page, d), v_hbm.dtype),
        ksem=pltpu.SemaphoreType.DMA((2,)),
        vsem=pltpu.SemaphoreType.DMA((2,)),
    )


def paged_attention_fused(q, k_pages, v_pages, page_table, cache_len,
                          softmax_scale=None):
    """One decode step of paged attention through the Pallas kernel.

    q: ``(b, h, d)``; k/v_pages: ``(P, hk / r, page, r * d)``; page_table:
    ``(b, max_pages)``; cache_len: ``(b,)``. Interpret mode off-TPU."""
    b, h, d = q.shape
    hk, ps = k_pages.shape[1], k_pages.shape[2]
    r = k_pages.shape[3] // d
    if h % (hk * r) != 0:
        raise AssertionError(f"query heads {h} must be a multiple of kv "
                             f"heads {hk * r}")
    g = h // hk
    mp = page_table.shape[1]
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / float(np.sqrt(d)))
    q4 = pack_queries(q, r, hk).reshape(b, hk, g, r * d)
    lens = cache_len.astype(jnp.int32)
    table = page_table.astype(jnp.int32).reshape(-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hk, g, r * d), lambda i, lens_ref, table_ref:
                         (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hk, g, r * d), lambda i, lens_ref, table_ref:
                               (i, 0, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=ps, max_pages=mp,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, r * d), q.dtype),
        name="paged_decode",
        interpret=_interpret(),
    )(lens, table, q4, k_pages, v_pages)
    return unpack_outputs(out.reshape(b, h, r * d), r, hk)


def paged_attention(q, k_pages, v_pages, page_table, cache_len, cap: int,
                    softmax_scale=None):
    """Dispatch: fused kernel on TPU (or under ``DS_TPU_PAGED_FORCE_FUSED=1``
    interpret mode), XLA dense-gather ground truth otherwise. The XLA path is
    the default on CPU hosts — it is bit-identical to a contiguous cache,
    which is what the serving parity tests gate on; the kernel carries its
    own numerical parity test."""
    d = q.shape[-1]
    if fused_paged_for(d):
        return paged_attention_fused(q, k_pages, v_pages, page_table,
                                     cache_len, softmax_scale)
    return paged_attention_xla(q, k_pages, v_pages, page_table, cache_len,
                               cap, softmax_scale)
