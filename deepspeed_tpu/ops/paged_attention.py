"""The KV page layout's one home: the row rule, pages -> rows, rows -> pages.

The served KV store is one global pool of fixed-size pages per layer,
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
dense rows -> whole pages (:func:`write_dense_pages`) and a slot's view rows ->
in-place page slabs (:func:`write_view_rows`). The pool's movers
and the serve programs call these; all but the first two take a row's head and
lane extents from their operands, whatever ``r`` made them.

No attention runs here. Every serve program attends over the dense view: the
gathered view is element-identical to the contiguous cache ``engine.generate``
decodes over, and both run the SAME attention op of ``ops/attention/decode.py``
on it, so greedy decode through it is **bit-identical** to it — the property
every serving parity test leans on. What that rests on, by op: the Mosaic
kernel and XLA's live-rows form (``decode_attention_live``: ALiBi, heads of
64) both walk the rows in blocks under an online softmax, and a sequence's
output is bit-equal whatever the number of blocks walked and whatever the
cap, for one block size (a block past a sequence's length adds exact zeros),
so the two sides need equal BLOCKS, which equal caps give them
(``live_block(T)``; the view is sliced to exactly ``cap`` rows, and
``generate``'s cache has the engine's ``max_out_tokens`` = ``cap``), not
equal trip counts or batches; the whole-cap forms left (a hit's suffix
prefill, the verify round: ``_prefix_attention_xla``) still rest on equal
reduction SHAPES, ``[:cap]``. No kernel gathers
K/V by page index inside its grid: on the chip one that did (a page a DMA)
took 1.07-2.6x the view's time a step (``PERF.md`` section 6, PR 27; section
7 says what one worth having needs).
"""

import jax
import jax.numpy as jnp


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


def latent_row_lanes(width: int) -> int:
    """Lanes of a LATENT layer's cache row (``models/causal_lm.LAYER_KINDS``:
    ``keeps == "latent"``): one row a token for ALL heads, ``[latent | shared
    rotary key]`` = ``width`` lanes as published (576 for a rank of 512 and 64
    rotary lanes), rounded up to whole 128-lane tiles with zero lanes (640:
    4.5 tiles would leave every row's last tile half filled and every page
    slab ragged). Such a layer's pages are ``{"k": (P, 1, page, lanes)}``: one
    "head", no ``v`` array (the values are expanded from the row's latent
    lanes), dense view and contiguous cache ``(b, 1, T, lanes)``. Everything
    below takes a row's extents from its operands, so the pages are gathered
    (:func:`gather_pages_dense`) and written (:func:`write_dense_pages`,
    :func:`write_view_rows`) as every other row is."""
    return -(-int(width) // 128) * 128


def kv_rows(x, r: int):
    """A projection's keys or values ``(b, t, h_kv, d)`` (after the per-head
    norm and the rotation) as head-major cache rows ``(b, h_kv / r, t, r *
    d)``: a row's ``r`` heads are adjacent in memory already."""
    b, t, hk, d = x.shape
    if r > 1:
        x = x.reshape(b, t, hk // r, r * d)
    return x.transpose(0, 2, 1, 3)


# ------------------------------------------------------------- dense gather
def gather_pages_dense(arrays, page_table, cap: int) -> list:
    """Reassemble the dense head-major cache view from pages, for every array
    a layer keeps in them (keys and values; a latent layer's one array of
    rows).

    ``arrays``: each ``(P, hk, page, d)`` (``hk`` rows of ``d`` lanes, here
    and below: :func:`heads_per_row`); ``page_table``: ``(b, max_pages)``
    int32. Returns a ``(b, hk, cap, d)`` each — rows sliced to EXACTLY ``cap``
    so downstream attention math (block sizes and, for the whole-cap forms,
    reduction shapes) is identical to a contiguous ``cap``-row cache's,
    keeping greedy bit-exact even when ``cap`` is not a page multiple (pages
    round it up internally). All gathers first, then the re-laying, then the
    slices: the order every key/value program has been lowered in."""
    got = [a[page_table] for a in arrays]          # (b, mp, hk, page, d)
    b, mp, hk, ps, d = got[0].shape
    dense = [p.transpose(0, 2, 1, 3, 4).reshape(b, hk, mp * ps, d) for p in got]
    return [x[:, :, :cap, :] for x in dense]


def gather_kv_dense(k_pages, v_pages, page_table, cap: int):
    """:func:`gather_pages_dense` of a layer's keys and values."""
    return tuple(gather_pages_dense((k_pages, v_pages), page_table, cap))


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
    returns), zero-padded to ``n`` whole pages. Every array the layer keeps
    in pages is written (a latent layer has ``"k"`` alone)."""
    n, ps = tbl.shape[0], pages["k"].shape[2]
    blocks = {}
    for key in pages:
        x = dense[key][0] if dense[key].ndim == 4 else dense[key]
        hk, R, d = x.shape
        blocks[key] = jnp.pad(x, ((0, 0), (0, n * ps - R), (0, 0))) \
            .reshape(hk, n, ps, d)
    return {key: pages[key].at[tbl].set(
        blocks[key].transpose(1, 0, 2, 3).astype(pages[key].dtype))
        for key in pages}


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
