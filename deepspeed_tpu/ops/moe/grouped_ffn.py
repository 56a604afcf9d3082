"""Grouped expert feed-forward: every touched expert's weights read once.

The expert layer lays the rows of its (token, expert) assignments out in
expert order, in tiles of ``tm`` rows, each tile holding rows of ONE expert
(:func:`dispatch_plan`; an expert's group is padded up to whole tiles). The
kernel walks the tiles; the expert of a tile is scalar-prefetched and picks
the weight block in the ``BlockSpec`` index maps, so consecutive tiles of one
expert reuse the block already in VMEM and an expert nobody chose is never
fetched. Per tile: ``act(x W1_e) W2_e``, or with a gate matrix the gated form
``(act(x Wg_e) * (x W1_e)) W2_e`` (the same kernel with one weight block more).
Tiles beyond the last real one (the grid is sized for the worst case) point at
the last real tile's expert, rows and output block — no fetch — and write
nothing: a layer call costs what is held here, not what the shapes allow.

``dispatch_plan`` also says where each assignment's row went, so the caller
gathers its ``k`` rows back per token and weights them: no scatter-add.
Prefill and decode use the same plan and the same kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.device import pallas_interpret as _interpret

# 2 x (W1 + W2, and the gate's where there is one) blocks of an expert in
# flight plus the tile's activations (``tile_bytes``)
VMEM_LIMIT_BYTES = 64 * 2 ** 20

# the most assignments (T * k) whose plan the dense comparisons count faster
# than the prefix sums: see ``plan_by_prefix_sums``
DENSE_PLAN_UP_TO = 4096


def tile_bytes(tm: int, l: int, f: int, itemsize: int) -> int:
    """VMEM a tile's activations take beside the weight blocks: its ``(tm,
    l)`` rows, its float32 output block and the result of the width block
    before handed in, each double-buffered, and the ``(tm, f)`` float32
    products of the gate and the up matrix with the cast the down product
    reads."""
    return tm * (2 * l * (itemsize + 4 + 4) + f * (4 + 4 + itemsize))


def width_blocks(l: int, f: int, mats: int, itemsize: int, tm: int) -> int:
    """Into how many blocks of its width ``f`` an expert is cut so that two
    experts' weight blocks in flight and the activations of a tile of ``tm``
    rows (:func:`tile_bytes`) fit ``VMEM_LIMIT_BYTES``: 1 for every size the
    cells had before sarvam (the largest, LFM2's gated 2048 x 1792: 42 MiB of
    blocks); 2 for sarvam's gated 4096 x 2048 (three blocks of 16 MiB: 96 MiB
    in flight whole, 48 halved) at every height :func:`tile_rows` gives: a
    128-row tile's activations are 11.25 MiB beside them, and the chip's
    compiler takes that; 256 rows (22.5 MiB) it refuses beside two blocks,
    so they would take four. Two blocks at 128 rows is also what the chip
    read fastest for the whole layer call at T 4096: 6.34 ms against 6.89 at
    128 rows x four blocks and 6.86 at 256 x four (each block more is one
    more pass over the live rows; PR 60). An expert splits
    exactly over its width, ``sum_b (act(x Wg_b) * (x W1_b)) W2_b``, so the
    kernel runs once a block on that block of every matrix (picked in the
    index maps: no copy) and the results are added."""
    n = 1
    while 2 * mats * l * (f // n) * itemsize + tile_bytes(tm, l, f // n, itemsize) \
            > VMEM_LIMIT_BYTES and f % (2 * n) == 0 and (f // (2 * n)) % 128 == 0:
        n *= 2
    return n


# rows an expert is expected to get (A / E) from which a tile is 128 rows high
TALL_TILES_FROM = 128


def tile_rows(assignments: int, experts: int) -> int:
    """Rows a tile, from the static ``A = T * k`` and the router's width
    ``E``: 16 (one bf16 sublane tile) while the step is bound by the weights
    it reads (up to 2,048 assignments: every decode step), 32 once a prompt
    brings tens of rows an expert, 128 (the height of the MXU's weight tile)
    once an expert is expected a whole such tile, ``A / E >=
    TALL_TILES_FROM``: the kernel's time goes by the tiles it walks (a
    128 x 128 weight tile is loaded to stream the tile's rows through it),
    the gathers' by the rows laid out, half a tile an expert of them padding.

    ONE layer call on a v5e (plan, ``x[row_token]``, the kernel calls,
    ``take`` and the sum; the kernel alone in brackets), ms by height, 16 of
    128 experts of 4096 x 2048 gated held, top 8 (sarvam; two width blocks):
    A / E 64: 32 rows 2.71 (2.17) | 64 rows 2.47 (1.42) | 128 rows 2.50
    (1.40) | 256 rows 3.30; A / E 128: 4.24 (2.21) | 3.82 (1.79) | 3.68
    (1.63) | 4.28; A / E 256: 7.37 (3.36) | 6.47 (2.46) | 6.34 (2.32) | 6.86
    (256 rows take four width blocks). 36 of 72 experts of 4096 x 768 gated
    held, top 10 (granite-small; one block, half of all rows held): A / E
    71: 2.09 (1.30) | 2.12 | 2.06 (1.16) | 2.44; A / E 142: 3.66 (1.57) |
    3.72 | 3.82 (1.69) | 3.54; A / E 284: 6.21 (2.16) | 6.18 | 6.32 (2.21) |
    6.65. So the wide expert gains 8 to 14 % of the layer from 64 rows an
    expert up, and the narrow one, whose 32-row tiles already run at half
    the MXU's rate, loses 2 to 4 % to the padding (8,704 rows laid at 142
    rows an expert where 32-row tiles lay 5,664): the height follows ``A /
    E`` alone, from a whole tile's worth of rows (PR 60, call 245). Every
    height gives a row the same bits."""
    if assignments <= 2048:
        return 16
    return 32 if assignments < TALL_TILES_FROM * experts else 128


def plan_rows(assignments: int, count: int, experts: int) -> int:
    """Rows :func:`dispatch_plan` lays out for ``assignments`` = T * k over
    ``count`` held experts of the router's ``experts`` at :func:`tile_rows`:
    the worst case, every assignment held and each expert's last tile part
    filled. A static shape; what is live of it the plan says
    (``tile_valid``)."""
    tm = tile_rows(assignments, experts)
    return (assignments // tm + min(count, assignments)) * tm


def plan_by_prefix_sums(assignments: int) -> bool:
    """Which way :func:`dispatch_plan` counts. The dense comparisons cost
    ``A x A`` and ``R x A`` operations, the prefix sums ``A x count`` and one
    scatter of ``A`` numbers whose cost hardly falls with ``A``. One plan on
    a v5e, ms, dense | prefix sums (PR 58, 16 to 128 held experts; the held
    count moved neither column past the other): A 704 0.018 | 0.020, 1,408
    0.022 | 0.026, 2,048 0.022 | 0.052, 4,096 0.052-0.057 | 0.064-0.093,
    5,632 0.149 | 0.074, 8,192 0.20-0.25 | 0.09-0.12, 11,264 0.413 | 0.144,
    16,384 0.692 | 0.124, 32,768 2.60-2.80 | 0.21-0.36. So a decode step
    (A 128 to 2,048) and a short prompt count densely, with no sort and no
    scatter; a prompt of more than ``DENSE_PLAN_UP_TO`` assignments by
    prefix sums."""
    return assignments > DENSE_PLAN_UP_TO


def dispatch_plan(idx, first: int, count: int, tm: int, valid=None):
    """Lay the assignments that fall on experts ``[first, first + count)``
    out in tiles of ``tm`` rows, one expert a tile.

    ``idx`` (T, k) int32: the experts each token chose, over ALL experts;
    ``valid`` (T,) bool: tokens that count (padding does not). Returns a dict:
    ``row_token`` (R,) the token that feeds each row (0 where the row is
    padding); ``pos`` (T, k) the row of each assignment, R where it is not
    held here; ``held`` (T, k) bool; ``tile_expert`` / ``tile_valid`` (NT,)
    int32; ``n_assigned`` and ``n_touched`` (scalars): assignments on held
    experts and distinct held experts with at least one.

    One plan, two ways to count it, chosen by the static ``A = T * k``
    (:func:`plan_by_prefix_sums`): an assignment's rank among its expert's and
    the token of a row are dense comparisons at a decode step's sizes and
    prefix sums with one scatter at a long prompt's."""
    T, k = idx.shape
    A = T * k
    NT = A // tm + min(count, A)
    R = NT * tm
    local = idx - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    key = jnp.where(held, local, count).reshape(A).astype(jnp.int32)
    a = jnp.arange(A, dtype=jnp.int32)
    experts = jnp.arange(count, dtype=jnp.int32)
    on = key[:, None] == experts[None, :]                             # (A, count)
    prefix = plan_by_prefix_sums(A)
    if prefix:
        # the running count of each expert's assignments, read at the
        # assignment's own column
        running = jnp.cumsum(on, axis=0, dtype=jnp.int32)
        here = running[-1]
        rank = jnp.sum(jnp.where(on, running, 0), axis=1) - 1
    else:
        # No sort and no scatter: both are slow on the chip beside a few dense
        # comparisons of these sizes (A x A, A x count, R x A), which fuse.
        here = jnp.sum(on, axis=0, dtype=jnp.int32)
        # rank of an assignment among the earlier ones of its expert
        rank = jnp.sum((key[:, None] == key[None, :]) & (a[None, :] < a[:, None]),
                       axis=1, dtype=jnp.int32)
    padded = (here + tm - 1) // tm * tm
    pad_end = jnp.sum(jnp.where(experts[None, :] <= experts[:, None],
                                padded[None, :], 0), axis=1)          # cumsum
    pad_start = pad_end - padded
    dest = jnp.where(key < count,
                     jnp.sum(jnp.where(on, pad_start[None, :], 0), axis=1) + rank,
                     R).astype(jnp.int32)
    if prefix:
        # the inverse of ``dest``: held assignments have a row each, the others
        # (R: past the end) are dropped
        row_token = jnp.zeros((R,), jnp.int32).at[dest].set(
            a // k, mode="drop", unique_indices=True)
    else:
        rows = jnp.arange(R, dtype=jnp.int32)
        row_token = jnp.sum(jnp.where(dest[None, :] == rows[:, None],
                                      (a // k)[None, :], 0), axis=1, dtype=jnp.int32)
    pos = dest.reshape(T, k)
    n_tiles = pad_end[-1] // tm
    tiles = jnp.arange(NT, dtype=jnp.int32)
    tile_valid = (tiles < n_tiles).astype(jnp.int32)
    # the expert whose padded range holds the tile's first row; tiles beyond
    # the last real one repeat its expert
    te = jnp.sum(pad_end[None, :] <= (jnp.minimum(tiles, n_tiles - 1) * tm)[:, None],
                 axis=1, dtype=jnp.int32)
    tile_expert = jnp.clip(te, 0, count - 1)
    return {"row_token": row_token, "pos": pos, "held": held,
            "tile_expert": tile_expert, "tile_valid": tile_valid,
            "n_assigned": jnp.sum(here), "n_touched": jnp.sum(here > 0)}


def grouped_ffn_xla(x_rows, tile_expert, tile_valid, w1, w2, act, tm: int,
                    w_gate=None):
    """``jax.numpy`` form: gathers a copy of each tile's expert (so it reads
    an expert once a TILE and writes the copy): what the kernel is checked
    against, not what serves."""
    NT = tile_expert.shape[0]
    xt = x_rows.reshape(NT, tm, -1)
    h = jnp.einsum("ntl,nlf->ntf", xt, w1[tile_expert],
                   preferred_element_type=jnp.float32)
    if w_gate is None:
        h = act(h)
    else:
        h = act(jnp.einsum("ntl,nlf->ntf", xt, w_gate[tile_expert],
                           preferred_element_type=jnp.float32)) * h
    y = jnp.einsum("ntf,nfl->ntl", h.astype(w2.dtype), w2[tile_expert],
                   preferred_element_type=jnp.float32)
    y = jnp.where(tile_valid[:, None, None] == 1, y, 0.0)
    return y.reshape(NT * tm, -1)


def _kernel(te_ref, tv_ref, last_ref, x_ref, *refs, act, gated: bool):
    """``refs``: the expert's weight blocks ``[gate,] w1, w2``, then the
    result of the width blocks before this one where there is one (added to
    this block's product), and the output. A tile past the last real one
    writes nothing: its blocks are the last real tile's, still in VMEM."""
    *gate, w1_ref, w2_ref = refs[:2 + gated]
    *before, o_ref = refs[2 + gated:]
    i = pl.program_id(0)

    @pl.when(tv_ref[i] == 1)
    def _():
        h = jnp.dot(x_ref[...], w1_ref[...], preferred_element_type=jnp.float32)
        if gate:
            h = act(jnp.dot(x_ref[...], gate[0][...],
                            preferred_element_type=jnp.float32)) * h
        else:
            h = act(h)
        y = jnp.dot(h.astype(w2_ref.dtype), w2_ref[...],
                    preferred_element_type=jnp.float32)
        o_ref[...] = before[0][...] + y if before else y


def grouped_ffn(x_rows, tile_expert, tile_valid, w1, w2, act, tm: int,
                w_gate=None):
    """``x_rows`` (NT * tm, l) rows in the plan's order; ``w1`` (e, l, f),
    ``w2`` (e, f, l) the held experts, ``w_gate`` (e, l, f) their gates where
    the expert is gated (``(act(x Wg) * (x W1)) W2``; None: ``act(x W1) W2``).
    Returns (NT * tm, l) float32; rows of padding hold whatever token 0 gives
    and rows of the tiles past the last real one (``tile_valid`` 0: the plan
    puts every real tile first) whatever the memory held: neither is ever
    gathered back, and a tile that is not real costs no fetch and no write
    (its ``x_rows`` and output blocks are the last real tile's, like its
    expert). The kernel's name in a trace is ``moe_grouped_ffn`` in both
    forms. Experts too wide for the kernel's VMEM are cut over their width
    (:func:`width_blocks`): one kernel call a block, each adding its product
    to the result of the one before, which it is handed as an aliased operand
    (no ``(R, l)`` sum outside the kernel)."""
    R, l = x_rows.shape
    f = w1.shape[2]
    NT = R // tm
    if not _interpret() and (l % 128 or f % 128):
        return grouped_ffn_xla(x_rows, tile_expert, tile_valid, w1, w2, act, tm,
                               w_gate)
    gate = [] if w_gate is None else [w_gate]
    n = width_blocks(l, f, 2 + len(gate), w1.dtype.itemsize, tm)
    fb = f // n
    # the last real tile (0 where no assignment is held: nothing is written)
    last = jnp.maximum(jnp.sum(tile_valid, dtype=jnp.int32) - 1, 0).reshape(1)

    def rows(i, te, tv, last):
        return (jnp.minimum(i, last[0]), 0)

    def call(b, *before):
        up = pl.BlockSpec((None, l, fb), lambda i, te, tv, last: (te[i], 0, b))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(NT,),
            in_specs=[pl.BlockSpec((tm, l), rows)]
            + [up] * (len(gate) + 1)
            + [pl.BlockSpec((None, fb, l), lambda i, te, tv, last: (te[i], b, 0))]
            + [pl.BlockSpec((tm, l), rows)] * len(before),
            out_specs=pl.BlockSpec((tm, l), rows),
        )
        operands = (tile_expert, tile_valid, last, x_rows, *gate, w1, w2, *before)
        return pl.pallas_call(
            functools.partial(_kernel, act=act, gated=bool(gate)),
            out_shape=jax.ShapeDtypeStruct((R, l), jnp.float32),
            grid_spec=grid_spec,
            input_output_aliases={len(operands) - 1: 0} if before else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name="moe_grouped_ffn",
            interpret=_interpret(),
        )(*operands)

    out = call(0)
    for b in range(1, n):
        out = call(b, out)
    return out


def grouped_experts(x, idx, w, first: int, count: int, experts: int, w1, w2,
                    act, valid=None, w_gate=None):
    """What an expert layer that holds experts ``[first, first + count)`` of
    its router's ``experts`` adds for tokens ``x`` (T, l): plan, ONE kernel
    call, and each token's ``k`` rows gathered back and weighted (``idx``,
    ``w`` (T, k): the experts each token chose over ALL experts, and their
    weights; the tiles' height follows ``idx.size / experts``, the rows an
    expert is expected: :func:`tile_rows`). Assignments that
    fall on experts held elsewhere add nothing. Returns ``(T, l)`` float32 and
    ``(assignments on held experts, distinct held experts touched)``."""
    from ...observability import scope   # here: the kernel's lines above stay put
    tm = tile_rows(idx.size, experts)
    with scope("moe.plan"):
        plan = dispatch_plan(idx, first, count, tm, valid)
    with scope("moe.rows"):
        x_rows = x[plan["row_token"]]
    with scope("moe.experts"):
        rows = grouped_ffn(x_rows, plan["tile_expert"], plan["tile_valid"], w1, w2,
                           act, tm, w_gate)                           # (R, l) f32
    with scope("moe.rows"):
        mine = jnp.take(rows, plan["pos"], axis=0, mode="fill", fill_value=0.0)
        out = jnp.sum(jnp.where(plan["held"], w, 0.0)[..., None] * mine, axis=1)
    with scope("moe.plan"):
        stats = jnp.stack([plan["n_assigned"], plan["n_touched"]]).astype(jnp.int32)
    return out, stats
