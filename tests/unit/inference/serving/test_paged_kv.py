"""Paged KV memory tests: page allocator (alloc/free/exhaustion/refusal),
refcount lifecycle + copy-on-write boundary page, the page layout's three
mappings against a numpy reference, the gathered view against the contiguous
cache through the decode math, one decode chunk for every pool,
hit/miss/retry/drain/migration bit-exactness, the page-bind chaos seam
(``when=restore`` extended to the bind path), the slab serialization API
roundtrip, and the front-door ``--kv-page-size`` validation.

Every parity assertion is exact token equality: the pool's XLA decode path
reassembles the exact contiguous cache ``engine.generate`` decodes over
(sliced to ``cap`` rows), so greedy decode is bit-identical to it — hit or
miss, killed or not, migrated or not.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (ChaosEvent, ChaosSchedule,
                                             ContinuousBatchingScheduler,
                                             PagedKVPool, PrefixCacheConfig,
                                             Router, RouterConfig,
                                             ServingConfig)
from deepspeed_tpu.models.causal_lm import gpt2_cfg
from deepspeed_tpu.ops.paged_attention import (gather_kv_dense,
                                               pages_to_dense,
                                               write_dense_pages,
                                               write_view_rows)
from deepspeed_tpu.ops.attention.decode import decode_attention_xla

pytestmark = pytest.mark.paged_kv

TINY = dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)
CAP = 48
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(
        gpt2_cfg(**TINY),
        ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                              max_out_tokens=CAP))


@pytest.fixture(scope="module")
def engines(engine):
    e1 = InferenceEngine(
        gpt2_cfg(**TINY),
        ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                              max_out_tokens=CAP),
        params=engine.params)
    return [engine, e1]


def _cache_cfg(**over):
    kw = dict(min_hit_tokens=4, min_insert_tokens=4, insert_on="prefill")
    kw.update(over)
    return PrefixCacheConfig(**kw)


def _sched(engine, cache=False, page_size=8, **over):
    kw = dict(slots=2, chunk_size=3, max_seq_len=CAP, retry_base_delay=0.001,
              kv_page_size=page_size,
              prefix_cache=(_cache_cfg() if cache is True
                            else (cache or None)))
    kw.update(over)
    return ContinuousBatchingScheduler(engine, ServingConfig(**kw))


def _ref(engine, prompt, max_new):
    out = np.asarray(engine.generate(prompt[None, :], max_new_tokens=max_new))
    return out[0, prompt.size:]


# ------------------------------------------------------------- allocator unit
def test_allocator_lifecycle():
    cfg = gpt2_cfg(**TINY)
    pool = PagedKVPool(cfg, slots=3, cap=32, page_size=8, dtype=jnp.float32)
    assert pool.max_pages == 4 and pool.total_pages == 13    # 3*4 + null
    # page-granular reservation: an 11-token request takes 2 pages, not 4
    s0 = pool.acquire(tokens=11)
    assert pool.pages_in_use == 2 and pool.free_slots == 2
    assert all(p != 0 for p in pool.page_table[s0, :2])
    assert all(p == 0 for p in pool.page_table[s0, 2:])
    # exhaustion: pages, not slots, are the binding constraint
    s1 = pool.acquire(tokens=32)          # 4 pages
    s2 = pool.acquire(tokens=32)          # 4 pages -> 10/12 used
    assert s1 is not None and s2 is not None
    assert pool.free_slots == 0
    assert pool.acquire(tokens=8) is None          # no slot left
    pool.release(s1)
    assert pool.free_slots == 1 and pool.pages_in_use == 6
    assert not pool.can_admit(60)                  # over per-slot cap class
    with pytest.raises(ValueError):
        pool.acquire(tokens=60)                    # exceeds cap: refused loud
    # refusal when pages are exhausted even though a slot is free
    s3 = pool.acquire(tokens=32)
    s4 = pool.acquire(tokens=32)
    assert s3 is not None and s4 is None           # 2+4+4 used, 2 free < 4
    pool.release(s0)                               # slot free, 4 pages free
    assert pool.can_admit(32) and not pool.can_admit(33)
    with pytest.raises(ValueError):
        pool.release(s0)                           # double free raises
    # construction validation
    with pytest.raises(ValueError):
        PagedKVPool(cfg, slots=1, cap=32, page_size=8, total_pages=3)


def test_released_pages_recycle():
    cfg = gpt2_cfg(**TINY)
    pool = PagedKVPool(cfg, slots=2, cap=16, page_size=8, dtype=jnp.float32)
    a = pool.acquire(tokens=16)
    pages_a = set(pool.page_table[a, :2])
    pool.release(a)
    assert pool.pages_in_use == 0
    # FIFO free list: the next two acquisitions drain fresh pages first, then
    # recycle a's freed pages; between them every usable page is handed out
    b = pool.acquire(tokens=16)
    c = pool.acquire(tokens=16)
    handed = set(pool.page_table[b, :2]) | set(pool.page_table[c, :2])
    assert pages_a <= handed and len(handed) == 4
    assert pool.acquire(tokens=8) is None          # fully allocated again


# -------------------------------------------------- refcounts + copy-on-write
def test_refcount_lifecycle_and_cow_boundary():
    cfg = gpt2_cfg(**TINY)
    pool = PagedKVPool(cfg, slots=3, cap=32, page_size=8, dtype=jnp.float32)
    donor = pool.acquire(tokens=24)                # 3 pages
    # stamp recognizable values into the donor's pages
    stamped = [{"k": c["k"].at[pool.page_table[donor, 0]].set(7.0),
                "v": c["v"].at[pool.page_table[donor, 0]].set(-7.0)}
               for c in pool.caches]
    pool.caches = stamped
    # share the first 20 prompt tokens -> 3 pages (boundary page included)
    shared = pool.share_prefix(donor, 20)
    assert len(shared) == 3
    assert all(pool._ref[int(p)] == 2 for p in shared)
    pool.release(donor)                            # donor gone, pages survive
    assert pool.pages_in_use == 3
    assert all(pool._ref[int(p)] == 1 for p in shared)
    # a hit matching 20 tokens: 2 full pages bind shared, page 3 is COW'd
    reader = pool.acquire(tokens=26, prefix_pages=shared, matched=20)
    assert reader is not None
    assert pool.cow_copies_total == 1
    row = pool.page_table[reader]
    assert row[0] == shared[0] and row[1] == shared[1]
    assert row[2] != shared[2]                     # private copy
    assert pool._ref[int(shared[0])] == 2          # bound + cache ref
    assert pool._ref[int(shared[2])] == 1          # cache ref only
    # COW copied the boundary page's CONTENT
    src = np.asarray(pool.caches[0]["k"][int(shared[2])])
    dst = np.asarray(pool.caches[0]["k"][int(row[2])])
    np.testing.assert_array_equal(src, dst)
    assert pool.shared_pages == 2
    # eviction is a refcount drop: bound pages survive until the slot releases
    pool.release_shared(shared)
    assert pool._ref[int(shared[0])] == 1          # still bound by reader
    assert pool._ref[int(shared[2])] == 0          # free again
    pool.release(reader)
    assert pool.pages_in_use == 0
    with pytest.raises(AssertionError):
        pool._decref(int(shared[0]))               # underflow is loud


def test_clear_releases_cached_pages(engine):
    """``PrefixCache.clear()`` against a still-live pool (the idle-replica
    revive path: no rebuild happens) must decref every cached prefix's pages
    back to the free list — without it each revive leaked the whole cached
    working set and the pool eventually refused all admission."""
    rng = np.random.default_rng(23)
    p = rng.integers(0, 96, size=16).astype(np.int32)
    sched = _sched(engine, cache=True)
    h = sched.submit(p, max_new_tokens=4)
    sched.run()
    assert h.state.value == "finished"
    pool = sched.executor.pool
    assert pool.pages_in_use > 0           # cache entries pin real pages
    sched.prefix_cache.clear()             # idle revive: live pool, no rebuild
    assert pool.pages_in_use == 0
    assert pool.can_admit(CAP)


# ------------------------------------------------------------- page layout
def _np_write_rows(pages, view, table, start, count, cap):
    """Row by row: row ``r`` of slot ``s``'s view goes to ``(table[s, r //
    ps], r % ps)`` for ``start[s] <= r < start[s] + count[s]``, ``r < cap``."""
    out, ps = pages.copy(), pages.shape[2]
    for s in range(table.shape[0]):
        for r in range(start[s], start[s] + count[s]):
            if r < cap:
                out[table[s, r // ps], :, r % ps, :] = view[s, :, r, :]
    return out


_TABLE = np.array([[5, 2, 7], [1, 8, 3], [4, 6, 9]], np.int32)
# name: (page table, cap, start, count, span); pages of 4 rows, 10 in the pool
_WRITES = {
    # rows 3..6 and 6..9 cross from one page into the next
    "span_straddles_a_page": (_TABLE, 12, [3, 6, 0], [4, 4, 4], 4),
    # the chunk's form: slot 0 stopped after one step, slot 2 took none
    "slot_stopped_mid_chunk": (_TABLE, 12, [2, 5, 9], [1, 4, 0], 4),
    "inactive_slots": (_TABLE, 12, [0, 7, 11], [0, 0, 0], 4),
    # slot 0 starts AT the cap, slot 1 past it, slot 2 runs over it
    "start_at_and_past_the_cap": (_TABLE, 12, [12, 15, 10], [2, 2, 4], 4),
    # cap 10 ends inside page 2: rows 10 and 11 of it are never written,
    # and the view's 10 rows end inside the page's slab
    "cap_ends_inside_a_page": (_TABLE, 10, [8, 9, 6], [4, 1, 3], 4),
    # the suffix prefill's form: one slot's table, a bucket of 8 rows from 6
    "one_slot_a_vector_of_rows": (_TABLE[1:2], 10, [6], [8], 8),
    # the verify round's: active & (j < valid) as where(active, valid, 0)
    "verify_round_mask": (_TABLE, 12, [3, 8, 5], [3, 0, 1], 5),
    # a span of more pages than the table has: every page of the slot
    "span_wider_than_the_table": (_TABLE[:1], 12, [1], [11], 16),
    # page 5 is slot 0's and slot 1's first (a shared prefix): slot 0 is
    # inactive INSIDE it, slot 1 appends behind it; 0 is the null page
    "shared_page_left_bit_equal": (
        np.array([[5, 2, 0], [5, 8, 3]], np.int32), 12, [2, 4], [0, 3], 4),
}


@pytest.mark.parametrize("case", sorted(_WRITES) + ["two_heads_a_row"])
def test_view_rows_go_into_their_pages_and_nothing_else_changes(case):
    """``write_view_rows`` against the row-by-row loop, bit for bit, in the
    forms its four callers give it (the chunk's and the block chunk's ``[lens_in,
    lens)``, the verify round's valid rows of active slots, the suffix
    prefill's one slot): every row the loop writes, and no other element of
    any page, shared, null or unnamed. ``two_heads_a_row``: the view's rows
    hold two KV heads of 64 side by side (``kv_rows(x, 2)``) and each lands
    in its half of the page's 128 lanes."""
    from deepspeed_tpu.ops.paged_attention import kv_rows
    ps, total = 4, 10
    rng = np.random.default_rng(len(case))
    if case == "two_heads_a_row":
        table, cap, start, count, span = _WRITES["span_straddles_a_page"]
        x = rng.normal(size=(3, cap, 4, 64)).astype(np.float32)   # (S, t, hk, d)
        view = np.asarray(kv_rows(jnp.asarray(x), 2))
        assert view.shape == (3, 2, cap, 128)
    else:
        table, cap, start, count, span = _WRITES[case]
        view = rng.normal(size=(table.shape[0], 2, cap, 3)).astype(np.float32)
    start, count = np.asarray(start, np.int32), np.asarray(count, np.int32)
    before = {key: rng.normal(size=(total,) + view.shape[1:2] + (ps,)
                              + view.shape[3:]).astype(np.float32)
              for key in ("k", "v")}
    views = {"k": view, "v": -view}
    after = jax.jit(write_view_rows, static_argnums=(5, 6))(
        [before], [views], jnp.asarray(table), jnp.asarray(start),
        jnp.asarray(count), span, cap)[0]
    for key in ("k", "v"):
        want = _np_write_rows(before[key], views[key], table, start, count, cap)
        np.testing.assert_array_equal(np.asarray(after[key]), want)
    written = np.asarray(after["k"]) != before["k"]
    if case == "inactive_slots":
        assert not written.any()
    else:
        assert written.any() and not written[0].any()       # never the null page
    if case == "shared_page_left_bit_equal":
        np.testing.assert_array_equal(np.asarray(after["k"])[5], before["k"][5])
    if case == "two_heads_a_row":
        s, r = 1, 7                                    # slot 1's row 7: page 8, row 3
        got = np.asarray(after["k"])[table[s, r // ps], :, r % ps, :]
        np.testing.assert_array_equal(got.reshape(4, 64), x[s, r])


@pytest.mark.parametrize("rows,batched", [(16, False), (13, True), (5, False)])
def test_dense_rows_to_pages_and_back(rows, batched):
    """``write_dense_pages`` then ``pages_to_dense``: the rows come back
    where a numpy loop puts them, the rest of the last page is zero, and no
    other page is touched (whole pages, a ragged last page, a single page;
    with and without the batch of one a prefill returns)."""
    ps, total, hk, d = 8, 7, 2, 3
    rng = np.random.default_rng(rows)
    n = -(-rows // ps)
    tbl = np.array([5, 2, 6], np.int32)[:n]
    before = {key: rng.normal(size=(total, hk, ps, d)).astype(np.float32)
              for key in ("k", "v")}
    dense = {key: rng.normal(size=(hk, rows, d)).astype(np.float32)
             for key in ("k", "v")}
    given = {key: jnp.asarray(x[None] if batched else x)
             for key, x in dense.items()}
    after = write_dense_pages({key: jnp.asarray(x) for key, x in before.items()},
                              given, jnp.asarray(tbl))
    for key in ("k", "v"):
        ref = before[key].copy()
        ref[tbl] = 0.0
        for r in range(rows):
            ref[tbl[r // ps], :, r % ps, :] = dense[key][:, r, :]
        np.testing.assert_array_equal(np.asarray(after[key]), ref)
        back = np.asarray(pages_to_dense(after[key], jnp.asarray(tbl)))
        assert back.shape == (hk, n * ps, d)
        np.testing.assert_array_equal(back[:, :rows], dense[key])
        assert not back[:, rows:].any()
        # the batched gather of the decode chunk reads the same rows
        k2, _ = gather_kv_dense(after[key], after[key],
                                jnp.asarray(tbl)[None], rows)
        np.testing.assert_array_equal(np.asarray(k2[0]), dense[key])


# --------------------------------------------- rows of several KV heads (PR 42)
def _wide(n_head, **over):
    """A GPT-2 of width 256: 4 heads x 64 (two rows of two heads) or 2 x 128."""
    return gpt2_cfg(**{**TINY, "n_embd": 256, "n_head": n_head, **over})


@pytest.mark.parametrize("n_head,r", [(4, 2), (2, 1)], ids=["d64", "d128"])
def test_rows_of_heads_go_page_to_dense_to_page_bit_for_bit(n_head, r):
    """The pool of a d 64 model holds two KV heads in every 128-lane row of
    its pages (``heads_per_row``; one at d 128): a prefill's rows scatter in,
    the chunk's batched gather and the slab API read them back as they were
    laid out, and written back they give the same pages, bit for bit."""
    from deepspeed_tpu.models.causal_lm import init_cache
    from deepspeed_tpu.ops.paged_attention import heads_per_row, kv_rows
    cfg = _wide(n_head, dtype=jnp.bfloat16)
    d, cap, ps = 256 // n_head, 40, 8
    assert heads_per_row(d, n_head) == r
    pool = PagedKVPool(cfg, slots=2, cap=cap, page_size=ps)
    assert pool.heads_per_row == r
    assert pool.caches[0]["k"].shape == (11, n_head // r, ps, r * d) == \
        (11, n_head // r, ps, 128)
    assert pool.page_nbytes == 2 * cfg.n_layer * n_head * ps * d * 2
    assert init_cache(cfg, 3, cap)[0]["k"].shape == (3, n_head // r, cap, 128)
    rng = np.random.default_rng(r)
    pool.acquire(tokens=8)
    slot = pool.acquire(tokens=cap)
    # as a prefill hands them over: the projection's (1, t, hk, d) laid out in rows
    one = [{key: kv_rows(jnp.asarray(rng.standard_normal((1, cap, n_head, d)),
                                     jnp.bfloat16), r) for key in ("k", "v")}
           for _ in range(cfg.n_layer)]
    pool.scatter_prefill(slot, one)
    before = [{key: np.asarray(c[key], np.float32) for key in c} for c in pool.caches]
    tbl = jnp.asarray(pool.page_table[slot])
    for c, o in zip(pool.caches, one):
        kd, vd = gather_kv_dense(c["k"], c["v"], tbl[None], cap)
        np.testing.assert_array_equal(np.asarray(kd, np.float32),
                                      np.asarray(o["k"], np.float32))
        np.testing.assert_array_equal(np.asarray(vd, np.float32),
                                      np.asarray(o["v"], np.float32))
        again = write_dense_pages(c, {"k": kd, "v": vd}, tbl)
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(again[key], np.float32),
                                          np.asarray(c[key], np.float32))
    # the slab API (the host tier's and the wire's format) is rows too
    slab = pool.gather_prefix(slot, 21)
    assert slab[0]["k"].shape == (n_head // r, 21, 128)
    pool.release(0)                         # both slots were taken: reuse the first
    fresh = pool.acquire(tokens=21)
    pool.restore_prefix(fresh, slab)
    for a, b in zip(slab, pool.gather_prefix(fresh, 21)):
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(a[key], np.float32),
                                          np.asarray(b[key], np.float32))
    # and nothing of the first slot's pages moved meanwhile
    for c, b4 in zip(pool.caches, before):
        pages = pool.page_table[slot][:cap // ps]
        np.testing.assert_array_equal(np.asarray(c["k"], np.float32)[pages], b4["k"][pages])


@pytest.mark.parametrize("n_head,r", [(4, 2), (2, 1)], ids=["d64", "d128"])
def test_the_pools_phase_says_how_many_heads_a_row_holds(n_head, r):
    """``setup.kv_pool`` carries ``heads_per_row`` (declared in the schema): 2
    for a d 64 model, 1 from d 128 on; and the scheduler over either pool
    serves what ``engine.generate`` gives, a prefix hit included."""
    from deepspeed_tpu.observability import schema
    from deepspeed_tpu.observability.trace import get_tracer
    assert "heads_per_row" in schema.SPANS["setup.kv_pool"][2]
    eng = InferenceEngine(_wide(n_head), ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=CAP))
    tracer = get_tracer()
    tracer.reset()
    tracer.enable()
    try:
        sched = _sched(eng, cache=True)
        spans = list(tracer.spans)
    finally:
        tracer.disable()
        tracer.reset()
    (attrs,) = [s["attrs"] for s in spans if s["name"] == "setup.kv_pool"]
    assert attrs["heads_per_row"] == sched.executor.pool.heads_per_row == r
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 96, size=16).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 96, size=n).astype(np.int32)])
               for n in (4, 7)]
    hs = [sched.submit(p, max_new_tokens=7) for p in prompts]
    sched.run()
    assert [h.prefix_hit_tokens for h in hs] == [0, 16]
    for h, p in zip(hs, prompts):
        np.testing.assert_array_equal(h.result(), _ref(eng, p, 7))


# ------------------------------------------------------- the one decode route
def test_the_gathered_view_decodes_as_the_contiguous_cache():
    """The view ``gather_kv_dense`` returns, through ``decode_attention_xla``,
    is what the contiguous cache of the same rows gives, bit for bit: tables
    in any page order, a slot that ends inside a page, one padded with the
    null page, and a cap that is not a page multiple."""
    rng = np.random.default_rng(0)
    P, hk, ps, d, b, g, cap = 9, 2, 8, 16, 3, 2, 20
    k_pages = jnp.asarray(rng.standard_normal((P, hk, ps, d)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((P, hk, ps, d)), jnp.float32)
    tables = np.asarray([[3, 1, 2], [4, 5, 0], [8, 6, 7]], np.int32)
    lens = jnp.asarray([20, 13, 17], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, hk * g, d)), jnp.float32)

    kd, vd = gather_kv_dense(k_pages, v_pages, jnp.asarray(tables), cap)
    assert kd.shape == vd.shape == (b, hk, cap, d)
    # the contiguous cache the same tokens would have filled, built in numpy
    contiguous = [np.stack([np.concatenate([np.asarray(pages)[t] for t in row],
                                           axis=1)[:, :cap]
                            for row in tables])
                  for pages in (k_pages, v_pages)]
    np.testing.assert_array_equal(np.asarray(kd), contiguous[0])
    np.testing.assert_array_equal(np.asarray(vd), contiguous[1])
    np.testing.assert_array_equal(
        np.asarray(decode_attention_xla(q, kd, vd, lens)),
        np.asarray(decode_attention_xla(q, *map(jnp.asarray, contiguous), lens)))


def test_the_model_has_no_mode_that_takes_pages(engine):
    """The model steps on dense caches only: a page table is not an argument
    it has (the mode is gone, not merely unreachable)."""
    with pytest.raises(TypeError, match="page_table"):
        engine.module.apply({"params": engine.params},
                            jnp.zeros((2, 1), jnp.int32),
                            page_table=jnp.zeros((2, 6), jnp.int32))


@pytest.mark.parametrize("pages", [None, 9])
def test_every_pool_decodes_through_the_one_chunk(engine, monkeypatch, pages):
    """A pool that holds a row for every slot's whole cap and an
    OVERSUBSCRIBED one (fewer pages than ``slots x cap`` rows) finish their
    requests token for token with ``engine.generate``, through ONE compiled
    chunk whose key names the pool's shape and nothing about a route."""
    rng = np.random.default_rng(47)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32) for n in (6, 13, 9)]
    refs = [_ref(engine, p, 7) for p in prompts]
    monkeypatch.setattr(engine, "_fns", {})
    sched = _sched(engine, kv_total_pages=pages)
    ex = sched.executor
    assert (ex.slots * ex.cap > (ex.pool.total_pages - 1) * ex.pool.page_size) \
        is (pages is not None)
    hs = [sched.submit(p, max_new_tokens=7) for p in prompts]
    sched.run()
    for h, ref in zip(hs, refs):
        assert h.state.value == "finished"
        np.testing.assert_array_equal(h.result(), ref)
    (key,) = [k for k in engine._fns if k[0] == "serve_chunk_paged"]
    assert key == ("serve_chunk_paged", ex.slots, ex.pool.total_pages,
                   ex.pool.page_size, ex.cap, ex.chunk_size, ex.sampling)


# --------------------------------------------------- end-to-end bit-exactness
def test_hit_miss_parity_and_zero_copy(engine):
    """Greedy through the paged pool == generate, miss and (zero-copy) hit;
    the hit binds pages instead of restoring a slab — asserted via the pool's
    sharing counters and the absence of any slab entry."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 96, size=16).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 96, size=s).astype(np.int32)])
               for s in (4, 6, 5)]
    sched = _sched(engine, cache=True)
    hs = [sched.submit(p, max_new_tokens=8) for p in prompts]
    sched.run()
    assert [h.prefix_hit_tokens for h in hs] == [0, 16, 16]
    for h, p in zip(hs, prompts):
        np.testing.assert_array_equal(h.result(), _ref(engine, p, 8))
    # zero-copy: entries hold page indices, never gathered slabs
    entries = list(sched.prefix_cache._lru.values())
    assert entries and all(e.slab is None and e.pages is not None
                           for e in entries)
    stats = sched.executor.pool.stats()
    assert stats["prefix_shared_pages"] >= 2
    assert sched.executor.pool.cow_copies_total == 0      # 16 % 8 == 0


def test_cow_hit_parity_unaligned_prefix(engine):
    """A hit whose match is NOT page-aligned copy-on-writes the boundary page
    and still decodes bit-exactly (the donor's page is never written)."""
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 96, size=13).astype(np.int32)   # 13 % 8 != 0
    p0 = np.concatenate([shared, rng.integers(0, 96, size=5).astype(np.int32)])
    p1 = np.concatenate([shared, rng.integers(0, 96, size=4).astype(np.int32)])
    sched = _sched(engine, cache=_cache_cfg(min_hit_tokens=8,
                                            min_insert_tokens=8))
    h0 = sched.submit(p0, max_new_tokens=6)
    sched.run()
    h1 = sched.submit(p1, max_new_tokens=6)
    sched.run()
    assert h1.prefix_hit_tokens == 13
    assert sched.executor.pool.cow_copies_total >= 1
    np.testing.assert_array_equal(h0.result(), _ref(engine, p0, 6))
    np.testing.assert_array_equal(h1.result(), _ref(engine, p1, 6))


def test_sampled_decode_parity_across_page_geometries(engine):
    """Seeded sampling: identical streams whatever the page size and the
    slot (per-slot key streams know nothing of where the rows live): pages
    of 8 alone in slot 0, pages of 4 behind another request in slot 1."""
    rng = np.random.default_rng(13)
    p = rng.integers(0, 96, size=9).astype(np.int32)
    other = rng.integers(0, 96, size=5).astype(np.int32)
    outs = []
    for page, crowded in ((8, False), (4, True)):
        sched = ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, chunk_size=3, max_seq_len=CAP, kv_page_size=page,
            do_sample=True, temperature=0.9, base_seed=5))
        if crowded:
            sched.submit(other, max_new_tokens=12, seed=3)
        h = sched.submit(p, max_new_tokens=8, seed=17)
        sched.run()
        assert h.state.value == "finished"
        outs.append(h.result())
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("page", [4, 8, 16])
def test_mixed_lengths_across_page_boundaries_match_generate(engine, page):
    """Prompts that end before, on and after a page boundary, decoded over
    one or more further boundaries through two recycled slots: every stream
    is ``engine.generate``'s, token for token."""
    rng = np.random.default_rng(page)
    sizes = (3, page, page + 1, 2 * page - 1, 30)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32) for n in sizes]
    sched = _sched(engine, page_size=page, max_queue=8)
    handles = [sched.submit(p, max_new_tokens=min(page + 2 + i, CAP - p.size))
               for i, p in enumerate(prompts)]
    sched.run()
    for p, h in zip(prompts, handles):
        assert h.state.value == "finished"
        np.testing.assert_array_equal(h.result(),
                                      _ref(engine, p, len(h.tokens)))
    assert sched.executor.pool.pages_in_use == 0


def test_the_removed_slot_pool_is_refused_at_construction():
    assert ServingConfig(kv_pool="paged").kv_pool == "paged"
    with pytest.raises(ValueError, match="slot-row pool was removed"):
        ServingConfig(kv_pool="slots")


def test_mixed_length_page_admission(engine):
    """More compiled slots than worst-case page capacity: short requests admit
    concurrently where the slot-row pool would have reserved cap each; a long
    request waits for pages, not forever — and everything stays bit-exact."""
    sched = _sched(engine, slots=4, page_size=8,
                   kv_total_pages=2 * 6 + 1,      # HBM of TWO cap-row slots
                   max_queue=8)
    rng = np.random.default_rng(17)
    shorts = [rng.integers(0, 96, size=4).astype(np.int32) for _ in range(3)]
    long = rng.integers(0, 96, size=30).astype(np.int32)
    hs = [sched.submit(p, max_new_tokens=4) for p in shorts]    # 1 page each
    hl = sched.submit(long, max_new_tokens=10)                  # 5 pages
    sched.step()
    # 3 shorts (3 pages) + the long (5 pages) = 8 <= 12: all four run at once
    # in a batch the slot-row pool at equal HBM (2 slots) could not hold
    assert sum(h.state.value == "running" or h.done for h in hs + [hl]) == 4
    sched.run()
    for h, p in zip(hs + [hl], shorts + [long]):
        assert h.state.value == "finished"
        np.testing.assert_array_equal(
            h.result(), _ref(engine, p, 4 if p.size == 4 else 10))


def test_slot_starvation_keeps_cache(engine):
    """A queue blocked on SLOTS (pages plentiful) must not trigger
    admission-pressure eviction: evicting cached prefixes frees pages, never
    slots, so the sweep would drain the whole cache for zero gain while the
    head waits for a slot either way."""
    rng = np.random.default_rng(31)
    warm = rng.integers(0, 96, size=12).astype(np.int32)
    sched = _sched(engine, cache=True)     # default page budget: plentiful
    h = sched.submit(warm, max_new_tokens=4)
    sched.run()
    assert h.state.value == "finished"
    assert sched.prefix_cache.entries >= 1     # refcount-1 pages, evictable
    longs = [rng.integers(0, 96, size=6).astype(np.int32) for _ in range(3)]
    hs = [sched.submit(p, max_new_tokens=16) for p in longs]
    sched.step()                           # 2 slots busy, head queued on slots
    assert sched.executor.pool.free_slots == 0 and len(sched.queue) >= 1
    assert sched.prefix_cache.evicted == 0     # nothing drained
    sched.run()
    for h2, p in zip(hs, longs):
        np.testing.assert_array_equal(h2.result(), _ref(engine, p, 16))


def test_admission_pressure_protects_head_hit(engine):
    """Page pressure must not evict the very entry the head request is about
    to bind: the sweep peeks the head's prefix (stats-free), exempts its
    matching entry, and admits on the hit-aware (suffix-only) fresh-page
    need — an all-fresh estimate would evict the hit and pay a full
    prefill."""
    rng = np.random.default_rng(29)
    shared = rng.integers(0, 96, size=16).astype(np.int32)
    p2 = np.concatenate([shared, rng.integers(0, 96, size=6).astype(np.int32)])
    sched = _sched(engine, cache=True, max_seq_len=32,      # 4-page cap class
                   kv_total_pages=6)                        # 5 usable pages
    h1 = sched.submit(shared, max_new_tokens=8)
    sched.run()
    pool = sched.executor.pool
    assert h1.state.value == "finished"
    assert 0 < pool.pages_in_use <= 3      # cached prefix pins pages
    # head: 22 prompt + 8 new = 4 pages all-fresh (> free list) but only 2
    # fresh past the shared prefix — admissible iff the hit survives
    h2 = sched.submit(p2, max_new_tokens=8)
    sched.run()
    assert h2.state.value == "finished"
    assert h2.prefix_hit_tokens == 16      # zero-copy bind, entry not evicted
    assert sched.prefix_cache.evicted == 0
    np.testing.assert_array_equal(h2.result(), _ref(engine, p2, 8))


# ------------------------------------------- router: retry / drain / migrate
def _router(engines, **over):
    serving = over.pop("serving", None) or ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP, retry_base_delay=0.001,
        kv_page_size=8, prefix_cache=_cache_cfg())
    rcfg = RouterConfig(serving=serving, suspect_after_s=0.04,
                        dead_after_s=0.12, recover_after_s=30.0,
                        breaker_threshold=2, max_attempts=4,
                        retry_base_delay=0.001)
    for k, v in over.items():
        setattr(rcfg, k, v)
    return Router(engines, rcfg)


def test_retry_after_kill_paged(engines):
    """Mid-decode replica kill on the paged pool: checkpointless retry stays
    bit-identical to an unkilled run, lost == 0."""
    import time
    router = _router(engines)
    rng = np.random.default_rng(19)
    p = rng.integers(0, 96, size=8).astype(np.int32)
    h = router.submit(p, max_new_tokens=12)
    victim = None
    t0 = time.monotonic()
    while not h.done and time.monotonic() - t0 < 60:
        if victim is None and h.inner is not None and len(h.inner.tokens) >= 2:
            victim = router.replicas[h.replica_id]
            victim.kill()
        router.step()
    assert h.state.value == "finished" and h.retried >= 1
    np.testing.assert_array_equal(h.result(), _ref(engines[0], p, 12))
    assert router.snapshot()["lost"] == 0


def test_drain_handoff_paged(engines):
    """Graceful drain on the paged pool: hand-off specs continue bit-exactly
    on a fresh router."""
    router = _router(engines)
    rng = np.random.default_rng(23)
    ps = [rng.integers(0, 96, size=s).astype(np.int32) for s in (6, 4, 5)]
    hs = [router.submit(p, max_new_tokens=12) for p in ps]
    router.step()
    router.begin_drain()
    specs = router.drain()
    assert len(specs) == len(hs) and router.snapshot()["lost"] == 0
    router2 = _router(engines)
    hs2 = {s["id"]: router2.submit(np.asarray(s["prompt"], np.int32),
                                   max_new_tokens=s["max_new_tokens"])
           for s in specs}
    router2.run()
    for h, p in zip(hs, ps):
        h2 = hs2[h.id]
        assert h2.state.value == "finished"
        full = np.concatenate([h.result(), h2.result()])
        np.testing.assert_array_equal(full, _ref(engines[0], p, 12))


def test_autoscale_migration_paged(engines):
    """Scale-down retire mid-flight on the paged pool: the migrated request's
    final stream is bit-identical, lost == 0."""
    import time
    router = _router(engines, retire_grace_s=0.05)
    rng = np.random.default_rng(29)
    p = rng.integers(0, 96, size=7).astype(np.int32)
    h = router.submit(p, max_new_tokens=14)
    t0 = time.monotonic()
    retired = False
    while not h.done and time.monotonic() - t0 < 60:
        if not retired and h.inner is not None and len(h.inner.tokens) >= 2:
            router.begin_retire(h.replica_id)
            retired = True
        router.step()
    assert retired and h.state.value == "finished"
    np.testing.assert_array_equal(h.result(), _ref(engines[0], p, 14))
    snap = router.snapshot()
    assert snap["lost"] == 0


def test_page_bind_chaos_kill(engines):
    """``kill:when=restore`` extended to the paged BIND seam: the kill lands
    between the zero-copy page bind and the suffix prefill; the request
    survives via router retry, bit-exact, lost == 0."""
    import time
    router = _router(engines)
    rng = np.random.default_rng(31)
    shared = rng.integers(0, 96, size=16).astype(np.int32)

    def prompt():
        return np.concatenate([shared,
                               rng.integers(0, 96, size=4).astype(np.int32)])

    h = router.submit(prompt(), max_new_tokens=3, session="s")
    while not h.done:
        router.step()
    pinned = router._affinity["s"]
    chaos = ChaosSchedule([ChaosEvent(kind="kill", replica=pinned,
                                      when="restore")])
    prompts = [prompt() for _ in range(3)]
    hs = [router.submit(p, max_new_tokens=6, session="s") for p in prompts]
    t0 = time.monotonic()
    while any(not h.done for h in hs) and time.monotonic() - t0 < 60:
        chaos.poll(router)
        router.step()
    assert chaos.exhausted, "bind-kill never fired (no cache-hit admission)"
    assert all(h.state.value == "finished" for h in hs)
    for h, p in zip(hs, prompts):
        np.testing.assert_array_equal(h.result(), _ref(engines[0], p, 6))
    assert router.snapshot()["lost"] == 0


def test_pool_rebuild_clears_page_cache(engine):
    """A pool rebuild (failed donated dispatch) voids the shared pages, so
    the paged prefix cache clears with it — the next same-prefix admission is
    an honest miss, still bit-exact."""
    rng = np.random.default_rng(37)
    shared = rng.integers(0, 96, size=16).astype(np.int32)
    p = np.concatenate([shared, rng.integers(0, 96, size=4).astype(np.int32)])
    sched = _sched(engine, cache=True)
    h = sched.submit(p, max_new_tokens=4)
    sched.run()
    assert sched.prefix_cache.entries > 0
    sched._rebuild_pool()
    assert sched.prefix_cache.entries == 0
    assert sched.executor.pool.pages_in_use == 0
    h2 = sched.submit(p, max_new_tokens=4)
    sched.run()
    assert h2.prefix_hit_tokens == 0              # honest miss after rebuild
    np.testing.assert_array_equal(h2.result(), _ref(engine, p, 4))


# ------------------------------------------------------- slab wire roundtrip
def test_gather_restore_slab_roundtrip():
    """gather_prefix/restore_prefix survive as the page-granular dense-slab
    serialization API (the disaggregation wire format): a slab gathered from
    one slot restores into a fresh slot bit-identically."""
    cfg = gpt2_cfg(**TINY)
    pool = PagedKVPool(cfg, slots=2, cap=32, page_size=8, dtype=jnp.float32)
    rng = np.random.default_rng(41)
    s0 = pool.acquire(tokens=20)
    one = [{"k": jnp.asarray(rng.standard_normal((1, 4, 32, 8)), jnp.float32),
            "v": jnp.asarray(rng.standard_normal((1, 4, 32, 8)), jnp.float32)}
           for _ in range(cfg.n_layer)]
    pool.scatter_prefill(s0, one)
    slab = pool.gather_prefix(s0, 20)
    for layer, s in zip(one, slab):
        np.testing.assert_array_equal(np.asarray(s["k"]),
                                      np.asarray(layer["k"][0, :, :20]))
    s1 = pool.acquire(tokens=20)
    pool.restore_prefix(s1, slab)
    slab2 = pool.gather_prefix(s1, 20)
    for a, b in zip(slab, slab2):
        np.testing.assert_array_equal(np.asarray(a["k"]), np.asarray(b["k"]))
        np.testing.assert_array_equal(np.asarray(a["v"]), np.asarray(b["v"]))


# ----------------------------------------------------------- front-door knob
def test_kv_page_size_validation():
    from deepspeed_tpu.inference.serving import server as srv
    with pytest.raises(SystemExit, match="multiple"):
        srv.main(["--kv-page-size", "10", "--chunk-size", "8", "--selftest",
                  "--requests", "1"])
    spec = importlib.util.spec_from_file_location(
        "loadgen_pagedtest", os.path.join(REPO, "benchmarks", "serving",
                                          "loadgen.py"))
    lg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lg)
    with pytest.raises(SystemExit):
        lg.main(["--smoke", "--kv-page-size", "10", "--chunk-size", "8"])
    with pytest.raises(SystemExit):
        lg.main(["--smoke", "--prompt-dist", "bimodal:garbage"])
