"""The serve path's KV cache pool: fixed-size pages behind per-slot page tables.

:class:`PagedKVPool` — one global pool of fixed-size KV **pages** per layer
(``{"k": (P, hk / r, page, r * d), ...}``; the layout itself, ``r`` heads a
row, lives in ``ops/paged_attention.py``) behind a static-shape per-slot page table. A slot
allocates only the pages its ``prompt + max_new`` needs (page-granular
admission: occupancy tracks requested tokens, not the pow2-bucketed worst
case), pages are refcounted so the prefix cache can **share** a prompt's pages
zero-copy (a hit binds page indices into the new slot's table — no slab
gather, no restore scatter; the first partially-covered page is
copy-on-write), and a page is the shipment unit disaggregated prefill will
serialize. Every pool mutation — scatter-in of a prefill's batch-1 cache,
copy-on-write, slab restore, a released slot's state zero-fill — runs as a
donated jitted update, so the pool's HBM footprint is constant:
``donate_argnums`` is honoured on CPU too, so there are no backend guards
(guarding donation behind backend checks cost 1500x on pool scatters in an
earlier revision of this codebase). Released pages are NOT zero-filled: every
row below a slot's ``cache_len`` is freshly written (prefill/suffix/decode) or
a verbatim shared prefix row, and attention masks everything at or beyond
``cache_len`` — leak safety is structural, and release is O(pages) host
bookkeeping.

``gather_prefix``/``restore_prefix`` are the dense-slab serialization API
(page-granular underneath) — the wire format disaggregated prefill ships
between replicas, and what the prefix cache's host tier holds.

Per-slot sequence lengths are scheduler state (host numpy, passed into each
decode chunk); the pool owns the device buffers, the free lists and the page
table + refcounts.
"""

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...models.causal_lm import LAYER_KINDS, PAGED, init_cache
from ...observability.trace import get_tracer
from ...ops.paged_attention import pages_to_dense, write_dense_pages


NULL_PAGE = 0      # reserved sentinel: pads every table row; rows it could
#   contribute are always masked by cache_len, writes to it are dead stores


# The movers live at MODULE level (lru_cache + jit-by-shape), not on the
# pool instance: a pool is rebuilt on every reset_pool (failure recovery) and
# per serving lane, and per-instance jitted closures re-paid their XLA compile
# each time — measured at ~0.15 s per pool, which dominated short serving
# runs. Geometry (page size, table width, layer count) is recovered from the
# argument shapes, so one compiled mover serves every same-shaped pool.
# ``keeps`` is ``CausalLMConfig.layer_keeps``: what each layer's cache is.
@functools.lru_cache(maxsize=None)
def _paged_scatter_jit(keeps):
    def scatter(caches, one, tbl, slot):
        # write a prefill's dense batch-1 cache into the slot's pages; rows
        # beyond cap pad with zeros into the (dead) null page. A layer's
        # per-slot state is written whole into row ``slot``.
        out = []
        for keep, c, o in zip(keeps, caches, one):
            if keep not in PAGED:
                out.append({key: c[key].at[slot].set(o[key][0].astype(c[key].dtype))
                            for key in c})
                continue
            out.append(write_dense_pages(c, o, tbl))
        return out

    return jax.jit(scatter, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _state_zero_jit(keeps):
    def zero_fill(caches, slot):
        return [c if keep in PAGED else
                {key: c[key].at[slot].set(0.0) for key in c}
                for keep, c in zip(keeps, caches)]

    return jax.jit(zero_fill, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _paged_cow_jit(keeps):
    def cow(caches, src, dst):
        return [{key: c[key].at[dst].set(c[key][src]) for key in c}
                if keep in PAGED else c for keep, c in zip(keeps, caches)]

    return jax.jit(cow, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _paged_gather_jit(R: int):
    def gather(caches, tbl):
        out = []
        for c in caches:
            k = pages_to_dense(c["k"], tbl)
            v = pages_to_dense(c["v"], tbl)
            out.append({"k": k[:, :R, :], "v": v[:, :R, :]})
        return out

    return jax.jit(gather)


@functools.lru_cache(maxsize=None)
def _paged_restore_jit(R: int):
    def restore(caches, slab, tbl):
        return [write_dense_pages(c, s, tbl) for c, s in zip(caches, slab)]

    return jax.jit(restore, donate_argnums=(0,))


class PagedKVPool:
    """Global fixed-size KV pages behind per-slot page tables (see module
    docstring). ``cap`` is the per-slot row capacity the compiled fns see —
    pages round it UP internally (``max_pages = ceil(cap / page)``) but every
    dense view the model computes over is sliced back to exactly ``cap`` rows,
    so attention math (reduction shapes included) is bit-identical to a
    contiguous ``cap``-row cache's."""

    def __init__(self, model_config, slots: int, cap: int, page_size: int = 16,
                 dtype=None, total_pages: Optional[int] = None):
        if slots < 1 or cap < 2:
            raise ValueError(f"need slots >= 1 and cap >= 2, got {slots}, {cap}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.slots = int(slots)
        self.cap = int(cap)
        self.page_size = ps = int(page_size)
        self.max_pages = mp = math.ceil(self.cap / ps)   # table width per slot
        if total_pages is None:
            # default budget: every slot's whole cap at once (plus the one
            # null page)
            total_pages = self.slots * mp + 1
        self.total_pages = P = int(total_pages)
        if P < mp + 1:
            raise ValueError(
                f"total_pages={P} cannot hold even one max-size request "
                f"({mp} pages) plus the null page")
        cfg = model_config
        self.n_layer = cfg.n_layer
        dtype = dtype or cfg.dtype
        self.heads_per_row = r = cfg.cache_row_heads
        shape = (P, cfg.kv_heads // r, ps, r * cfg.head_dim)
        self.programs = 0     # compiled programs dispatched for this pool
        # two kinds of state in one manager: pages for the layers that keep
        # keys and values, a per-slot array for the layers with a recurrent
        # state or a ring of their last rows (bound to the slot, not to
        # pages: it does not grow with the sequence), nothing for the rest
        self.caches = init_cache(cfg, self.slots, dtype=dtype, kv_shape=shape)
        self.keeps = keeps = cfg.layer_keeps
        self.kv_layers = sum(1 for keep in keeps if keep in PAGED)
        self.state_nbytes = sum(int(a.nbytes)
                                for keep, c in zip(keeps, self.caches)
                                if keep not in PAGED for a in c.values())
        # the part of the per-slot state that is windowed layers' rings
        self.ring_nbytes = sum(int(a.nbytes)
                               for kind, c in zip(cfg.layer_kinds, self.caches)
                               if LAYER_KINDS[kind].ring for a in c.values())
        # a page of every paged layer: keys and values (2 x kv_heads x
        # head_dim lanes a token), or a latent layer's one row a token
        self.page_nbytes = sum(int(a.nbytes) // P
                               for keep, c in zip(keeps, self.caches)
                               if keep in PAGED for a in c.values())
        # bytes a token a layer as a latent layer stores them (0: none does)
        self.latent_row_nbytes = next(
            (int(c["k"].shape[3]) * c["k"].dtype.itemsize
             for keep, c in zip(keeps, self.caches) if keep == "latent"), 0)
        # host allocator state
        self.page_table = np.full((self.slots, mp), NULL_PAGE, np.int32)
        self._free_slots: List[int] = list(range(self.slots))
        self._free_pages: List[int] = list(range(1, P))     # 0 = null page
        self._ref = np.zeros(P, np.int64)
        self._slot_npages = np.zeros(self.slots, np.int32)
        self._slot_tokens = np.zeros(self.slots, np.int64)  # reserved tokens
        self.cow_copies_total = 0
        # pool pages donated unconditionally (the old buffers are always
        # dead after the update; the prefill's batch-1 cache is NOT donatable:
        # its buffers cannot alias any page); the jitted movers are
        # module-level shape-keyed singletons — rebuilding a pool after a
        # failure (or per serving lane) must not re-pay XLA compiles
        self._scatter_fn = _paged_scatter_jit(keeps)
        self._cow_fn = _paged_cow_jit(keeps)

    # --------------------------------------------------------------- allocator
    def pages_for(self, tokens: int) -> int:
        return math.ceil(max(1, int(tokens)) / self.page_size)

    def _fresh_needed(self, tokens: int, matched: int = 0) -> int:
        """Pages a new request must ALLOCATE (shared full pages bind for free;
        a partially-covered boundary page costs one copy-on-write page)."""
        need = self.pages_for(tokens)
        shared_full = int(matched) // self.page_size
        return need - shared_full

    def can_admit(self, tokens: Optional[int] = None, matched: int = 0) -> bool:
        tokens = self.cap if tokens is None else int(tokens)
        return bool(self._free_slots) and \
            len(self._free_pages) >= self._fresh_needed(tokens, matched)

    def acquire(self, tokens: Optional[int] = None, prefix_pages=None,
                matched: int = 0) -> Optional[int]:
        """Borrow a slot and allocate its pages, or ``None`` when slot or page
        capacity is exhausted (the caller leaves the request queued).

        ``tokens`` is the reservation (``prompt + max_new``; defaults to
        ``cap``). With ``prefix_pages``/``matched`` (a prefix-cache hit), the
        first ``matched // page`` table entries BIND the shared pages
        (refcount bump, zero-copy) and a partially-covered boundary page is
        copied into a fresh private page (copy-on-write) so the new slot's
        suffix writes never touch shared rows."""
        tokens = self.cap if tokens is None else int(tokens)
        if tokens > self.cap:
            raise ValueError(f"reservation {tokens} exceeds cap {self.cap}")
        matched = int(matched)
        if prefix_pages is None:
            matched = 0
        need = self.pages_for(tokens)
        shared_full = matched // self.page_size
        cow = 1 if matched % self.page_size else 0
        fresh = need - shared_full
        if not self._free_slots or len(self._free_pages) < fresh:
            return None
        if prefix_pages is not None and shared_full + cow > len(prefix_pages):
            raise ValueError(
                f"matched={matched} needs {shared_full + cow} prefix pages, "
                f"entry holds {len(prefix_pages)}")
        with get_tracer().span("serving.page_table",
                               op="acquire" if prefix_pages is None else "bind",
                               pages_fresh=fresh, pages_shared=shared_full,
                               cow=cow):
            slot = self._free_slots.pop(0)
            row = self.page_table[slot]
            n = 0
            for j in range(shared_full):               # zero-copy shared bind
                p = int(prefix_pages[j])
                self._ref[p] += 1
                row[n] = p
                n += 1
            if cow:                                    # boundary page: COW
                src = int(prefix_pages[shared_full])
                dst = self._free_pages.pop(0)
                self._move(self._cow_fn, np.int32(src), np.int32(dst))
                self.cow_copies_total += 1
                self._ref[dst] = 1
                row[n] = dst
                n += 1
            for _ in range(need - n):                  # private fresh pages
                p = self._free_pages.pop(0)
                self._ref[p] = 1
                row[n] = p
                n += 1
            self._slot_npages[slot] = need
            self._slot_tokens[slot] = tokens
        return slot

    def release(self, slot: int) -> None:
        """Return the slot and decref its pages; pages at refcount 0 go back
        to the free list (a page the prefix cache still references survives —
        eviction there is just another refcount drop). No zero-fill: see the
        module docstring's leak-safety argument."""
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} is already free")
        with get_tracer().span("serving.page_table", op="release",
                               pages_fresh=int(self._slot_npages[slot]),
                               pages_shared=0, cow=0):
            row = self.page_table[slot]
            for j in range(int(self._slot_npages[slot])):
                self._decref(int(row[j]))
            row[:] = NULL_PAGE
            self._slot_npages[slot] = 0
            self._slot_tokens[slot] = 0
            self._free_slots.append(slot)
        if self.state_nbytes:
            # a recurrent state has no length to mask it by: the slot's is
            # cleared here (and written whole again at the next admission; a
            # ring's length does mask it, and it is cleared with the rest)
            with get_tracer().span("serving.clear_state", slot=int(slot),
                                   state_bytes=self.state_nbytes // self.slots):
                self._move(_state_zero_jit(self.keeps), np.int32(slot))

    def _decref(self, page: int) -> None:
        if page == NULL_PAGE:
            return
        if self._ref[page] <= 0:
            raise AssertionError(f"refcount underflow on page {page}")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free_pages.append(page)

    # ----------------------------------------------------- prefix page sharing
    def share_prefix(self, slot: int, tokens: int) -> np.ndarray:
        """Refcount-bump the slot's pages covering rows ``[0, tokens)`` and
        return their indices — the prefix cache's zero-copy insert. The
        boundary page is shared too: a
        later hit only trusts its rows below the matched length and
        copy-on-writes before writing."""
        n = self.pages_for(tokens)
        if n > int(self._slot_npages[slot]):
            raise ValueError(f"slot {slot} holds {self._slot_npages[slot]} "
                             f"pages, cannot share {n}")
        pages = self.page_table[slot, :n].copy()
        for p in pages:
            self._ref[int(p)] += 1
        return pages

    def release_shared(self, pages) -> None:
        """Drop a prefix-cache entry's page references (LRU eviction path)."""
        for p in pages:
            self._decref(int(p))

    def page_ref(self, page: int) -> int:
        """Current refcount of a page (admission-pressure eviction asks which
        cache entries would actually free pages: exactly those holding a
        page at refcount 1)."""
        return int(self._ref[int(page)])

    def table_row(self, slot: int) -> np.ndarray:
        return self.page_table[slot]

    def _move(self, mover, *args) -> None:
        """Dispatch one of the compiled movers on the arrays (donated to it)
        and bind what it hands back."""
        self.programs += 1
        self.caches = mover(self.caches, *args)

    @property
    def consumed(self) -> bool:
        """A program that was handed the arrays (donated) took them along and
        failed: nothing can read or write this pool again, the owner rebuilds
        it. The buffers say so themselves: what a dispatch was handed stays
        bound until it has handed its result back, and is bound again by an
        owner whose fetch of that result failed. False after a failure that
        came before the dispatch took anything."""
        return any(a.is_deleted() for a in jax.tree_util.tree_leaves(self.caches))

    # ------------------------------------------------------ prefill scatter-in
    def scatter_prefill(self, slot: int, one_caches: List[Dict[str, Any]]) \
            -> None:
        """Write a prefill's dense batch-1 per-layer cache into the slot's
        pages (and the slot's per-slot state, where a layer keeps one)."""
        self._move(self._scatter_fn, one_caches,
                   jnp.asarray(self.page_table[slot]), np.int32(slot))

    # --------------------------------------------------------- slab I/O (wire)
    def gather_prefix(self, slot: int, rows: int) -> List[Dict[str, Any]]:
        """Copy rows ``[0, rows)`` of ``slot`` out as an independent dense KV
        slab — the page-granular serialization API disaggregated prefill
        ships (NOT donated; the slab's lifetime is the caller's). Underneath
        it is a page gather sliced to ``rows``."""
        R = int(rows)
        if not 0 < R <= self.cap:
            raise ValueError(f"rows must be in [1, cap={self.cap}], got {R}")
        return _paged_gather_jit(R)(self.caches,
                                    jnp.asarray(self.page_table[slot]))

    def gather_pages(self, pages, rows: int) -> List[Dict[str, Any]]:
        """Dense slab from an ARBITRARY page-index vector — ``gather_prefix``
        without a slot, for pages a live table no longer (or never) maps:
        the prefix cache's spill path gathers an evicted entry's pages into
        a host-tier slab right before dropping its refcounts. Same compiled
        mover as ``gather_prefix`` (the jit is keyed only on ``rows``), NOT
        donated — the pool keeps serving."""
        R = int(rows)
        if not 0 < R <= self.cap:
            raise ValueError(f"rows must be in [1, cap={self.cap}], got {R}")
        n = self.pages_for(R)
        if n > len(pages):
            raise ValueError(f"{R} rows span {n} pages, got {len(pages)}")
        tbl = jnp.asarray(np.asarray(pages, np.int32)[:n])
        return _paged_gather_jit(R)(self.caches, tbl)

    def restore_prefix(self, slot: int, slab: List[Dict[str, Any]]) -> None:
        """Write a dense gathered slab into rows ``[0, slab_rows)`` of the
        slot's pages (donated pool update). Assumes a freshly acquired slot:
        boundary-page rows beyond the slab are zero-padded, which is exactly
        the unwritten state they are in."""
        R = int(slab[0]["k"].shape[1])
        if R > self.cap:
            raise ValueError(f"slab rows {R} exceed pool cap {self.cap}")
        n = self.pages_for(R)
        if n > int(self._slot_npages[slot]):
            raise ValueError(f"slot {slot} holds {self._slot_npages[slot]} "
                             f"pages, slab needs {n}")
        self._move(_paged_restore_jit(R), slab,
                   jnp.asarray(self.page_table[slot, :n]))

    def promote_prefix(self, slot: int, slab: List[Dict[str, Any]],
                       matched: int) -> None:
        """Restore a host-tier slab's first ``matched`` rows into a freshly
        acquired slot — the promote path of the tiered prefix cache. The
        restore width is normalized HOST-SIDE to the page multiple covering
        ``matched`` (slice or zero-pad the numpy slab), so the compiled
        restore is keyed on page multiples only — geometry-bounded compile
        keys instead of one per distinct spilled-prompt length. Rows in
        ``[matched, page-multiple)`` land in the slot's own private pages and
        are overwritten by the suffix prefill or masked by ``cache_len`` —
        the same argument ``restore_prefix`` already makes for its padding.
        Requires a slot acquired WITHOUT shared prefix pages (the donated
        write would otherwise clobber rows other slots still trust)."""
        m = int(matched)
        rows = int(slab[0]["k"].shape[1])
        if not 0 < m <= min(rows, self.cap):
            raise ValueError(f"matched must be in [1, min(slab rows {rows}, "
                             f"cap {self.cap})], got {m}")
        n = self.pages_for(m)
        if n > int(self._slot_npages[slot]):
            raise ValueError(f"slot {slot} holds {self._slot_npages[slot]} "
                             f"pages, promote needs {n}")
        R = n * self.page_size
        if rows != R:
            fixed = []
            for s in slab:
                k = np.asarray(s["k"])[:, :R, :]
                v = np.asarray(s["v"])[:, :R, :]
                if k.shape[1] < R:
                    pad = ((0, 0), (0, R - k.shape[1]), (0, 0))
                    k, v = np.pad(k, pad), np.pad(v, pad)
                fixed.append({"k": k, "v": v})
            slab = fixed
        self._move(_paged_restore_jit(R), slab,
                   jnp.asarray(self.page_table[slot, :n]))

    # ------------------------------------------------------------------ metrics
    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def pages_in_use(self) -> int:
        return self.total_pages - 1 - len(self._free_pages)

    @property
    def occupancy(self) -> float:
        """SLOT occupancy (the autoscaler's signal); page-level utilisation
        is in :meth:`stats`."""
        return 1.0 - len(self._free_slots) / self.slots

    @property
    def shared_pages(self) -> int:
        """Pages referenced more than once (slot+cache or multi-slot bind)."""
        return int(np.sum(self._ref > 1))

    @property
    def page_fragmentation(self) -> float:
        """Internal fragmentation of slot-held pages: the fraction of
        allocated page rows beyond the slots' token reservations (allocation
        granularity waste — the quantity the page-size knob trades against
        table width)."""
        pages = int(np.sum(self._slot_npages))
        if pages == 0:
            return 0.0
        reserved = int(np.sum(self._slot_tokens))
        return 1.0 - reserved / (pages * self.page_size)

    def stats(self) -> Dict[str, float]:
        return {
            "pages_in_use": float(self.pages_in_use),
            "page_fragmentation": float(self.page_fragmentation),
            "prefix_shared_pages": float(self.shared_pages),
            "cow_copies_total": float(self.cow_copies_total),
            "total_pages": float(self.total_pages - 1),
            "page_size": float(self.page_size),
            "state_bytes": float(self.state_nbytes),
            "ring_bytes": float(self.ring_nbytes),
            "latent_row_bytes": float(self.latent_row_nbytes),
        }
