"""Chunked decode executor: compiled fixed-shape chunks over a slot-batch.

The refactored form of ``InferenceEngine._loop_fns``: instead of one
run-to-completion ``lax.while_loop`` per user call, decode runs in chunks of K
steps over a fixed slot-batch and returns to the host between chunks — the host
window in which the continuous-batching scheduler retires finished requests,
recycles their KV slots and prefills pending prompts, while the other slots keep
decoding. Compile-key discipline:

- ONE decode-chunk compile per (slots, cap, chunk, sampling) key, cached on the
  owning engine's ``_fns`` so coexisting executors share it;
- ONE prefill compile per (prompt-bucket, cap, sampling) key — prompts are
  right-padded to power-of-two buckets so arbitrary lengths hit a handful of
  compiles.

KV buffers are donated unconditionally (chunk in-place-updates the pool pages;
``donate_argnums`` is honoured on CPU too — no backend guards).

Watchdog: with ``chunk_deadline_s`` set, each chunk (dispatch + host fetch — the
two places a hung compile or collective wedges) runs on a watchdog thread and a
deadline overrun raises :class:`ChunkTimeoutError` instead of blocking the
scheduler loop forever. The timed region declares the ``serving.chunk_compute``
fault point, so a ``delay`` fault (or the :meth:`stall_next` chaos hook) models
the hang deterministically. A timed-out chunk's pool buffers are unrecoverable —
they were donated into the wedged dispatch — so the caller must ``reset_pool``
(the scheduler's decode-failure path already does).
"""

import threading
import time
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...models.causal_lm import init_cache
from ...observability import profiler as obs_profiler
from ...observability.trace import get_tracer, scope
from ...utils.fault_injection import fault_point
from ...ops.moe.grouped_ffn import plan_rows, tile_rows
from ...ops.paged_attention import pages_to_dense, write_view_rows
from ..decode_fns import (block_chunk_width, build_block_decode_chunk,
                          build_paged_decode_chunk, build_paged_spec_verify,
                          build_prefill, build_prefix_prefill,
                          make_slot_select_fn)
from ..speculative import accept_tokens
from .kv_pool import PagedKVPool


class ChunkTimeoutError(RuntimeError):
    """A decode chunk exceeded its wall-clock deadline (hung compile/collective).

    Deliberately NOT a retryable transient: the chunk's donated KV buffers are
    lost inside the wedged dispatch, so the only safe recovery is evict + pool
    rebuild (+ requeue on another replica, when a router is above)."""

    def __init__(self, deadline_s: float):
        super().__init__(f"decode chunk exceeded its {deadline_s:.3f}s deadline")
        self.deadline_s = float(deadline_s)


class ReplicaKilledError(RuntimeError):
    """Raised by the ``arm_restore_kill`` chaos hook: the stand-in for a
    replica dying between prefix restore/bind and suffix prefill."""


# One int32 operand in and one int32 result out a call: every small array is
# a host-device crossing of its own with the chip idle, whatever its size. A
# chunk's operand ``ctl`` is a row a slot, these columns and then the slot's
# page-table row; its result is a row a slot, the chunk's emitted tokens and
# then ``OUT``'s columns, with the two expert counts at the head of one more
# row where the model has expert layers. A prefill's rank-1 ``ctl`` is ``len,
# seed``; a suffix prefill's is ``prefix_len, suffix_len, seed`` and then the
# slot's page-table row.
CTL_TOK, CTL_LEN, CTL_ACTIVE, CTL_REMAINING, CTL_EOS, CTL_SEED, CTL_STEPS, \
    CTL_COLS = range(8)
OUT_TOK, OUT_LEN, OUT_ACTIVE, OUT_REMAINING, OUT_STEPS = range(5)
PRE_COLS = 3


def ctl_head(block: int) -> int:
    """Columns of a chunk's operand before the page-table row: ``CTL_COLS``
    and, for a model that generates by blocks of ``block``, the block's
    tokens, the bitmask of its masked places and ``skip``."""
    return CTL_COLS + (block + 2 if block else 0)


def _packed_chunk(chunk):
    """``chunk`` (``decode_fns.build_paged_decode_chunk``'s function) behind
    the packed operand and result the module's head describes; the name the
    trace and the lowered module carry stays ``decode_chunk``."""

    def decode_chunk(params, ctl, caches, base_key):
        # the table is host state bound at admission; it never changes
        # inside a chunk, so it rides in the operand's tail
        with scope("chunk.pack"):
            page_table = ctl[:, CTL_COLS:]
            args = (ctl[:, CTL_TOK:CTL_TOK + 1], caches, page_table,
                    ctl[:, CTL_LEN], ctl[:, CTL_ACTIVE] != 0,
                    ctl[:, CTL_REMAINING], ctl[:, CTL_EOS], ctl[:, CTL_SEED],
                    ctl[:, CTL_STEPS])
        buf, toks, caches, lens, active, remaining, steps, *stats = chunk(
            params, *args, base_key)
        with scope("chunk.pack"):
            packed = jnp.concatenate(
                [buf, toks, lens[:, None], active.astype(jnp.int32)[:, None],
                 remaining[:, None], steps[:, None]], axis=1)
            if stats:
                counts = jnp.pad(stats[0], (0, packed.shape[1] - 2))
                packed = jnp.concatenate([packed, counts[None]], axis=0)
        return packed, caches

    return decode_chunk


def _prefill(prefill_logits, select, cfg, cap: int, dtype):
    """The cache-miss prefill's program
    (:meth:`ChunkedDecodeExecutor._prefill_fn`) over
    ``decode_fns.build_prefill``'s function: the stand-alone prefill on a
    batch-1 cache of its own, the forward ``engine.generate``'s is to the bit.
    ``one`` is read by nothing: it is the last miss's batch-1 cache, donated,
    and this one's is written over its buffers, so that the program's 60-80
    results are allocated by no one. ``ctl`` is ``(len, seed)``."""

    def prefill(params, one, ids, ctl, base_key):
        del one
        with scope("chunk.pack"):
            caches = init_cache(cfg, 1, cap, dtype=dtype)
            seed = ctl[1:2]
            lens0 = ctl[0:1]
        logits, new_caches, *stats = prefill_logits(params, ids, caches, lens0)
        tok0 = select(logits, base_key, seed, jnp.zeros_like(seed))
        # the first token, and the expert layers' two counts behind it
        with scope("chunk.pack"):
            return jnp.concatenate([tok0[0], *stats]), new_caches

    return prefill


def _suffix_prefill(prefix_prefill, select, cap: int):
    """The cache-hit prefill's program
    (:meth:`ChunkedDecodeExecutor._suffix_prefill_fn_paged`) over
    ``decode_fns.build_prefix_prefill``'s function: ``ctl`` is ``(prefix_len,
    suffix_len, seed)`` and then the slot's page-table row."""

    def suffix_prefill(params, caches, ids, ctl, base_key):
        with scope("chunk.pack"):
            prefix_len, suffix_len, seed = ctl[0:1], ctl[1:2], ctl[2:3]
            tbl = ctl[PRE_COLS:]        # the slot's page-table row
        one = []
        with scope("kv.gather"):
            for c in caches:
                k = pages_to_dense(c["k"], tbl)
                v = pages_to_dense(c["v"], tbl)
                one.append({"k": k[None, :, :cap, :],
                            "v": v[None, :, :cap, :]})
        logits, new_one = prefix_prefill(params, ids, one, prefix_len,
                                         suffix_len)
        tok0 = select(logits, base_key, seed, jnp.zeros_like(seed))
        # ONLY the suffix rows [prefix, prefix + bucket) go back, as slab
        # writes; a row at or past cap keeps what its page held
        t = ids.shape[1]
        with scope("kv.copy_back"):
            out = write_view_rows(caches, new_one, tbl[None], prefix_len,
                                  jnp.full_like(prefix_len, t), t, cap)
        return tok0[0], out

    return suffix_prefill


def _packed_block_chunk(chunk, block: int):
    """``chunk`` (``decode_fns.build_block_decode_chunk``'s function) behind
    the packed operand and result: a model that generates by diffusion over
    blocks carries a slot's block in flight between chunks, so its operand
    has, between ``CTL_COLS`` and the page-table row, the block's ``block``
    tokens, a bit a position that is still masked, and how many of the tokens
    the prompt gave; its result has the same three behind ``OUT``'s columns.
    The last row holds ``(expert assignments, experts touched, blocks
    committed, positions unmasked, commits that opened their next block)``,
    the first two 0 without expert layers.
    The name in the trace stays ``decode_chunk``."""
    bits = 1 << np.arange(block, dtype=np.int32)
    tail = ctl_head(block)

    def decode_chunk(params, ctl, caches, base_key):
        with scope("chunk.pack"):
            blk = ctl[:, CTL_COLS:CTL_COLS + block]
            masked = (ctl[:, CTL_COLS + block, None] & bits[None]) != 0
            args = (blk, masked, ctl[:, CTL_COLS + block + 1], caches,
                    ctl[:, tail:], ctl[:, CTL_LEN], ctl[:, CTL_ACTIVE] != 0,
                    ctl[:, CTL_REMAINING], ctl[:, CTL_EOS], ctl[:, CTL_SEED],
                    ctl[:, CTL_STEPS])
        buf, blk, masked, skip, caches, lens, active, remaining, steps, counts, \
            *stats = chunk(params, *args, base_key)
        with scope("chunk.pack"):
            packed = jnp.concatenate(
                [buf, jnp.zeros_like(lens)[:, None], lens[:, None],
                 active.astype(jnp.int32)[:, None], remaining[:, None],
                 steps[:, None], blk,
                 jnp.sum(jnp.where(masked, bits[None], 0), axis=1,
                         dtype=jnp.int32)[:, None], skip[:, None]], axis=1)
            moe = stats[0] if stats else jnp.zeros((2,), jnp.int32)
            last = jnp.pad(jnp.concatenate([moe, counts]), (0, packed.shape[1] - 5))
            return jnp.concatenate([packed, last[None]], axis=0), caches

    return decode_chunk


def prompt_buckets(max_prompt_len: int, smallest: int = 8) -> Tuple[int, ...]:
    """Power-of-two right-pad buckets covering ``[1, max_prompt_len]``."""
    buckets = []
    b = smallest
    while b < max_prompt_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_prompt_len)
    return tuple(buckets)


@dataclass
class ChunkResult:
    """Host view of one decode chunk: slices of the one packed array its
    fetch brought back (a speculative round builds the fields on the host)."""
    buf: np.ndarray          # (S, K) emitted tokens; per-slot real prefix only
    toks: np.ndarray         # (S, 1) each slot's last token
    lens: np.ndarray         # (S,) KV append positions
    active: np.ndarray       # (S,) bool
    remaining: np.ndarray    # (S,) decode budget left
    steps: np.ndarray        # (S,) per-request tokens emitted so far
    elapsed: float           # wall seconds for dispatch + fetch: from the end
    #   of ``serving.place_inputs`` to the end of ``serving.fetch`` (the
    #   dispatch, the device's chunk and the one copy back)
    moe: Optional[np.ndarray] = None   # (assignments on held experts, distinct
    #   held experts read) over the chunk's steps and expert layers; None for
    #   a model without expert layers
    moe_plan_rows: int = 0   # rows their dispatch plans laid out (static)
    block: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None   # a
    #   model that generates by blocks: each slot's block in flight after the
    #   chunk, ``(tokens (S, B), still masked (S, B) bool, given by the
    #   prompt (S,))``
    block_counts: Optional[np.ndarray] = None   # and (blocks committed,
    #   positions unmasked, commits that opened their next block in the same
    #   forward) over the chunk's forwards
    stamps: Optional[Tuple[float, float, float]] = None   # ``time.monotonic``
    #   stamps the spans took: the end of ``serving.dispatch``, the start and
    #   the end of ``serving.fetch`` (``elapsed`` ends at the last; the
    #   scheduler's fetch wait and turnaround are differences of these and of
    #   no other clock reading)


@dataclass
class SpecResult(ChunkResult):
    """One speculative verify round, harvest-compatible with a chunk: ``buf``
    is (S, k+1) wide and a slot's real tokens are still the prefix of length
    ``steps_out - steps_in``, so the scheduler's chunk harvest works
    unchanged. ``proposed``/``accepted`` feed the ``serving/spec_*``
    telemetry; ``draft_s`` is filled by the scheduler (the proposer runs on
    the host before the dispatch)."""
    proposed: int = 0        # real draft tokens offered this round
    accepted: int = 0        # draft tokens that survived accept/reject
    draft_s: float = 0.0     # host proposer wall seconds (set by caller)


class ChunkedDecodeExecutor:
    """Drives prefill-into-slot + K-step decode chunks for a scheduler."""

    def __init__(self, engine, slots: int, cap: int, chunk_size: int,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, max_prompt_len: Optional[int]
                 = None, base_seed: int = 0,
                 chunk_deadline_s: Optional[float] = None,
                 cold_chunk_grace_s: float = 120.0, kv_page_size: int = 16,
                 kv_total_pages: Optional[int] = None):
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if chunk_deadline_s is not None and chunk_deadline_s <= 0:
            raise ValueError("chunk_deadline_s must be positive when set, got "
                             f"{chunk_deadline_s}")
        self.engine = engine
        self.slots = int(slots)
        self.cap = int(cap)
        self.chunk_size = int(chunk_size)
        self.max_prompt_len = int(max_prompt_len or cap - 1)
        if self.max_prompt_len >= self.cap:
            raise ValueError("max_prompt_len must leave room for at least one "
                             f"generated token (cap={self.cap})")
        self.sampling = (bool(do_sample), float(temperature), int(top_k),
                         float(top_p))
        self.buckets = prompt_buckets(self.max_prompt_len)
        self.kv_page_size = int(kv_page_size)
        self.kv_total_pages = kv_total_pages
        kinds = engine.model_config.layer_kinds
        # what the model's layers keep decides what the programs carry
        # (``causal_lm.LAYER_KINDS``): a state-space or short-convolution
        # layer a per-slot state, an expert layer its two counts and no
        # cache. The prefix and slab movers (and the suffix prefill) read
        # keys and values from EVERY layer, so prefix hits and speculation
        # need a model whose layers all keep them: see the scheduler
        self.kv_every_layer = engine.model_config.kv_every_layer
        self.with_stats = "E" in kinds
        # a model that generates by diffusion over blocks: the chunk counts
        # FORWARDS, a slot carries its block in flight between chunks, a
        # prefill yields no token
        self.block = int(engine.model_config.gen_block_length)
        if self.block and (self.cap % self.block
                           or self.kv_page_size % self.block):
            raise ValueError(
                f"a block of {self.block} positions must divide the cap "
                f"({self.cap}) and the page size ({self.kv_page_size}): a "
                "block is committed whole and never straddles a page")
        self.last_prefill_moe = None    # the last prefill's counts (or None)
        self.last_prefill_plan_rows = 0  # and the rows its plans laid out
        # what a decode chunk's plans lay out: its forwards x a forward's rows
        # (a block model's forward carries two blocks a slot)
        self._chunk_tokens = self.slots * max(1, 2 * self.block)
        self._chunk_plan_rows = self.chunk_size * self.moe_plan_rows(
            self._chunk_tokens) if self.with_stats else 0
        self.pool = self._build_pool()
        self._one = None                # the miss prefill's batch-1 cache
        self._slot_select = make_slot_select_fn(*self.sampling)
        self._base_key = jax.random.PRNGKey(base_seed)
        self.chunk_deadline_s = chunk_deadline_s
        self.cold_chunk_grace_s = float(cold_chunk_grace_s)
        self._warm_chunk = False        # first successful chunk marks warm
        self._called = set()            # compiled fns this executor has run
        self._stall_next = 0.0
        self._restore_kill = None       # chaos hook: fires between prefix
        #   restore and suffix prefill (see arm_restore_kill)

    @property
    def chunk_warm(self) -> bool:
        """True once the chunk fn has completed at least once — the point from
        which ``chunk_deadline_s`` is enforced at face value (the first chunk is
        granted ``cold_chunk_grace_s`` to cover its XLA compile)."""
        return self._warm_chunk

    def arm_restore_kill(self, callback) -> None:
        """Chaos hook: invoke ``callback`` exactly once, in the window between
        the prefix-slab restore and the suffix prefill of the next cache-hit
        admission, then abort that admission attempt — the deterministic
        stand-in for a replica dying with a restored-but-unprefilled slot. The
        scheduler's prefill retry re-runs the whole restore (donation-safe:
        ``restore_prefix`` rebinds the pool before this hook can fire)."""
        self._restore_kill = callback

    @property
    def restore_kill_pending(self) -> bool:
        return self._restore_kill is not None

    def stall_next(self, seconds: float) -> None:
        """Chaos hook: make the next chunk stall ``seconds`` inside the timed
        region — a deterministic stand-in for a hung compile/collective. With a
        ``chunk_deadline_s`` armed the watchdog converts it into a
        :class:`ChunkTimeoutError`; without one it wedges, which is the failure
        mode the watchdog exists to remove."""
        self._stall_next = float(seconds)

    def _build_pool(self):
        with get_tracer().phase("setup.kv_pool", pool="paged") as ph:
            pool = PagedKVPool(self.engine.model_config, self.slots,
                               self.cap, page_size=self.kv_page_size,
                               dtype=self.engine.dtype,
                               total_pages=self.kv_total_pages)
            ph.set(pages=pool.total_pages, slots=self.slots,
                   state_bytes=pool.state_nbytes,
                   heads_per_row=pool.heads_per_row)
            if pool.ring_nbytes:
                ph.set(ring_bytes=pool.ring_nbytes)
            return pool

    def reset_pool(self) -> None:
        """Discard the pool (e.g. after a failed dispatch that may have consumed
        donated buffers) and rebuild it fresh, every slot free. This also
        voids every page the prefix cache holds references to — the scheduler
        clears its cache alongside (``_rebuild_pool``). The old pool's
        arrays go first: where a pool is most of the chip beside the weights
        (Granite's 64 slots of recurrent state: 5.97 GB) two of them do not
        fit."""
        self.pool = None
        self.pool = self._build_pool()

    # ------------------------------------------------------------- compiled fns
    def _chunk_fn(self):
        # every pool decodes on the dense view gathered once a chunk: on the
        # chip a kernel that gathered by page index inside its grid took
        # 1.07-2.6x the view's time a step (PERF.md section 6, PR 27)
        # one compile per (slots, pages, page, cap, chunk, sampling) key:
        # per-request page COUNTS are runtime table data, so mixed-length
        # traffic and page growth never mint a new key (sweep-pinned).
        key = ("serve_chunk_paged", self.slots, self.pool.total_pages,
               self.pool.page_size, self.cap, self.chunk_size,
               self.sampling)
        fns = self.engine._fns
        if key not in fns and self.block:
            # always on the dense view: every query of a block sees the same
            # rows, which is the decode kernel's operand
            chunk = build_block_decode_chunk(
                self.engine.module, self.engine._dequant, self._slot_select,
                self.chunk_size, kv_cap=self.cap,
                overlap=getattr(self.engine, "comm_overlap", None),
                with_stats=self.with_stats)
            fns[key] = jax.jit(_packed_block_chunk(chunk, self.block),
                               donate_argnums=(2,))          # pages
        if key not in fns:
            chunk = build_paged_decode_chunk(
                self.engine.module, self.engine._dequant,
                self._slot_select, self.chunk_size, kv_cap=self.cap,
                overlap=getattr(self.engine, "comm_overlap", None),
                with_stats=self.with_stats)
            fns[key] = jax.jit(_packed_chunk(chunk),
                               donate_argnums=(2,))          # pages
        return fns[key]

    def _prefill_fn(self, bucket: int):
        key = ("serve_prefill", bucket, self.cap, self.sampling)
        fns = self.engine._fns
        if key not in fns:
            engine = self.engine
            prefill_logits = build_prefill(engine.module, engine._dequant,
                                           overlap=getattr(engine,
                                                           "comm_overlap", None),
                                           with_stats=self.with_stats)
            prefill = _prefill(prefill_logits, self._slot_select,
                               engine.model_config, self.cap, engine.dtype)
            fns[key] = jax.jit(prefill, donate_argnums=(1,), keep_unused=True)
        return fns[key]

    def _one_cache(self):
        """The batch-1 cache a miss's prefill writes its results over: handed
        to it donated, handed back as its result, read by the pool's scatter.
        A prefill that allocated its 60-80 result arrays anew held the device
        idle 3-4 ms under its dispatch (PERF.md section 6, PR 50). Committed
        to the engine's mesh as a program's result is: an uncommitted operand
        would be another signature to jit."""
        if self._one is None:
            engine = self.engine
            self._one = jax.device_put(
                init_cache(engine.model_config, 1, self.cap, dtype=engine.dtype),
                NamedSharding(engine.mesh_spec.mesh, PartitionSpec()))
        return self._one

    def _suffix_prefill_fn_paged(self, bucket: int):
        """Cache-hit prefill: the slot's pages (shared prefix pages bound
        zero-copy at admission + its COW/fresh pages) are gathered into the
        dense batch-1 view INSIDE the dispatch, the suffix forward runs at
        the prefix offset, and ONLY the suffix rows go back to their
        page-mapped positions, one slab write a page (``write_view_rows``)
        — a shared page is rewritten with its own content at most. The
        POOL pages flow through and are donated; one compile per
        (pages, page, cap, suffix-bucket, sampling) key."""
        key = ("serve_suffix_prefill_paged", self.pool.total_pages,
               self.pool.page_size, self.cap, bucket, self.sampling)
        fns = self.engine._fns
        if key not in fns:
            engine = self.engine
            prefix_prefill = build_prefix_prefill(
                engine.module, engine._dequant,
                overlap=getattr(engine, "comm_overlap", None))
            suffix_prefill = _suffix_prefill(prefix_prefill,
                                             self._slot_select, self.cap)
            fns[key] = jax.jit(suffix_prefill, donate_argnums=(1,))
        return fns[key]

    def _spec_verify_fn(self, k: int):
        """Speculative one-pass verify: ONE compile per (slots, pages, page,
        cap, k, sampling) key, mirroring the chunk key. ``k`` is the static
        window width minus the cur-token row — per-slot draft LENGTHS are
        runtime data (``valid``), so shrunken proposals at the cap edge or a
        dry proposer never mint a new key. The pool pages are donated like
        every other decode dispatch."""
        key = ("serve_spec_verify_paged", self.slots, self.pool.total_pages,
               self.pool.page_size, self.cap, k, self.sampling)
        fns = self.engine._fns
        if key not in fns:
            fn = build_paged_spec_verify(
                self.engine.module, self.engine._dequant, kv_cap=self.cap,
                overlap=getattr(self.engine, "comm_overlap", None))
            fns[key] = jax.jit(fn, donate_argnums=(2,))   # pages
        return fns[key]

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(f"prompt length {prompt_len} exceeds max_prompt_len="
                         f"{self.max_prompt_len}")

    def _dispatch_watched(self, timed):
        """Run ``timed`` under the chunk watchdog (when armed): dispatch +
        host fetch on a worker thread, :class:`ChunkTimeoutError` on overrun.
        The first dispatch per executor pays its XLA compile inside the timed
        region — it is granted ``cold_chunk_grace_s`` so a routine compile
        doesn't read as a wedged replica (a genuinely hung compile still
        trips)."""
        if self.chunk_deadline_s is None:
            return timed()
        deadline = (self.chunk_deadline_s if self._warm_chunk
                    else max(self.chunk_deadline_s, self.cold_chunk_grace_s))
        box = {}

        def runner():
            try:
                box["out"] = timed()
            except BaseException as e:          # surfaced on the caller thread
                box["exc"] = e

        th = threading.Thread(target=runner, daemon=True,
                              name="ds-serve-chunk-watchdog")
        th.start()
        th.join(deadline)
        if th.is_alive():
            raise ChunkTimeoutError(deadline)
        if "exc" in box:
            raise box["exc"]
        return box["out"]

    # -------------------------------------------------------------------- steps
    def _dispatch(self, fn, args, program: str, bucket: int = 0, parent=None):
        """Call a compiled function under ``serving.dispatch``; returns its
        result and the span's end stamp. The first call this executor makes
        of ``fn`` is python tracing + lowering + compile (or a cache load) as
        the host sees it: kept as a ``setup.program`` phase. ``parent`` places
        the ring span when the caller is the watchdog's worker thread.
        ``seq`` is the pool's running count of dispatched programs: the
        device runs them in that order."""
        tracer = get_tracer()
        self.pool.programs += 1
        with tracer.span("serving.dispatch", parent=parent, program=program,
                         seq=self.pool.programs) as sp:
            if fn in self._called:
                out = fn(*args)
            else:
                self._called.add(fn)
                with tracer.phase("setup.program", program=program,
                                  bucket=bucket) as ph:
                    # the tokens of one forward: a prefill's bucket, a chunk's
                    # slot-batch (a verify's rows are not reckoned here)
                    tokens = {"prefill": bucket, "suffix_prefill": bucket,
                              "decode_chunk": self._chunk_tokens}.get(program)
                    if self.with_stats and tokens:
                        ph.set(moe_tile_rows=self.moe_tile_rows(tokens))
                    out = fn(*args)
        return out, sp.t1

    def prefill_into_slot(self, slot: int, prompt: np.ndarray, seed: int = 0,
                          prefix_len: int = 0, prefix_slab=None,
                          request_id: int = -1) -> Tuple[int, float]:
        """Prefill ``prompt`` (1-D int tokens) and scatter its KV into ``slot``.

        A miss is the stand-alone prefill (its batch-1 cache written over the
        last miss's, donated: nothing is allocated under the dispatch) and,
        after the first token's stamp, the pool's scatter of that cache.

        With ``prefix_len > 0`` (prefix-cache hit) the slot's first
        ``prefix_len`` rows are already there — shared pages bound at
        admission, or ``prefix_slab`` (a host-tier entry's numpy slab)
        restored here by the pool's donated scatter — and ONLY the suffix
        ``prompt[prefix_len:]`` is prefilled at cache offset ``prefix_len`` —
        the prompt bucket is chosen by **suffix** length, so a 128-token cached
        system prompt with an 8-token user turn pays an 8-bucket forward, not a
        256-bucket one. The ``serving.prefix_restore`` fault point (and the
        chaos ``when=restore`` hook) sits exactly between bind/restore and
        suffix prefill — the boundary whose donation discipline the soak guards.

        Returns ``(first_token, first_token_at)``: the ``time.monotonic``
        stamp at which the first token was on the host, which is the end of
        the ``serving.prefill`` / ``serving.suffix_prefill`` span — the
        scheduler's TTFT and the span share it. The spans nest (in the ring)
        under whatever span the calling thread has open, ``serving.admit``
        when the scheduler calls. A model that generates by diffusion over
        blocks yields no token here (``first_token`` is None): the prompt's
        whole blocks are committed, and the tokens left open its first block.
        """
        # lint: host-sync-ok (host prompt tokens, never a device value)
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        t = prompt.shape[0]
        tracer = get_tracer()
        self.engine._activate()
        if prefix_len and not self.kv_every_layer:
            raise NotImplementedError(
                "a prefix hit restores keys and values of every layer: a "
                "recurrent state after the prefix was not kept, and a layer "
                "without a cache has no rows to restore")
        if prefix_len:
            if not 0 < prefix_len < t:
                raise ValueError(f"prefix_len must be in (0, prompt_len={t}), "
                                 f"got {prefix_len}")
            suffix = prompt[prefix_len:]
            bucket = self.bucket_for(suffix.size)
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :suffix.size] = suffix
            fn = self._suffix_prefill_fn_paged(bucket)
            if prefix_slab is not None:
                # host-tier PROMOTE hit: the match lives as a spilled
                # dense slab, not as live pages — restore it into the
                # slot's (all-fresh, unshared) pages, paying one
                # host→device copy instead of a re-prefill. (A zero-copy
                # hit has nothing to restore here: its pages were bound
                # at admission, under ``serving.page_table``.)
                with tracer.span("serving.restore_prefix", slot=slot,
                                 prefix_len=int(prefix_len), promoted=1):
                    self.pool.promote_prefix(slot, prefix_slab, prefix_len)
            # the bind->prefill (promote: restore->prefill) seam: the chaos
            # when=restore hook and fault point fire exactly here, after the
            # pool/table was touched and before the suffix forward
            fault_point("serving.prefix_restore")
            if self._restore_kill is not None:
                cb, self._restore_kill = self._restore_kill, None
                cb()
                raise ReplicaKilledError("chaos: replica killed between "
                                         "prefix restore/bind and suffix "
                                         "prefill")
            with tracer.span("serving.suffix_prefill", request_id=request_id,
                             bucket=bucket, tokens=int(suffix.size),
                             prefix_len=int(prefix_len)) as sp:
                with tracer.span("serving.place_inputs",
                                 program="suffix_prefill", arrays=2):
                    ctl = np.empty(PRE_COLS + self.pool.max_pages, np.int32)
                    ctl[:PRE_COLS] = prefix_len, suffix.size, seed
                    ctl[PRE_COLS:] = self.pool.page_table[slot]
                    args = (self.engine.params, self.pool.caches,
                            *jax.device_put((ids, ctl)), self._base_key)
                handed = self.pool.caches
                try:
                    (out, caches), _ = self._dispatch(fn, args,
                                                      "suffix_prefill", bucket)
                    self.pool.caches = caches
                    with tracer.span("serving.fetch", program="suffix_prefill",
                                     arrays=1):
                        # lint: host-sync-ok (honest TTFT: first token synced on purpose)
                        tok0 = int(np.asarray(out)[0])
                except BaseException:
                    # the pool was the program's from its dispatch on: what
                    # it was handed is bound again, so that the buffers say
                    # what became of them (``PagedKVPool.consumed``: deleted
                    # once the dispatch took them, whatever failed after, a
                    # device error that surfaces at the fetch included)
                    self.pool.caches = handed
                    raise
            obs_profiler.tick("prefill")
            return tok0, sp.t1
        bucket = self.bucket_for(t)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :t] = prompt
        fn = self._prefill_fn(bucket)
        with tracer.span("serving.prefill", request_id=request_id,
                         bucket=bucket, tokens=int(t), prefix_len=0) as sp:
            with tracer.span("serving.place_inputs", program="prefill",
                             arrays=2):
                ctl = np.empty(2, np.int32)
                ctl[:] = t, seed
                args = (self.engine.params, self._one_cache(),
                        *jax.device_put((ids, ctl)), self._base_key)
            # the batch-1 cache is the program's from here (donated): after a
            # failure it is built anew, the pool was not touched
            self._one = None
            (out, one_caches), _ = self._dispatch(fn, args, "prefill", bucket)
            with tracer.span("serving.fetch", program="prefill", arrays=1):
                # lint: host-sync-ok (honest TTFT: first token synced on
                # purpose; the expert counts ride in the same array)
                out = np.asarray(out)
            self._one = one_caches
            tok0 = int(out[0])
            self.last_prefill_moe = None
            if self.with_stats:
                self.last_prefill_moe = out[1:]
                self.last_prefill_plan_rows = self.moe_plan_rows(bucket)
                sp.set(moe_assignments=int(out[1]),
                       moe_experts_touched=int(out[2]),
                       moe_tile_rows=self.moe_tile_rows(bucket))
            if self.block:
                # the whole blocks of the prompt are committed and no token
                # is yielded: what the head gave is not a token of this model
                tok0 = None
                sp.set(blocks_committed=t // self.block)
            if self.engine.model_config.prefill_stop is not None:
                # a prefill that stops early: the layers up to the stop ran
                # at every position of the bucket, those after it at one
                sp.set(positions_self=bucket, positions_cross=1)
        with tracer.span("serving.scatter_prefill"):
            self.pool.scatter_prefill(slot, one_caches)
        obs_profiler.tick("prefill")
        return tok0, sp.t1

    def moe_plan_rows(self, tokens: int) -> int:
        """Rows the expert layers' dispatch plans lay out in ONE forward of
        ``tokens`` tokens (``grouped_ffn.plan_rows``: the static worst case;
        the programs' own counts say what of it was live)."""
        cfg = self.engine.model_config
        return cfg.layer_kinds.count("E") * plan_rows(
            tokens * cfg.experts_per_token, cfg.held_experts[1],
            cfg.n_routed_experts)

    def moe_tile_rows(self, tokens: int) -> int:
        """The height of the expert kernel's tiles in a forward of ``tokens``
        tokens (``grouped_ffn.tile_rows``: static, from the assignments and
        the router's width)."""
        cfg = self.engine.model_config
        return tile_rows(tokens * cfg.experts_per_token, cfg.n_routed_experts)

    def run_chunk(self, toks: np.ndarray, lens: np.ndarray, active: np.ndarray,
                  remaining: np.ndarray, eos_ids: np.ndarray, seeds: np.ndarray,
                  steps: np.ndarray, block=None) -> ChunkResult:
        """One K-step compiled chunk over the slot-batch; pool pages are donated
        in and rebound from the output. All other state is host numpy.
        ``block``: a model that generates by blocks is also given each slot's
        block in flight (``ChunkResult.block``'s triple), K counts forwards
        and ``toks`` is not read.

        With ``chunk_deadline_s`` set, dispatch + host fetch run on a watchdog
        thread; an overrun raises :class:`ChunkTimeoutError` and the pool is left
        unusable (its buffers are inside the wedged dispatch) — callers recover
        via ``reset_pool``.
        """
        self.engine._activate()
        fn = self._chunk_fn()
        tracer = get_tracer()
        # snapshot the cache binding on THIS thread: if the watchdog abandons a
        # wedged chunk and the caller rebuilds the pool, the late-finishing
        # thread must keep donating the OLD buffers, never the fresh pool's
        caches_in = self.pool.caches
        S, K, B = self.slots, self.chunk_size, self.block
        head = ctl_head(B)
        if B:
            K = block_chunk_width(self.engine.model_config, K)
        with tracer.span("serving.place_inputs", program="decode_chunk",
                         arrays=1) as placed:
            # a fresh array a call: the CPU client may alias a host buffer for
            # the device array's life, and a chunk the watchdog abandoned
            # still holds its operand
            ctl = np.empty((S, head + self.pool.max_pages), np.int32)
            if B:
                blk, masked, skip = block
                ctl[:, CTL_COLS:CTL_COLS + B] = blk
                ctl[:, CTL_COLS + B] = masked @ (1 << np.arange(B))
                ctl[:, CTL_COLS + B + 1] = skip
            for col, host in ((CTL_TOK, np.reshape(toks, -1)), (CTL_LEN, lens),
                              (CTL_ACTIVE, active), (CTL_REMAINING, remaining),
                              (CTL_EOS, eos_ids), (CTL_SEED, seeds),
                              (CTL_STEPS, steps)):
                ctl[:, col] = host
            ctl[:, head:] = self.pool.page_table
            args = (self.engine.params, jax.device_put(ctl), caches_in,
                    self._base_key)
        (packed,), caches, stamps = self._dispatch_watched(
            self._timed(fn, args, "decode_chunk", "serving.chunk_compute"))
        self._warm_chunk = True
        obs_profiler.tick("decode_chunk")
        self.pool.caches = caches
        state = packed[:S, K:]
        in_flight = counts = None
        if B:
            tail = state[:, OUT_STEPS + 1:]
            in_flight = (tail[:, :B], (tail[:, B, None] >> np.arange(B)) & 1 != 0,
                         tail[:, B + 1])
            counts = packed[S, 2:5]
        return ChunkResult(buf=packed[:S, :K],
                           toks=state[:, OUT_TOK:OUT_TOK + 1],
                           lens=state[:, OUT_LEN],
                           active=state[:, OUT_ACTIVE] != 0,
                           remaining=state[:, OUT_REMAINING],
                           steps=state[:, OUT_STEPS],
                           elapsed=stamps[2] - placed.t1, stamps=stamps,
                           moe=packed[S, :2] if self.with_stats else None,
                           moe_plan_rows=self._chunk_plan_rows,
                           block=in_flight, block_counts=counts)

    def _timed(self, fn, args, program: str, fault: str):
        """The region a chunk's deadline must cover, as a callable for
        :meth:`_dispatch_watched`: injected stalls, compile + dispatch (hung
        compile), and host fetch (hung collective). ``fn`` returns a tuple
        that ends in the pool's caches; the callable returns ``(the other
        outputs as host arrays, caches, ChunkResult.stamps)``: a chunk
        has one other output, its packed result. It may run on the watchdog's
        worker thread, so its spans are handed the caller's open span."""
        tracer = get_tracer()
        parent = tracer.current()

        def timed():
            fault_point(fault)
            if self._stall_next > 0:
                stall, self._stall_next = self._stall_next, 0.0
                time.sleep(stall)
            (*outs, caches), dispatched = self._dispatch(
                fn, args, program, self.chunk_size, parent)
            with tracer.span("serving.fetch", parent=parent, program=program,
                             arrays=len(outs)) as fetched:
                for x in outs:
                    # lint: host-sync-ok (starts the copy and waits for
                    # nothing: every copy is under way before the first is
                    # read, so several outputs cost one wait; a chunk has one)
                    x.copy_to_host_async()
                # lint: host-sync-ok (chunk-boundary harvest: the scheduler
                # retires/admits between chunks and a verify round's accept
                # rule needs the window logits; this fetch IS the boundary)
                host = tuple(np.asarray(x) for x in outs)
            return host, caches, (dispatched, fetched.t0, fetched.t1)

        return timed

    def run_spec_round(self, toks: np.ndarray, lens: np.ndarray,
                       active: np.ndarray, remaining: np.ndarray,
                       eos_ids: np.ndarray, seeds: np.ndarray,
                       steps: np.ndarray, proposals: np.ndarray,
                       spec_lens: np.ndarray) -> SpecResult:
        """One draft-verify round over the slot-batch: a single target forward
        scores every slot's ``[cur_tok, draft...]`` window, the accept rule
        runs on the host, and commitment is a per-slot ``lens`` advance.

        ``proposals (S, k)`` holds each slot's draft tokens (pad beyond
        ``spec_lens[s]`` is arbitrary — pad rows are neither attended as
        committed state nor mirrored to pages, and their logits are never
        read). A slot with ``spec_lens == 0`` degenerates to a plain
        single-token decode step through the same compiled shape, which is
        how the cap-edge truncation and a dry proposer are handled — no
        separate fallback path exists to drift from.

        Same donation/watchdog/fault-surface as :meth:`run_chunk` (the
        ``serving.spec_verify`` fault point sits where ``chunk_compute``
        does); a failed dispatch leaves the pool unrecoverable and callers
        recover via ``reset_pool``."""
        self.engine._activate()
        S = int(toks.shape[0])
        proposals = np.asarray(proposals, np.int32).reshape(S, -1)
        k = int(proposals.shape[1])
        fn = self._spec_verify_fn(k)
        tracer = get_tracer()
        caches_in = self.pool.caches
        ids = np.concatenate(
            [np.asarray(toks, np.int32).reshape(-1, 1), proposals], axis=1)
        spec_lens = np.asarray(spec_lens, np.int32)
        valid = spec_lens + 1
        with tracer.span("serving.place_inputs", program="spec_verify") as placed:
            args = (self.engine.params, jnp.asarray(ids), caches_in,
                    jnp.asarray(self.pool.page_table),
                    jnp.asarray(lens, jnp.int32),
                    jnp.asarray(valid, jnp.int32),
                    jnp.asarray(active, bool))
            placed.set(arrays=len(args) - 2)    # all but params and caches
        # the mid-verify chaos/injection seam: after the proposer built the
        # window, before/through the verify dispatch + logits fetch
        (logits,), caches, stamps = self._dispatch_watched(
            self._timed(fn, args, "spec_verify", "serving.spec_verify"))
        self._warm_chunk = True
        obs_profiler.tick("spec_verify")
        self.pool.caches = caches

        buf = np.zeros((S, k + 1), np.int32)
        toks_out = np.asarray(toks, np.int32).copy()
        lens_out = np.asarray(lens, np.int32).copy()
        active_out = np.asarray(active, bool).copy()
        remaining_out = np.asarray(remaining, np.int32).copy()
        steps_out = np.asarray(steps, np.int32).copy()
        proposed = accepted = 0
        for s in range(S):
            if not active_out[s]:
                continue
            L = int(spec_lens[s])
            proposed += L
            emitted, acc = accept_tokens(
                proposals[s, :L], logits[s, :L + 1], sampling=self.sampling,
                base_key=self._base_key, seed=int(seeds[s]),
                step0=int(steps[s]))
            accepted += acc
            # chunk semantics on the emitted stream: clamp to the decode
            # budget, truncate at the first EOS (inclusive), then commit
            r = int(remaining_out[s])
            if len(emitted) > r:
                emitted = emitted[:r]
            eos = int(eos_ids[s])
            if eos >= 0 and eos in emitted:
                emitted = emitted[:emitted.index(eos) + 1]
            e = len(emitted)
            buf[s, :e] = emitted
            toks_out[s] = emitted[-1]
            lens_out[s] += e
            steps_out[s] += e
            remaining_out[s] = r - e
            if remaining_out[s] <= 0 or (eos >= 0 and emitted[-1] == eos):
                active_out[s] = False
        return SpecResult(buf=buf, toks=toks_out.reshape(-1, 1),
                          lens=lens_out, active=active_out,
                          remaining=remaining_out, steps=steps_out,
                          elapsed=stamps[2] - placed.t1, stamps=stamps,
                          proposed=proposed, accepted=accepted)
