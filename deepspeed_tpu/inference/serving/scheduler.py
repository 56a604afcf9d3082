"""Continuous-batching scheduler: bounded queue → slot-recycled decode chunks.

The serving loop above ``InferenceEngine``'s single-call ``generate``: requests
arrive at any time, wait in a bounded FIFO queue, are prefilled into a free KV
slot between decode chunks, and decode alongside whatever else is in flight. A
finished sequence releases its slot at the next chunk boundary and a pending
prompt is prefilled into it while the other slots keep decoding — continuous
batching in the sense of Orca/vLLM, built from two compiled shapes (bucketed
prefill + K-step chunk) instead of a token-level iteration.

Semantics:

- **admission control** — ``submit`` validates prompt/budget against the pool cap
  up front (fail fast, never poison the queue);
- **backpressure** — a full queue raises :class:`QueueFullError` carrying a
  ``retry_after`` hint: the request is *rejected*, never silently dropped;
- **deadlines / cancellation** — checked at every chunk boundary, for queued and
  in-flight requests alike; an expired/cancelled in-flight request keeps its
  partial tokens and frees its slot;
- **transient faults** — prefill and chunk dispatch run under
  ``retry_with_backoff`` with ``fault_point`` sites ``serving.prefill`` /
  ``serving.decode_chunk``, the same injection substrate as the checkpoint ring.
  A dispatch that still fails with one of :data:`TRANSIENT_FAULTS` fails its
  requests and the loop keeps serving; any other exception — a compile
  refusal, an HBM ``RESOURCE_EXHAUSTED``, a trace-time error — is
  deterministic, so after the same clean-up it propagates out of ``step()``.

Token parity: greedy decode through the scheduler is bit-identical to per-request
``InferenceEngine.generate`` (same prefill math, same per-step decode math —
shared via ``decode_fns``). Sampled decode is deterministic per request ``seed``
and independent of slot placement/co-batching (per-slot key streams), but is not
bit-identical to ``generate``'s batched key stream.

Threading: the scheduler is single-threaded by design — drive it with ``step()``
/ ``run()`` from one thread (the loadgen and ``deepspeed-serve`` do exactly
that). ``RequestHandle.cancel`` only sets a flag and is safe to call from
anywhere.
"""

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Deque, List, Optional

import numpy as np

from ...observability.trace import CAT_SERVING, get_tracer
from ...ops.attention.decode import live_rows
from ...utils.fault_injection import fault_point, retry_with_backoff
from ...utils.logging import logger
from ..decode_fns import open_block
from ..speculative import SpeculativeConfig, make_proposer
from .executor import (ChunkedDecodeExecutor, ChunkTimeoutError,
                       ReplicaKilledError)
from .prefix_cache import PrefixCache, PrefixCacheConfig
from .telemetry import ServingTelemetry, adaptive_retry_after


#: Dispatch failures the serving loop outlives: I/O errors (the class
#: ``retry_with_backoff`` retries), a chunk that overran its watchdog deadline,
#: and the chaos kill hook. Everything else would fail every later dispatch
#: the same way, so it is re-raised to whoever drives ``step()``.
TRANSIENT_FAULTS = (OSError, ChunkTimeoutError, ReplicaKilledError)


class RequestState(Enum):
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    CANCELLED = "cancelled"
    EXPIRED = "expired"
    EVICTED = "evicted"     # replica death/drain: partial tokens kept for retry


class QueueFullError(RuntimeError):
    """Backpressure: the admission queue is at capacity. ``retry_after`` is the
    scheduler's hint (seconds) for when to resubmit."""

    def __init__(self, retry_after: float):
        super().__init__(f"serving queue full; retry after {retry_after:.3f}s")
        self.retry_after = float(retry_after)


@dataclass
class ServingConfig:
    slots: int = 2                      # concurrent sequences in the slot-batch
    chunk_size: int = 8                 # decode steps per compiled chunk
    max_queue: int = 16                 # admission queue bound (backpressure)
    max_seq_len: Optional[int] = None   # KV cap; default engine max_out_tokens
    max_prompt_len: Optional[int] = None
    default_max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    retry_after_s: float = 0.25         # backpressure hint FLOOR (the emitted
    #   hint is load-adaptive: queue depth / observed drain rate)
    retry_after_max_s: float = 8.0
    transient_retries: int = 2          # retry_with_backoff budget per dispatch
    retry_base_delay: float = 0.02
    base_seed: int = 0
    chunk_deadline_s: Optional[float] = None   # per-chunk watchdog (None = off)
    prefix_cache: Optional[PrefixCacheConfig] = None   # None = cache off
    # the one KV pool: global fixed-size pages behind per-slot page tables,
    # page-count admission, zero-copy refcounted prefix sharing. The field
    # has one value and nothing reads it; it stays until the benchmark's
    # serve_closed.py stops passing it (ROADMAP D14)
    kv_pool: str = "paged"
    kv_page_size: int = 16
    kv_total_pages: Optional[int] = None   # HBM budget in pages (None = every
    #   slot's whole cap: slots * ceil(cap/page) + the null page)
    # speculative decoding: every decode chunk becomes ONE draft-propose /
    # one-pass-verify round (greedy output stays bit-identical; sampled keeps
    # the per-slot key-stream distribution exactly — see inference.speculative)
    speculate: bool = False
    spec_k: int = 4                     # draft tokens per verify window
    spec_proposer: str = "ngram"        # "ngram" | "draft_model"
    spec_ngram_max: int = 4
    spec_ngram_min: int = 1
    spec_draft_engine: object = None    # tiny engine for "draft_model"

    def __post_init__(self):
        if self.kv_pool != "paged":
            raise ValueError(
                f"kv_pool={self.kv_pool!r}: the slot-row pool was removed; "
                "'paged' is the one KV pool")


def validate_admission(prompt, max_new_tokens: Optional[int],
                       default_max_new: int, max_prompt_len: int, cap: int):
    """Shared admission contract (scheduler + router): normalize the prompt and
    budget, raise ``ValueError`` for anything that could never fit. One owner —
    the router's pre-check must never drift from what a replica will accept."""
    prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
    max_new = int(default_max_new if max_new_tokens is None else max_new_tokens)
    if prompt.size < 1:
        raise ValueError("prompt must contain at least one token")
    if max_new < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
    if prompt.size > max_prompt_len:
        raise ValueError(f"prompt length {prompt.size} exceeds "
                         f"max_prompt_len={max_prompt_len}")
    if prompt.size + max_new > cap:
        raise ValueError(f"prompt ({prompt.size}) + max_new_tokens "
                         f"({max_new}) exceeds KV capacity {cap}")
    return prompt, max_new


@dataclass
class RequestHandle:
    """Caller's view of a submitted request (filled in by the scheduler)."""
    id: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_token_id: Optional[int]
    deadline_s: Optional[float]
    seed: int
    arrival: float
    state: RequestState = RequestState.QUEUED
    tokens: List[int] = field(default_factory=list)
    ttft: Optional[float] = None        # queue wait + prefill, seconds
    tpot: Optional[float] = None        # seconds per decode token
    finish_reason: Optional[str] = None  # eos | length | cancelled | deadline
    slot: Optional[int] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    prefix_hit_tokens: int = 0          # prefill tokens skipped via the
    #   prefix cache (0 = cold miss); loadgen splits TTFT on this
    _cancel: bool = False
    _span: Optional[object] = None      # request-scoped trace root (OpenSpan)

    @property
    def trace_id(self) -> Optional[str]:
        return self._span.trace_id if self._span is not None else None

    def cancel(self) -> None:
        self._cancel = True

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.EXPIRED, RequestState.EVICTED)

    def result(self) -> np.ndarray:
        """Generated tokens (EOS included when emitted; partial if cancelled)."""
        return np.asarray(self.tokens, dtype=np.int32)

    def output_ids(self) -> np.ndarray:
        return np.concatenate([self.prompt.astype(np.int32), self.result()])


class ContinuousBatchingScheduler:
    """Admission queue + slot tables driving a :class:`ChunkedDecodeExecutor`."""

    def __init__(self, engine, config: Optional[ServingConfig] = None,
                 monitor=None):
        self.config = cfg = config or ServingConfig()
        cap = int(cfg.max_seq_len or engine._config.max_out_tokens)
        # it is the model that generates by diffusion over blocks, not a
        # serving switch: block length, steps, order and mask token are the
        # model configuration's
        self.block = block = int(engine.model_config.gen_block_length)
        if block and cfg.speculate:
            raise ValueError(
                "speculate with this model: it generates by diffusion over "
                f"blocks of {block}, so a forward yields no next token for a "
                "draft to be checked against and a block's rows count only "
                "once it is committed; set speculate=False")
        if block and cfg.prefix_cache is not None and cfg.prefix_cache.enabled \
                and cfg.kv_page_size % block:
            raise ValueError(
                f"prefix_cache.enabled with kv_page_size={cfg.kv_page_size} "
                f"and a model that generates in blocks of {block}: a cached "
                "prefix is shared by whole pages and a block is committed "
                "whole, so the block length must divide the page size")
        self.executor = ChunkedDecodeExecutor(
            engine, slots=cfg.slots, cap=cap, chunk_size=cfg.chunk_size,
            do_sample=cfg.do_sample, temperature=cfg.temperature,
            top_k=cfg.top_k, top_p=cfg.top_p,
            max_prompt_len=cfg.max_prompt_len, base_seed=cfg.base_seed,
            chunk_deadline_s=cfg.chunk_deadline_s,
            kv_page_size=cfg.kv_page_size, kv_total_pages=cfg.kv_total_pages)
        self.cap = cap
        if not self.executor.kv_every_layer:
            # both rest on "a sequence's state up to token n is the first n
            # cache rows of every layer": a prefix hit restores rows, a
            # rejected draft rewinds cache_len. A recurrent state is one array
            # per slot that every token overwrites, so neither holds until the
            # pool keeps state snapshots; and the movers index every layer's
            # keys and values, which an expert layer does not keep.
            stateful = engine.model_config.slot_state_layers
            if stateful:
                why = (f"the per-slot state of its {' and '.join(stateful)} "
                       "layers is overwritten by every token and was not kept "
                       "(needs state snapshots)")
            elif engine.model_config.latent_layers:
                why = ("its latent-attention layers keep one latent row a "
                       "token, which only the one-token decode attends (in "
                       "absorbed form): a hit's suffix prefill and the verify "
                       "round have no such form yet")
            else:
                why = ("some of its layers keep no keys and values (expert "
                       "layers), which the prefix and slab movers read from "
                       "every layer")
            if cfg.prefix_cache is not None and cfg.prefix_cache.enabled:
                raise ValueError(
                    "prefix_cache.enabled with this model: a prefix hit "
                    f"restores every layer's keys and values, and {why}; "
                    "set prefix_cache.enabled=False")
            if cfg.speculate:
                raise ValueError(
                    "speculate with this model: a rejected draft rewinds "
                    f"cache_len over every layer's keys and values, and {why}; "
                    "set speculate=False")
        self.proposer = None
        self._spec_cfg: Optional[SpeculativeConfig] = None
        if cfg.speculate:
            self._spec_cfg = SpeculativeConfig(
                k=cfg.spec_k, proposer=cfg.spec_proposer,
                ngram_max=cfg.spec_ngram_max, ngram_min=cfg.spec_ngram_min,
                draft_engine=cfg.spec_draft_engine)
            self.proposer = make_proposer(self._spec_cfg)
        self.telemetry = ServingTelemetry(monitor)
        self._tracer = get_tracer()
        self.prefix_cache: Optional[PrefixCache] = None
        if cfg.prefix_cache is not None and cfg.prefix_cache.enabled:
            self.prefix_cache = PrefixCache(cfg.prefix_cache)
            # LRU eviction of a page entry decrefs against the CURRENT
            # pool (any pool swap clears the cache first, so an entry's
            # pages always belong to the pool this resolves to)
            self.prefix_cache.page_release = \
                lambda pages: self.executor.pool.release_shared(pages)
            # spill path: gather an evicted entry's pages as a dense host
            # slab (the gather_prefix wire format) before the refs drop
            self.prefix_cache.page_gather = \
                lambda pages, rows: self.executor.pool.gather_pages(
                    pages, rows)
        self.queue: Deque[RequestHandle] = deque()
        self._ids = itertools.count()
        S = cfg.slots
        self._slot_req: List[Optional[RequestHandle]] = [None] * S
        self._toks = np.zeros(S, np.int32)
        self._lens = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._remaining = np.zeros(S, np.int32)
        self._eos = np.full(S, -1, np.int32)
        self._seeds = np.zeros(S, np.int32)
        self._steps = np.zeros(S, np.int32)
        # generation by blocks: each slot's block in flight (its tokens, which
        # are still masked, how many the prompt gave); carried as state and
        # never read back from the ids, since a prompt may hold the mask token
        self._blk = np.zeros((S, max(block, 1)), np.int32)
        self._masked = np.ones((S, max(block, 1)), bool)
        self._skip = np.zeros(S, np.int32)
        self._step_idx = 0
        # stalled deliveries: prefills done so far, and per slot how many had
        # been done at its stream's last delivery (or its own first token)
        self._prefills_done = 0
        self._prefills_seen = np.zeros(S, np.int64)
        # the chunk cycle: the last chunk's fetch return (None once a step
        # ran no chunk: an empty server's wait is no turnaround) and the
        # seconds of ``serving.admit`` since, which a turnaround leaves out
        self._fetched_at: Optional[float] = None
        self._admit_s = 0.0

    # ---------------------------------------------------------------- frontend
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None, seed: int = 0,
               trace_ctx=None) -> RequestHandle:
        """Enqueue a request. Raises ``ValueError`` on inadmissible shapes and
        :class:`QueueFullError` (with ``retry_after``) under backpressure.

        ``trace_ctx`` (an ``observability.trace.SpanContext`` or ``None``)
        joins this request's spans to a propagated parent trace — the router
        passes its dispatch-attempt context here, and the subprocess replica
        deserializes one off the JSONL pipe, so replica-side spans land on the
        same trace id as the frontend's."""
        prompt, max_new = validate_admission(
            prompt, max_new_tokens, self.config.default_max_new_tokens,
            self.executor.max_prompt_len, self.cap)
        if len(self.queue) >= self.config.max_queue:
            self.telemetry.on_rejected()
            raise QueueFullError(self.retry_after_hint())
        handle = RequestHandle(
            id=next(self._ids), prompt=prompt, max_new_tokens=max_new,
            eos_token_id=eos_token_id, deadline_s=deadline_s, seed=int(seed),
            arrival=time.monotonic())
        handle._span = self._tracer.begin(
            "replica_request", cat=CAT_SERVING, ctx=trace_ctx,
            t0=handle.arrival,
            attrs={"request_id": handle.id, "prompt_tokens": int(prompt.size),
                   "max_new_tokens": max_new})
        self.queue.append(handle)
        return handle

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    def retry_after_hint(self, now: Optional[float] = None) -> float:
        """Load-adaptive backpressure hint (see
        :func:`~.telemetry.adaptive_retry_after`)."""
        cfg = self.config
        return adaptive_retry_after(cfg.retry_after_s, cfg.retry_after_max_s,
                                    len(self.queue), cfg.max_queue,
                                    self.telemetry.drain_rate(now))

    @property
    def active_requests(self) -> List[RequestHandle]:
        return [h for h in self._slot_req if h is not None]

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(h is not None for h in self._slot_req)

    # ------------------------------------------------------------------- loop
    def step(self) -> bool:
        """One scheduler iteration: sweep deadlines/cancellations, admit pending
        prompts into free slots, run one decode chunk, retire finished slots.
        Returns True when any request made progress."""
        tracer = self._tracer
        self._step_idx += 1
        with tracer.span("serving.step", step=self._step_idx,
                         queue_depth=len(self.queue),
                         active_slots=int(np.count_nonzero(self._active))):
            with tracer.span("serving.sweep"):
                now = time.monotonic()
                self._sweep_queue(now)
                self._sweep_running(now)
            admitted = self._admit()
            decoded = self._decode_chunk()
            with tracer.span("serving.telemetry"):
                pool = self.executor.pool
                self.telemetry.on_step(
                    len(self.queue), pool.occupancy,
                    prefix_stats=(None if self.prefix_cache is None
                                  else self.prefix_cache.stats()),
                    paged_stats=pool.stats())
        return admitted or decoded

    def run(self, max_steps: int = 100000) -> dict:
        """Drive ``step()`` until queue and slots drain; returns the telemetry
        snapshot."""
        steps = 0
        while self.busy and steps < max_steps:
            self.step()
            steps += 1
        return self.telemetry.snapshot()

    # ------------------------------------------------------------ prefix cache
    def _insert_prefix(self, handle: RequestHandle, slot: int) -> None:
        """Index the slot's prompt KV in the trie under the full prompt token
        path: SHARE the slot's prompt-covering pages (refcount bump —
        zero-copy, no device gather at all)."""
        if self.prefix_cache is None:
            return
        with self._tracer.span("serving.prefix_insert"):
            P = int(handle.prompt.size)
            if P < self.prefix_cache.config.min_insert_tokens:
                self.prefix_cache.insert_skipped += 1
                return
            if self.prefix_cache.contains(handle.prompt):
                return               # resident (LRU refreshed): same tokens ⇒
                #   the same rows, nothing to share again
            pool = self.executor.pool
            nbytes = pool.pages_for(P) * pool.page_nbytes
            if nbytes > self.prefix_cache.config.max_bytes:
                self.prefix_cache.insert_skipped += 1
                return
            pages = pool.share_prefix(slot, P)
            if not self.prefix_cache.insert_pages(handle.prompt, pages,
                                                  nbytes):
                pool.release_shared(pages)   # resident/refused: drop our refs

    def _retire_prefix(self, handle: RequestHandle, slot: int) -> None:
        """Completion-path insert hook: runs for every request leaving a slot
        through a healthy retirement (finished / cancelled / expired — the
        prefill was paid, so its prompt KV is worth keeping). Eviction paths
        (``evict_all``) deliberately skip it: the pool may be poisoned there.
        """
        if (self.prefix_cache is not None
                and self.prefix_cache.config.insert_on == "completion"):
            self._insert_prefix(handle, slot)

    @property
    def prefix_hit_rate(self) -> float:
        """ADMISSION-level hit rate (successful prefills) — everything named
        ``prefix_hit_rate`` (this, the monitor tags, the snapshot) derives
        from the same counters; the trie's lookup-level rate (which also
        counts failed/retried admissions) is only in
        :meth:`prefix_cache_report`."""
        if self.prefix_cache is None:
            return 0.0
        t = self.telemetry
        n = t.prefix_hits + t.prefix_misses
        return t.prefix_hits / n if n else 0.0

    def prefix_cache_report(self) -> dict:
        """``weight_stream_report()``-style summary of the prefix cache: hit
        accounting, resident slab bytes against budget, and the modeled
        prefill-compute saving (skipped prefill tokens / total prompt tokens
        seen). The ``hits``/``misses``/``hit_rate`` here are the trie's
        LOOKUP-level counters (they also tick on admissions that later fail
        and retry) — everything published as ``prefix_hit_rate`` elsewhere is
        admission-level."""
        if self.prefix_cache is None:
            return {"enabled": False}
        s = self.prefix_cache.stats()
        seen = max(1, s["lookup_tokens"])
        return {
            "enabled": True,
            **s,
            "budget_fill": s["cached_bytes"] / max(1, s["max_bytes"]),
            "prefill_tokens_skipped_frac": s["hit_tokens"] / seen,
        }

    def _rebuild_pool(self) -> None:
        """Discard + rebuild the KV pool after a failure that may have
        consumed donated buffers. The prefix cache's shared pages live INSIDE
        the discarded buffers, so its device rung is dropped with it (without
        spilling — gathering from a poisoned pool is not trustworthy) — the
        honest cost of zero-copy sharing. Host-rung entries are independent
        numpy slabs and survive to serve promote hits against the rebuilt
        pool."""
        if self.prefix_cache is not None:
            self.prefix_cache.drop_device()
        self.executor.reset_pool()

    # --------------------------------------------------------------- eviction
    def evict_all(self, reason: str = "evicted") -> List[RequestHandle]:
        """Evict every queued and in-flight request with its generated-so-far
        prefix: each handle finalizes as ``EVICTED`` (tokens kept), the slot
        tables are cleared and the KV pool rebuilt.

        This is the checkpointless-retry hook the router relies on: an evicted
        handle re-enqueues elsewhere as ``prompt + tokens`` with the remaining
        budget, and greedy decode continues prefix-consistently — the request,
        not a checkpoint, is the unit of recovery on the inference path.
        """
        now = time.monotonic()
        out: List[RequestHandle] = []
        for h in self.queue:
            self._finalize(h, RequestState.EVICTED, reason, now)
            out.append(h)
        self.queue.clear()
        for slot, h in enumerate(self._slot_req):
            if h is None:
                continue
            self._finalize(h, RequestState.EVICTED, reason, now)
            out.append(h)
            self._slot_req[slot] = None
        self._clear_slot_state()
        # rebuild rather than per-slot zero-fill: on the death path the old
        # buffers may be inside a failed/wedged dispatch and cannot be trusted
        self._rebuild_pool()
        return out

    # ----------------------------------------------------------------- sweeps
    def _expired(self, handle: RequestHandle, now: float) -> bool:
        return (handle.deadline_s is not None
                and now - handle.arrival > handle.deadline_s)

    def _sweep_queue(self, now: float) -> None:
        kept = deque()
        for h in self.queue:
            if h._cancel:
                self._finalize(h, RequestState.CANCELLED, "cancelled", now)
            elif self._expired(h, now):
                self._finalize(h, RequestState.EXPIRED, "deadline", now)
            else:
                kept.append(h)
        self.queue = kept

    def _sweep_running(self, now: float) -> None:
        for slot, h in enumerate(self._slot_req):
            if h is None:
                continue
            if h._cancel:
                self._retire_prefix(h, slot)   # prefill was paid: keep its KV
                self._finalize(h, RequestState.CANCELLED, "cancelled", now)
                self._release(slot)
            elif self._expired(h, now):
                self._retire_prefix(h, slot)
                self._finalize(h, RequestState.EXPIRED, "deadline", now)
                self._release(slot)

    # -------------------------------------------------------------- admission
    def _admit(self) -> bool:
        admitted = False
        tracer = self._tracer
        while self.queue:
            pool = self.executor.pool    # re-read: a failed hit-prefill below
            head = self.queue[0]         # rebuilds the pool mid-loop
            # page-count admission: the pool admits when the request's OWN
            # reservation (prompt + budget, page-granular) fits — not when a
            # whole cap of rows frees up. Conservative (all-fresh) check: a
            # prefix hit can only need fewer pages. FIFO: a head that
            # doesn't fit waits.
            need_tokens = int(head.prompt.size) + int(head.max_new_tokens)
            if self.proposer is not None:
                # speculation headroom: a verify window writes up to spec_k
                # draft rows past the committed length before the accept rule
                # trims them — admit only when those rows fit too, so a
                # mid-stream round never lands on an unreserved page. Clamped
                # to the cap: the per-slot proposal limit already shrinks the
                # window near the cap edge.
                need_tokens = min(need_tokens + self._spec_cfg.k, self.cap)
            if not pool.can_admit(need_tokens):
                # admission-pressure eviction: cached prefixes pin
                # real pool pages, so a full free list trades the coldest
                # cached prefixes for admission capacity before giving up —
                # a waiting request always outranks a cold cached prefix.
                # Only entries holding a refcount-1 page are worth dropping:
                # evicting one whose pages live slots still bind frees
                # nothing, and would just empty the cache for no capacity.
                # Peek the head's own prefix first (stats/LRU-free): its
                # matching entry must survive the sweep — evicting it would
                # trade the head's zero-copy hit for a full prefill — and a
                # hit shrinks the fresh-page need to the unshared suffix.
                # ... but ONLY when pages are the shortage: evicting cached
                # prefixes frees pages, never slots, so a queue blocked on a
                # full slot set must not drain the cache for zero gain.
                matched_hint = 0
                if self.prefix_cache is not None and pool.free_slots > 0:
                    matched_hint, keep = self.prefix_cache.peek(head.prompt)
                    if keep is not None and keep.pages is None:
                        # host-rung match: the promote path acquires all-fresh
                        # pages, so the hint must not shrink the page need
                        matched_hint = 0
                    frees = lambda e: e is not keep and \
                        e.pages is not None and any(  # noqa: E731
                            pool.page_ref(p) == 1 for p in e.pages)
                    while not pool.can_admit(need_tokens,
                                             matched=matched_hint) and \
                            self.prefix_cache.evict_lru(frees):
                        pass
                if not pool.can_admit(need_tokens, matched=matched_hint):
                    break
            handle = self.queue.popleft()
            waited = time.monotonic() - handle.arrival
            with tracer.span("serving.admit", parent=handle._span,
                             request_id=handle.id,
                             queue_wait_ms=round(waited * 1e3, 3),
                             prompt_tokens=int(handle.prompt.size)) as span:
                tracer.record_span("queue_wait", handle._span, handle.arrival,
                                   span.t0)
                outcome = self._admit_one(handle, need_tokens, span)
            self._admit_s += span.t1 - span.t0
            if outcome is None:     # no slot after all: the head waits
                break
            admitted = admitted or outcome
        return admitted

    def _admit_one(self, handle: RequestHandle, need_tokens: int,
                   span) -> Optional[bool]:
        """The work of one admission, inside its ``serving.admit`` span:
        prefix lookup, slot and pages, the prefill, the slot made live.
        True = admitted (or finished at its first token), False = its prefill
        failed and the request with it, None = no slot (requeued)."""
        cfg = self.config
        tracer = self._tracer
        pool = self.executor.pool
        programs = pool.programs
        matched, entry = 0, None
        if self.prefix_cache is not None:
            with tracer.span("serving.prefix_lookup") as lk:
                matched, entry = self.prefix_cache.lookup(handle.prompt)
                lk.set(hit=int(entry is not None), matched_tokens=int(matched))
        if entry is not None and entry.pages is not None:
            # zero-copy hit: bind the shared prefix pages into the fresh
            # slot's table (refcount bump + one COW boundary page)
            slot = pool.acquire(need_tokens, prefix_pages=entry.pages,
                                matched=matched)
        else:
            # miss, or host-rung PROMOTE hit (entry with a spilled numpy
            # slab): all-fresh pages; the promote restores the slab into
            # them inside prefill_into_slot
            slot = pool.acquire(need_tokens)
        if slot is None:       # can_admit is conservative, so only a racing
            self.queue.appendleft(handle)              # caller could land here
            span.set(outcome="requeued")
            return None
        prefix_len = int(matched) if entry is not None else 0
        span.set(slot=slot, prefix_len=prefix_len)

        def attempt():
            fault_point("serving.prefill")
            if entry is not None:
                return self.executor.prefill_into_slot(
                    slot, handle.prompt, handle.seed, prefix_len=matched,
                    prefix_slab=entry.slab, request_id=handle.id)
            return self.executor.prefill_into_slot(
                slot, handle.prompt, handle.seed, request_id=handle.id)

        def no_retry_on_a_consumed_pool(_, exc):
            if pool.consumed:       # a second try would run on deleted buffers
                raise exc

        try:
            tok0, first_token_at = retry_with_backoff(
                attempt, retries=cfg.transient_retries,
                base_delay=cfg.retry_base_delay,
                on_retry=no_retry_on_a_consumed_pool)
        except Exception as e:
            span.set(outcome="error")
            # retry budget exhausted: fail THIS request and (for a
            # transient fault) keep serving — the slot must not leak and
            # the loop must not die with the queue still holding live
            # requests
            logger.error(f"[serving] prefill failed for request "
                         f"{handle.id}: {type(e).__name__}: {e}")
            now = time.monotonic()
            self._finalize(handle, RequestState.CANCELLED, "error", now)
            if pool.consumed:
                # a program of the admission had the pool DONATED to it and
                # took it along (a hit's suffix prefill, a copy or a restore
                # before it, a miss's scatter): same recovery as a failed
                # decode chunk — fail the in-flight requests, rebuild the
                # pool, keep serving (a router retries them elsewhere)
                logger.error("[serving] the failed admission consumed the KV "
                             "pool; failing "
                             f"{sum(h is not None for h in self._slot_req)}"
                             " in-flight request(s) and rebuilding it")
                self._fail_in_flight(now)
                self._rebuild_pool()
            else:           # nothing had taken the pool yet: it stands
                self._release(slot)
            if not isinstance(e, TRANSIENT_FAULTS):
                raise
            return False
        # the compiled programs it dispatched: a miss's prefill and scatter;
        # a hit's suffix prefill and, before it, a boundary page's copy or a
        # promoted slab's restore
        span.set(outcome="ok", programs=pool.programs - programs)
        handle.state = RequestState.RUNNING
        handle.slot = slot
        if tok0 is not None:
            handle.tokens.append(int(tok0))
            # the stamp at which the token was on the host: the end of the
            # executor's prefill span
            handle.first_token_at = first_token_at
            handle.ttft = first_token_at - handle.arrival
        handle.prefix_hit_tokens = prefix_len
        self._prefills_done += 1
        self._prefills_seen[slot] = self._prefills_done
        self.telemetry.on_moe(self.executor.last_prefill_moe,
                              self.executor.last_prefill_plan_rows)
        self.telemetry.on_prefix(entry is not None,
                                 handle.prefix_hit_tokens,
                                 enabled=self.prefix_cache is not None)
        if (self.prefix_cache is not None
                and self.prefix_cache.config.insert_on == "prefill"):
            self._insert_prefix(handle, slot)
        eos = -1 if handle.eos_token_id is None else int(handle.eos_token_id)
        if tok0 is None:
            # generation by blocks: the prefill committed the prompt's whole
            # blocks and yielded no token; the tokens left open the first
            # block, unmasked, and the slot starts it at the next chunk. The
            # first token is stamped when a chunk has brought it to the host
            whole = int(handle.prompt.size) // self.block * self.block
            self._blk[slot], self._masked[slot], self._skip[slot] = open_block(
                self.executor.engine.model_config, handle.prompt[whole:])
            tok0, committed, emitted = 0, whole, 0
        elif tok0 == eos or handle.max_new_tokens == 1:
            self._retire_prefix(handle, slot)
            self._finalize(handle, RequestState.FINISHED,
                           "eos" if tok0 == eos else "length",
                           time.monotonic())
            self._release(slot)
            return True
        else:
            committed, emitted = handle.prompt.size, 1   # token 0 came from prefill
        self._slot_req[slot] = handle
        self._toks[slot] = tok0
        self._lens[slot] = committed
        self._active[slot] = True
        self._remaining[slot] = handle.max_new_tokens - emitted
        self._eos[slot] = eos
        self._seeds[slot] = handle.seed
        self._steps[slot] = emitted
        return True

    def _fail_in_flight(self, now: float) -> None:
        """A dispatch that had the pool's buffers donated to it died: every
        in-flight request fails with it and the slot tables are cleared."""
        for slot, h in enumerate(self._slot_req):
            if h is not None:
                self._finalize(h, RequestState.CANCELLED, "error", now)
                self._slot_req[slot] = None
        self._clear_slot_state()

    def _clear_slot_state(self) -> None:
        self._active[:] = False
        self._remaining[:] = 0
        self._steps[:] = 0
        self._eos[:] = -1

    # ----------------------------------------------------------------- decode
    def _decode_chunk(self) -> bool:
        prev_fetched, self._fetched_at = self._fetched_at, None
        admit_s, self._admit_s = self._admit_s, 0.0
        if not self._active.any():
            return False
        cfg = self.config
        tracer = self._tracer
        steps_before = self._steps.copy()
        streams = [(slot, h) for slot, h in enumerate(self._slot_req)
                   if h is not None and self._active[slot]]
        chunk_idx = self.telemetry._chunk_idx + 1
        spec = self.proposer is not None
        width = self._spec_cfg.k + 1 if spec else cfg.chunk_size
        slot_steps = width * len(streams)

        def attempt():
            fault_point("serving.decode_chunk")
            if spec:
                return self._spec_round()
            in_flight = {"block": (self._blk, self._masked, self._skip)} \
                if self.block else {}
            return self.executor.run_chunk(
                self._toks, self._lens, self._active, self._remaining,
                self._eos, self._seeds, self._steps, **in_flight)

        # the span stays on this thread whether or not the chunk watchdog
        # moves the dispatch to its worker; the executor's place_inputs /
        # dispatch / fetch nest under it
        at_start = dict(chunk=chunk_idx, active_slots=len(streams),
                        request_ids=" ".join(str(h.id) for _, h in streams),
                        slot_steps_run=slot_steps)
        if not spec and not self.block:
            # the cache rows a step of this chunk's attention walks at most:
            # the batch's longest length at the chunk's end, in whole blocks
            # (``decode_attention_live``; over the cap, the share it reads)
            at_start["attn_rows"] = live_rows(int(self._lens.max()) + width,
                                              self.cap)
        with (tracer.span("serving.spec_verify", **at_start) if spec
              else tracer.span("serving.decode_chunk", **at_start)) as span:
            try:
                res = retry_with_backoff(attempt,
                                         retries=cfg.transient_retries,
                                         base_delay=cfg.retry_base_delay)
            except Exception as e:
                # retry budget exhausted mid-decode: the pool buffers may have
                # been donated into a dispatch that died, so they cannot be
                # trusted — fail every in-flight request, rebuild the pool,
                # keep serving the queue (same contract as admission: the loop
                # outlives transient faults; a deterministic failure propagates
                # once the in-flight requests are failed — rebuilding a pool
                # that cannot be allocated would only raise again)
                logger.error(f"[serving] decode chunk failed: "
                             f"{type(e).__name__}: {e}; failing "
                             f"{sum(h is not None for h in self._slot_req)} "
                             "in-flight request(s) and rebuilding the KV pool")
                self._fail_in_flight(time.monotonic())
                if not isinstance(e, TRANSIENT_FAULTS):
                    raise
                self._rebuild_pool()
                return False
            counts = res.steps - steps_before
            delivered = [(slot, h) for slot, h in streams if counts[slot] > 0]
            total = int(sum(counts[slot] for slot, _ in delivered))
            # a delivery is stalled when a prefill of ANOTHER request ran
            # since this stream's previous delivery (or its first token)
            stalled = sum(1 for slot, _ in delivered
                          if self._prefills_seen[slot] < self._prefills_done)
            span.set(tokens_kept=total, deliveries=len(delivered),
                     stalled_deliveries=stalled)
            if res.stamps is not None:
                # the chunk cycle on the host's clock, from the stamps the
                # executor's spans took (no clock is read for it)
                span.set(**self.telemetry.on_cycle(
                    *res.stamps, prev_fetched=prev_fetched, admit_s=admit_s))
                self._fetched_at = res.stamps[2]
            if res.moe is not None:
                span.set(moe_assignments=int(res.moe[0]),
                         moe_experts_touched=int(res.moe[1]))
            if res.block_counts is not None:
                span.set(forwards=width, block_length=self.block,
                         blocks_committed=int(res.block_counts[0]),
                         positions_unmasked=int(res.block_counts[1]),
                         blocks_merged=int(res.block_counts[2]))
        now = span.t1
        with tracer.span("serving.harvest") as harvest:
            chunk_t0 = now - res.elapsed
            for slot, h in delivered:
                if h.first_token_at is None:
                    # generation by blocks: the request's first generated
                    # token reached the host with this chunk, no earlier
                    h.first_token_at = now
                    h.ttft = now - h.arrival
                h.tokens.extend(res.buf[slot, :counts[slot]].tolist())
                self._prefills_seen[slot] = self._prefills_done
                # one ring span per participating request: the chunk is a
                # batch-level dispatch, but "where did THIS request's time
                # go" needs it on the request's own trace. Guarded:
                # tracing-off must not build attrs dicts on the hottest loop.
                if h._span is not None:
                    tracer.record_span(
                        "decode_chunk", h._span, chunk_t0, now,
                        attrs={"request_id": h.id, "chunk": chunk_idx,
                               "slot": slot, "tokens": int(counts[slot])})
            was_active = self._active.copy()
            self._toks = res.toks[:, 0].copy()
            self._lens = res.lens.copy()
            self._remaining = res.remaining.copy()
            self._steps = res.steps.copy()
            self._active = res.active.copy()
            if res.block is not None:
                self._blk, self._masked, self._skip = (
                    np.array(a) for a in res.block)
            finished = 0
            for slot in np.nonzero(was_active & ~res.active)[0]:
                h = self._slot_req[int(slot)]
                if h is None:
                    continue
                reason = ("eos" if h.eos_token_id is not None
                          and h.tokens and h.tokens[-1] == h.eos_token_id
                          else "length")
                self._retire_prefix(h, int(slot))
                self._finalize(h, RequestState.FINISHED, reason, now)
                self._release(int(slot))
                finished += 1
            harvest.set(finished=finished)
        self.telemetry.on_chunk(total, res.elapsed, slot_steps=slot_steps,
                                deliveries=len(delivered), stalled=stalled)
        self.telemetry.on_moe(res.moe, res.moe_plan_rows)
        if res.block_counts is not None:
            self.telemetry.on_blocks(width, res.block_counts)
        if spec:
            self.telemetry.on_spec(res.proposed, res.accepted, total,
                                   res.draft_s, res.elapsed)
        return True

    def _spec_round(self):
        """Build each active slot's draft window on the host (the proposer
        sees the request's full prompt+generated stream — pure host state, so
        a checkpointless retry re-derives the same drafts anywhere) and run
        one fixed-shape verify round through the executor."""
        k = self._spec_cfg.k
        S = self.config.slots
        proposals = np.zeros((S, k), np.int32)
        spec_lens = np.zeros(S, np.int32)
        t0 = time.monotonic()
        for slot, h in enumerate(self._slot_req):
            if h is None or not self._active[slot]:
                continue
            # window rows [lens, lens+L] must fit the cap, and an L-draft
            # round can emit L+1 tokens — cap-edge and budget-edge slots get
            # a truncated (possibly empty) window, degenerating to the plain
            # single-token step through the same compiled shape
            limit = min(k, self.cap - 1 - int(self._lens[slot]),
                        int(self._remaining[slot]) - 1)
            if limit <= 0:
                continue
            ctx = np.concatenate([h.prompt.astype(np.int32),
                                  np.asarray(h.tokens, np.int32)])
            draft = np.asarray(self.proposer.propose(ctx, limit), np.int32)
            L = min(int(draft.size), limit)
            if L > 0:
                proposals[slot, :L] = draft[:L]
                spec_lens[slot] = L
        draft_s = time.monotonic() - t0
        res = self.executor.run_spec_round(
            self._toks, self._lens, self._active, self._remaining,
            self._eos, self._seeds, self._steps, proposals, spec_lens)
        res.draft_s = draft_s
        return res

    # --------------------------------------------------------------- lifecycle
    def _finalize(self, handle: RequestHandle, state: RequestState,
                  reason: str, now: float) -> None:
        handle.state = state
        handle.finish_reason = reason
        handle.finished_at = now
        if (handle.first_token_at is not None and len(handle.tokens) > 1
                and now > handle.first_token_at):
            handle.tpot = (now - handle.first_token_at) / (len(handle.tokens) - 1)
        if handle._span is not None:
            self._tracer.instant("retire", handle._span,
                                 attrs={"state": state.value,
                                        "reason": reason})
            self._tracer.end_span(
                handle._span, t1=now,
                attrs={"state": state.value, "reason": reason,
                       "tokens": len(handle.tokens)})
            handle._span = None
        self.telemetry.on_finished(handle)

    def _release(self, slot: int) -> None:
        self._slot_req[slot] = None
        self._active[slot] = False
        self._lens[slot] = 0      # a free slot's length sets no step's trip count
        self._remaining[slot] = 0
        self._steps[slot] = 0
        self._eos[slot] = -1
        self.executor.pool.release(slot)
