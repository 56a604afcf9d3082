"""Serving telemetry: per-request TTFT/TPOT, queue depth, slot occupancy,
tokens/sec — recorded into the process-wide observability registry
(``observability.metrics``: bounded instruments, Prometheus exposition),
emitted as ``MonitorMaster`` events (any enabled backend: csv, tensorboard,
wandb, jsonl) and aggregated for the load-generator's BENCH JSON.

Event tags are declared once in ``observability.schema`` (step semantics in
parentheses):

- ``serving/ttft_ms``, ``serving/tpot_ms`` — per finished request (completion idx);
- ``serving/tokens_per_sec`` — per decode chunk (chunk idx);
- ``serving/queue_depth``, ``serving/slot_occupancy`` — per scheduler step (tick);
- ``serving/completed_total``, ``serving/rejected_total`` — per scheduler step;
- ``serving/prefix_hit_rate``, ``serving/prefix_cached_bytes``,
  ``serving/prefix_evicted_total`` — per scheduler step, prefix cache enabled
  only (hit/miss/inserted/evicted counters + cached-token bytes ride the
  aggregate snapshot);
- ``serving/prefix_spilled_bytes``, ``serving/prefix_spills_total``,
  ``serving/prefix_promotions_total`` — per scheduler step, tiered prefix
  cache (host-RAM rung) enabled only;
- ``serving/decode_slot_steps_total``, ``serving/decode_tokens_kept_total``,
  ``serving/deliveries_total``, ``serving/deliveries_stalled_total``,
  ``serving/moe_assignments_total``, ``serving/moe_experts_touched_total``,
  ``serving/moe_plan_rows_total``
  (expert layers only), ``serving/ssm_state_bytes`` (layers with a per-slot state only), ``serving/kv_ring_bytes`` (windowed layers only),
  ``serving/kv_latent_row_bytes`` (latent-attention layers only),
  ``serving/block_forwards_total``, ``serving/blocks_committed_total``,
  ``serving/positions_unmasked_total``, ``serving/blocks_merged_total`` (a model
  that generates by diffusion over blocks only) — per
  scheduler step: decode steps run against tokens a stream kept, and the
  deliveries a prefill of another request held up (the same counts ride the
  ``serving.decode_chunk`` span);
- ``serving/spec_*`` — per verify round, speculation enabled only; the
  emission site lives in ``inference.speculative.emit_spec_events`` (the
  subsystem that owns the semantics), this class only keeps the counters;
- ``serving/chunk_fetch_wait_ms``, ``serving/chunk_turnaround_ms`` — per decode
  chunk (chunk idx): the chunk cycle on the host's clock, from the stamps the
  spans take; ``host/stalls_total``, ``host/stall_ms_total`` — a chunk whose
  fetch wait or turnaround ran far over its running median
  (:data:`STALL_MULTIPLE`, :data:`STALL_FLOOR_MS`), kept as ``host.stall``
  in ``tracer.pauses`` (:meth:`ServingTelemetry.on_cycle`).

Latency distributions are **fixed-log-bucket histograms**, not lists: memory
stays O(1) over a week-long soak (the pre-PR-10 ``ttfts``/``tpots`` Python
lists grew one float per request forever) while ``snapshot()`` keeps the same
percentile keys, now bucket-derived.
"""

import time
from collections import deque
from typing import Dict, Iterable, Optional

from ...observability.metrics import Histogram, RegistryFeed
from ...observability.trace import get_tracer
from ..speculative import SpecStats, emit_spec_events


def window_rate(times: Iterable[float], now: float,
                horizon_s: float = 10.0) -> Optional[float]:
    """Events per second over the trailing ``horizon_s`` window, or None
    without fresh evidence (fewer than two events inside the horizon — a
    stale window must never report an ancient rate). THE drain-rate helper:
    scheduler/router backpressure hints and the autoscale estimator all rate
    their completion streams through this one function."""
    recent = [t for t in times if t >= now - horizon_s]
    if len(recent) < 2 or now <= recent[0]:
        return None
    return (len(recent) - 1) / max(now - recent[0], 1e-6)


def adaptive_retry_after(floor_s: float, cap_s: float, queue_depth: int,
                         max_queue: int,
                         drain_rate: Optional[float]) -> float:
    """Load-adaptive backpressure hint: estimated seconds until one queue
    slot drains (``(depth + 1) / drain_rate``), a fill-scaled multiple of
    the floor before any drain evidence exists; bounded to
    ``[floor_s, cap_s]`` so one bad estimate cannot park every client for
    minutes. A static hint convoys rejected clients back in lockstep at
    exactly the wrong moment — this one stretches with the backlog. Shared
    by the scheduler and the router (the two QueueFullError emitters)."""
    if drain_rate is None or drain_rate <= 0:
        hint = floor_s * (1.0 + queue_depth / max(1, max_queue))
    else:
        hint = (queue_depth + 1) / drain_rate
    return float(min(max(hint, floor_s), cap_s))


#: A chunk's phase is a stall when it ran over BOTH this multiple of the
#: phase's running median and :data:`STALL_FLOOR_MS`. The fetch wait is the
#: device's chunk as the host sees it, the same K steps every time (90-260 ms
#: in the benchmark's cells, within a few per cent of its median), so half
#: as much again is no chunk. The turnaround is 1-8 ms of host work that a
#: profiler or the machine's slow mode stretch two- to fourfold and a
#: finished request's harvest doubles: neither is a stall, a collection of
#: 0.13 s or a process that stood still is.
STALL_MULTIPLE = {"fetch": 1.5, "turnaround": 8.0}
STALL_FLOOR_MS = 50.0
#: the running medians are read off the histograms every so many chunks; no
#: stall is called before the first reading
STALL_REFRESH_CHUNKS = 16


class ServingTelemetry:
    """Aggregator + event emitter; ``monitor`` is an optional MonitorMaster."""

    def __init__(self, monitor=None):
        self.monitor = monitor
        self._tick = 0
        self._chunk_idx = 0
        self._finished_idx = 0
        # per-telemetry bounded histograms (ms): the snapshot's percentile
        # source. The process registry keeps its own global instruments via
        # record_events — per-replica snapshots must not blend across replicas.
        self.ttft_ms = Histogram()
        self.tpot_ms = Histogram()
        # per-emitter registry feed: this telemetry's cumulative counters
        # contribute DELTAS, so N replicas (and successive runs) sum in
        # /metrics instead of max-merging
        self._feed = RegistryFeed()
        self.tokens_total = 0
        self.completed = 0
        self.rejected = 0
        self.cancelled = 0
        self.expired = 0
        self.evicted = 0
        self.decode_seconds = 0.0
        # decode waste and stalled deliveries (tokens kept = tokens_total)
        self.decode_slot_steps = 0
        self.deliveries = 0
        self.deliveries_stalled = 0
        # expert layers (a model without them leaves these at 0 and unpublished)
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        self.moe_plan_rows = 0
        self.block_forwards = 0
        self.blocks_committed = 0
        self.positions_unmasked = 0
        self.blocks_merged = 0
        # prefix-cache counters (only advanced when the cache is enabled)
        self.prefix_enabled = False
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self._prefix_stats = None    # latest PrefixCache.stats() gauge set
        self._paged_stats = None     # latest PagedKVPool.stats() gauge set
        # speculative-decoding counters (only advanced when speculation is on);
        # the spec_* event emission itself lives in inference.speculative
        self.spec = SpecStats()
        self.spec_enabled = False
        # the chunk cycle on the host's clock (ms), this scheduler's own: the
        # running medians a stall is held against come from these two
        self.chunk_ms = {"fetch": Histogram(), "turnaround": Histogram()}
        self._typical_ms: Dict[str, float] = {}
        self.stalls = 0
        self.stall_ms = 0.0
        self._stalls_written = -1
        # completion timestamps (bounded): the observed drain rate behind the
        # load-adaptive QueueFullError.retry_after hint
        self._finish_times = deque(maxlen=64)
        self._t_start = time.perf_counter()

    # ------------------------------------------------------------------- emits
    def _write(self, events):
        self._feed.record_events(events)   # process registry (/metrics)
        if self.monitor is not None and getattr(self.monitor, "enabled", False):
            self.monitor.write_events(events)

    def on_step(self, queue_depth: int, occupancy: float,
                prefix_stats=None, paged_stats=None) -> None:
        self._tick += 1
        ev = [("serving/queue_depth", float(queue_depth), self._tick),
              ("serving/slot_occupancy", float(occupancy), self._tick),
              ("serving/completed_total", float(self.completed), self._tick),
              ("serving/rejected_total", float(self.rejected), self._tick),
              ("serving/decode_slot_steps_total",
               float(self.decode_slot_steps), self._tick),
              ("serving/decode_tokens_kept_total", float(self.tokens_total),
               self._tick),
              ("serving/deliveries_total", float(self.deliveries), self._tick),
              ("serving/deliveries_stalled_total",
               float(self.deliveries_stalled), self._tick)]
        if paged_stats is not None:
            # paged-pool gauges/counters (PagedKVPool.stats()): page-granular
            # occupancy, allocation-granularity waste, zero-copy sharing
            self._paged_stats = paged_stats
            ev += [("serving/pages_in_use",
                    float(paged_stats["pages_in_use"]), self._tick),
                   ("serving/page_fragmentation",
                    float(paged_stats["page_fragmentation"]), self._tick),
                   ("serving/prefix_shared_pages",
                    float(paged_stats["prefix_shared_pages"]), self._tick),
                   ("serving/cow_copies_total",
                    float(paged_stats["cow_copies_total"]), self._tick)]
            if paged_stats.get("state_bytes"):
                ev += [("serving/ssm_state_bytes",
                        float(paged_stats["state_bytes"]), self._tick)]
            if paged_stats.get("ring_bytes"):
                ev += [("serving/kv_ring_bytes",
                        float(paged_stats["ring_bytes"]), self._tick)]
            if paged_stats.get("latent_row_bytes"):
                ev += [("serving/kv_latent_row_bytes",
                        float(paged_stats["latent_row_bytes"]), self._tick)]
        if self.moe_assignments:
            ev += [("serving/moe_assignments_total",
                    float(self.moe_assignments), self._tick),
                   ("serving/moe_experts_touched_total",
                    float(self.moe_experts_touched), self._tick),
                   ("serving/moe_plan_rows_total",
                    float(self.moe_plan_rows), self._tick)]
        if self.block_forwards:
            ev += [("serving/block_forwards_total", float(self.block_forwards),
                    self._tick),
                   ("serving/blocks_committed_total",
                    float(self.blocks_committed), self._tick),
                   ("serving/positions_unmasked_total",
                    float(self.positions_unmasked), self._tick),
                   ("serving/blocks_merged_total",
                    float(self.blocks_merged), self._tick)]
        if prefix_stats is not None:
            self._prefix_stats = prefix_stats
            # hit_rate here is ADMISSION-level (successful prefills), the same
            # quantity the snapshot publishes under the same name — the trie's
            # own lookup-level counters (which also tick on failed/retried
            # admissions) live in prefix_cache_report() only
            n = self.prefix_hits + self.prefix_misses
            ev += [("serving/prefix_hit_rate",
                    self.prefix_hits / n if n else 0.0, self._tick),
                   ("serving/prefix_cached_bytes",
                    float(prefix_stats["cached_bytes"]), self._tick),
                   ("serving/prefix_evicted_total",
                    float(prefix_stats["evicted"]), self._tick)]
            if "spilled_bytes" in prefix_stats:
                # tiered-cache rung (PR 19): host-RAM residency + the two
                # movement counters (device→host spill, host→device promote)
                ev += [("serving/prefix_spilled_bytes",
                        float(prefix_stats["spilled_bytes"]), self._tick),
                       ("serving/prefix_spills_total",
                        float(prefix_stats["spills"]), self._tick),
                       ("serving/prefix_promotions_total",
                        float(prefix_stats["promotions"]), self._tick)]
        self._write(ev)

    def on_prefix(self, hit: bool, tokens: int, enabled: bool = True) -> None:
        """Per-admission hit/miss accounting (``tokens`` = prefill tokens
        skipped via the restored prefix; 0 on a miss)."""
        if not enabled:
            return
        self.prefix_enabled = True
        if hit:
            self.prefix_hits += 1
            self.prefix_hit_tokens += int(tokens)
        else:
            self.prefix_misses += 1

    def on_moe(self, stats, plan_rows: int = 0) -> None:
        """``stats`` = (assignments on held experts, distinct held experts
        read) of one compiled program, summed over its expert layers and
        steps; None from a model without expert layers. ``plan_rows``: the
        rows its dispatch plans laid out for them (a static count)."""
        if stats is not None:
            self.moe_assignments += int(stats[0])
            self.moe_experts_touched += int(stats[1])
            self.moe_plan_rows += int(plan_rows)

    def on_blocks(self, forwards: int, counts) -> None:
        """One decode chunk of a model that generates by diffusion over
        blocks: the forwards it ran and ``counts`` = (blocks committed,
        positions unmasked, commits that opened their next block in the same
        forward) over its slots."""
        self.block_forwards += int(forwards)
        self.blocks_committed += int(counts[0])
        self.positions_unmasked += int(counts[1])
        self.blocks_merged += int(counts[2])

    def on_chunk(self, tokens: int, elapsed: float, slot_steps: int = 0,
                 deliveries: int = 0, stalled: int = 0) -> None:
        """One decode chunk: ``tokens`` handed to streams of the
        ``slot_steps`` (steps x active slots) it ran, in ``deliveries``
        deliveries of which ``stalled`` waited on another request's prefill."""
        self._chunk_idx += 1
        self.tokens_total += int(tokens)
        self.decode_seconds += float(elapsed)
        self.decode_slot_steps += int(slot_steps)
        self.deliveries += int(deliveries)
        self.deliveries_stalled += int(stalled)
        if elapsed > 0:
            self._write([("serving/tokens_per_sec", tokens / elapsed,
                          self._chunk_idx)])

    def on_cycle(self, dispatched: float, fetch_t0: float, fetched: float,
                 prev_fetched: Optional[float] = None,
                 admit_s: float = 0.0) -> Dict[str, float]:
        """One decode chunk's cycle, from the stamps its spans took
        (``ChunkResult.stamps``): the wait in ``serving.fetch`` and, where
        the previous step ran a chunk too (``prev_fetched``: its fetch's
        return), the host's turnaround from there to this chunk's
        ``serving.dispatch`` return, the ``admit_s`` seconds of
        ``serving.admit`` between the two left out. Both go into their
        histograms; one that ran far over its running median is kept as a
        ``host.stall``. Returns the chunk span's end-of-span attributes."""
        idx = self._chunk_idx + 1
        attrs = {"fetch_wait_ms": round((fetched - fetch_t0) * 1e3, 3)}
        events = [("serving/chunk_fetch_wait_ms", attrs["fetch_wait_ms"], idx)]
        self._phase("fetch", fetch_t0, fetched, attrs["fetch_wait_ms"])
        if prev_fetched is not None:
            ms = round((dispatched - prev_fetched - admit_s) * 1e3, 3)
            attrs.update(turnaround_ms=ms, admit_ms=round(admit_s * 1e3, 3))
            events.append(("serving/chunk_turnaround_ms", ms, idx))
            self._phase("turnaround", prev_fetched, dispatched, ms)
        if self.stalls != self._stalls_written:     # and once at 0
            self._stalls_written = self.stalls
            events += [("host/stalls_total", float(self.stalls), idx),
                       ("host/stall_ms_total", self.stall_ms, idx)]
        self._write(events)
        return attrs

    def _phase(self, phase: str, t0: float, t1: float, ms: float) -> None:
        hist = self.chunk_ms[phase]
        typical = self._typical_ms.get(phase)
        hist.observe(ms)
        if hist.count % STALL_REFRESH_CHUNKS == 0:
            self._typical_ms[phase] = hist.percentile(50)
        if typical is None or ms <= max(STALL_MULTIPLE[phase] * typical,
                                        STALL_FLOOR_MS):
            return
        self.stalls += 1
        self.stall_ms += ms - typical       # what the stall cost: the time over
        tracer = get_tracer()
        tracer.record_pause("host.stall", t0, t1, phase=phase, ms=ms,
                            typical_ms=round(typical, 3),
                            gc_ms=round(tracer.gc_ms_between(t0, t1), 3))

    def on_spec(self, proposed: int, accepted: int, tokens: int,
                draft_s: float, verify_s: float) -> None:
        """Per-verify-round speculative accounting (one round == one target
        forward pass over the whole slot-batch)."""
        self.spec_enabled = True
        s = self.spec
        s.rounds += 1
        s.proposed += int(proposed)
        s.accepted += int(accepted)
        s.tokens += int(tokens)
        s.draft_s += float(draft_s)
        s.verify_s += float(verify_s)
        emit_spec_events(self, s, draft_s, s.rounds)

    def on_rejected(self) -> None:
        self.rejected += 1

    def on_finished(self, handle) -> None:
        from .scheduler import RequestState
        if handle.state == RequestState.CANCELLED:
            self.cancelled += 1
            return
        if handle.state == RequestState.EXPIRED:
            self.expired += 1
            return
        if handle.state == RequestState.EVICTED:
            self.evicted += 1
            return
        self.completed += 1
        self._finished_idx += 1
        self._finish_times.append(time.monotonic())
        events = []
        if handle.ttft is not None:
            self.ttft_ms.observe(handle.ttft * 1e3)
            events.append(("serving/ttft_ms", handle.ttft * 1e3,
                           self._finished_idx))
        if handle.tpot is not None:
            self.tpot_ms.observe(handle.tpot * 1e3)
            events.append(("serving/tpot_ms", handle.tpot * 1e3,
                           self._finished_idx))
        self._write(events)

    def drain_rate(self, now: Optional[float] = None,
                   horizon_s: float = 10.0) -> Optional[float]:
        """Recent completions per second, or None without fresh evidence."""
        now = time.monotonic() if now is None else now
        return window_rate(self._finish_times, now, horizon_s)

    # --------------------------------------------------------------- aggregate
    def snapshot(self) -> Dict:
        elapsed = time.perf_counter() - self._t_start
        prefix = {}
        if self.prefix_enabled or self._prefix_stats is not None:
            n = self.prefix_hits + self.prefix_misses
            prefix = {
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_hit_rate": self.prefix_hits / n if n else 0.0,
                "prefix_hit_tokens": self.prefix_hit_tokens,
            }
            if self._prefix_stats is not None:
                prefix["prefix_inserted"] = self._prefix_stats["inserted"]
                prefix["prefix_evicted"] = self._prefix_stats["evicted"]
                prefix["prefix_cached_bytes"] = \
                    self._prefix_stats["cached_bytes"]
                if "spilled_bytes" in self._prefix_stats:
                    prefix["prefix_spilled_bytes"] = \
                        self._prefix_stats["spilled_bytes"]
                    prefix["prefix_spills"] = self._prefix_stats["spills"]
                    prefix["prefix_promotions"] = \
                        self._prefix_stats["promotions"]
        paged = ({f"paged_{k}": v for k, v in self._paged_stats.items()}
                 if self._paged_stats is not None else {})
        spec = self.spec.snapshot() if self.spec_enabled else {}
        return {
            **prefix,
            **paged,
            **spec,
            "elapsed_s": elapsed,
            "completed": self.completed,
            "rejected": self.rejected,
            "cancelled": self.cancelled,
            "expired": self.expired,
            "evicted": self.evicted,
            "tokens_total": self.tokens_total,
            "decode_slot_steps": self.decode_slot_steps,
            "deliveries": self.deliveries,
            "deliveries_stalled": self.deliveries_stalled,
            "host_stalls": self.stalls,
            "chunk_fetch_wait_ms_p50": self.chunk_ms["fetch"].percentile(50),
            "chunk_turnaround_ms_p50":
                self.chunk_ms["turnaround"].percentile(50),
            "tokens_per_sec": (self.tokens_total / self.decode_seconds
                               if self.decode_seconds > 0 else 0.0),
            "ttft_ms_p50": self.ttft_ms.percentile(50),
            "ttft_ms_p95": self.ttft_ms.percentile(95),
            "tpot_ms_p50": self.tpot_ms.percentile(50),
            "tpot_ms_p95": self.tpot_ms.percentile(95),
        }
