"""Serving load generator + chaos soak harness: Poisson (or Markov-modulated
bursty) arrivals through the continuous-batching scheduler — or, with
``--replicas N``, through the multi-replica router under scheduled fault
injection — one JSON object on stdout. The tests drive it in-process at
``--smoke`` size for its counts and parity; it is never the source of a
rate (the benchmark is ``benchmarks/chipbench/``).

Drives the real frontend (admission, backpressure, slot recycling, and in
router mode health supervision + checkpointless retry) with open-loop traffic:
request arrival times are drawn from an exponential inter-arrival distribution
and submitted when wall clock passes them. A rejected (queue-full) submission is
never dropped: the client honours ``QueueFullError.retry_after`` with jittered
backoff (``retry_after * (0.5 + U[0,1))``, per request — no head-of-line
thundering herd) and resubmits. Emitted throughput therefore includes
admission-control effects, not just raw decode speed.

Shared-prefix traces (``--prefix-pool N --prefix-len L``): every prompt is one
of N pool "system prompts" of L tokens plus a short random tail — real serving
traffic's shape, and what the radix prefix KV cache's tests run
(``--prefix-cache``). The JSON then splits TTFT into **hit vs miss**
percentiles (a request is a hit when its first token came from a
restored-prefix suffix prefill, ``handle.prefix_hit_tokens > 0``) and reports
the measured hit-rate plus the engine-side ``prefix_cache_report``.

Bursty mode (``--arrival bursty``): a two-state Markov-modulated Poisson
process — exponential ON/OFF holding times (``--burst-on-s`` / ``--burst-off-s``
means), arrivals only during ON at ``rate * --burst-mult`` — the arrival shape
that makes prefill spikes (and the prefix cache's absorption of them) visible.

Time-varying offered load (``--arrival schedule:<rate@dur,...>``): a piecewise
Poisson schedule — e.g. ``schedule:2@3,10@2,2@3`` offers 2 req/s for 3 s, then
10 req/s for 2 s, then 2 req/s again, cycling until ``--requests`` arrivals are
drawn. ``schedule+bursty:<...>`` composes the Markov ON/OFF modulation on top
of the piecewise base rate. The JSON then carries per-window TTFT/TPOT
percentiles plus ``replica_seconds`` (attached replicas integrated over the
run). A chaos ``surge`` event (``surge:mult=4,at=1.0,s=2.0``) multiplies the
offered rate inside its window on any arrival mode.

Autoscaling (``--autoscale --min-replicas N --max-replicas M``): the router
starts at N replicas and an :class:`~.autoscale.Autoscaler` closes the
metrics→capacity loop mid-run (scale-up through the RECOVERING warm probe,
scale-down through graceful retire — migrated requests stay bit-exact and the
run still requires ``lost == 0``). ``--slo-admission`` (+ ``--deadline-s``)
turns on SLO-aware admission: requests whose estimated completion misses their
deadline are shed at the front door with a load-adaptive ``retry_after`` (the
client counts them, it does not resubmit a doomed deadline).

Chaos soak (``--replicas >= 2 --chaos "<spec>"``, grammar in
``inference.serving.chaos``): scheduled replica kills/stalls run against the
router mid-load — including ``kill:replica=i,when=restore``, which lands the
kill between a prefix-slab restore and its suffix prefill; the JSON then
carries the no-loss accounting — ``retried`` / ``evicted`` / ``lost`` (the run
fails unless ``lost == 0``) — and, for greedy runs, ``parity_ok``: every
evicted-and-retried request's final output is re-checked bit-identical against
an unkilled per-request ``generate``. ``--verify-parity`` extends that re-check
to EVERY request (the prefix-cache bit-exactness acceptance gate).

Observability (PR 10, ``docs/OBSERVABILITY.md``): ``--trace-out FILE`` enables
the request-scoped span tracer for the run and writes a Perfetto-loadable
Chrome trace on exit (documented alongside ``--jsonl-metrics`` — one is the
span stream, the other the metric stream of the same spine).

``--smoke`` shrinks everything (tiny model, few requests) to a seconds-long run —
the mode the serving tests execute in-process.

Output: one JSON object, ``{"metric": "serving_tokens_per_sec", "value": ...,
"unit": "tok/s", ...}`` with the telemetry snapshot nested under ``"detail"``
(also written to ``--out FILE`` when given).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable as `python benchmarks/serving/loadgen.py` from any cwd
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def build_engine(args, params=None):
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    cfg = gpt2_cfg(vocab_size=args.vocab_size, max_seq_len=args.max_seq_len,
                   n_embd=args.n_embd, n_layer=args.n_layer, n_head=args.n_head,
                   dtype=jnp.float32 if args.dtype == "float32" else jnp.bfloat16)
    return InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype=args.dtype, max_out_tokens=args.max_seq_len), params=params)


def parse_dist(spec: str):
    """``bimodal:<lo_min>-<lo_max>,<hi_min>-<hi_max>,<p_hi>`` — the
    short/long mixed-length knob (``--prompt-dist`` / ``--output-dist``).
    Returns ``(lo_min, lo_max, hi_min, hi_max, p_hi)``."""
    if not spec.startswith("bimodal:"):
        raise ValueError(f"malformed length dist {spec!r} (expected "
                         "bimodal:<lo-lo>,<hi-hi>,<p_hi>)")
    parts = spec.split(":", 1)[1].split(",")
    if len(parts) != 3:
        raise ValueError(f"malformed length dist {spec!r}: need two ranges "
                         "and a probability")

    def _range(s):
        lo, sep, hi = s.partition("-")
        if not sep:
            raise ValueError(f"malformed range {s!r} in length dist")
        lo, hi = int(lo), int(hi)
        if not 0 < lo <= hi:
            raise ValueError(f"range {s!r}: need 0 < min <= max")
        return lo, hi

    lo_min, lo_max = _range(parts[0])
    hi_min, hi_max = _range(parts[1])
    p = float(parts[2])
    if not 0.0 <= p <= 1.0:
        raise ValueError(f"p_hi {p} must be in [0, 1]")
    return (lo_min, lo_max, hi_min, hi_max, p)


def draw_lengths(rng, n, base_min, base_max, dist):
    """Per-request token counts: uniform ``[base_min, base_max]`` without a
    dist, else the bimodal short/long mix."""
    if dist is None:
        return rng.integers(base_min, base_max + 1, size=n)
    lo_min, lo_max, hi_min, hi_max, p = dist
    lo = rng.integers(lo_min, lo_max + 1, size=n)
    hi = rng.integers(hi_min, hi_max + 1, size=n)
    return np.where(rng.random(n) < p, hi, lo)


def make_prompts(args, rng):
    """Random prompts; with ``--prefix-pool`` each is pool-prefix + random tail
    (the shared-system-prompt trace shape). ``--prompt-dist`` draws the
    tail lengths from a short/long bimodal mix instead of the uniform
    ``[--min-prompt, --max-prompt]``."""
    n = args.requests
    sizes = draw_lengths(rng, n, args.min_prompt, args.max_prompt,
                         getattr(args, "prompt_dist", None))
    tails = [rng.integers(0, args.vocab_size,
                          size=int(s)).astype(np.int32) for s in sizes]
    if not args.prefix_pool:
        return tails, [None] * n
    pool = [rng.integers(0, args.vocab_size, size=args.prefix_len
                         ).astype(np.int32) for _ in range(args.prefix_pool)]
    picks = rng.integers(0, args.prefix_pool, size=n)
    prompts = [np.concatenate([pool[int(p)], t])
               for p, t in zip(picks, tails)]
    # session = pool id: the router's affinity then concentrates each shared
    # prefix on one replica — the locality hook the per-replica caches need
    return prompts, [f"pool{int(p)}" for p in picks]


def parse_schedule(spec: str):
    """``rate@dur,...`` → [(rate, duration), ...] (the piecewise windows)."""
    windows = []
    for part in filter(None, (p.strip() for p in spec.split(","))):
        rate, sep, dur = part.partition("@")
        if not sep:
            raise ValueError(f"malformed schedule window {part!r} "
                             "(expected rate@duration)")
        r, d = float(rate), float(dur)
        if r <= 0 or d <= 0:
            raise ValueError(f"schedule window {part!r}: rate and duration "
                             "must be positive")
        windows.append((r, d))
    if not windows:
        raise ValueError("empty arrival schedule")
    return windows


def make_arrivals(args, rng, surges=(), mult_fn=None):
    """Open-loop arrival offsets (seconds from run start) + per-request
    schedule-window index (None without a schedule).

    One sequential generator covers every mode: the instantaneous rate is the
    schedule window's base rate (or ``--rate``), times any open chaos ``surge``
    window (``mult_fn``, run-relative — the caller wraps
    ``ChaosSchedule.load_multiplier`` so there is ONE surge implementation;
    ``surges`` carries just the (at, duration) edges for boundary redraws),
    times the Markov ON/OFF burst modulation when composed. Draws that would
    straddle a rate-change boundary are re-drawn from the boundary
    (memorylessness makes that statistically exact), so each window really
    offers its nominal rate."""
    n = args.requests
    schedule = getattr(args, "schedule_windows", None)
    bursty = args.arrival == "bursty" or (schedule is not None
                                          and getattr(args, "schedule_bursty",
                                                      False))
    cycle = sum(d for _, d in schedule) if schedule else None

    def base_rate(t):
        if not schedule:
            return args.rate, None
        tc = t % cycle
        acc = 0.0
        for i, (r, d) in enumerate(schedule):
            acc += d
            if tc < acc:
                return r, i
        return schedule[-1][0], len(schedule) - 1

    def next_boundary(t):
        bs = []
        if schedule:
            tc = t % cycle
            acc = 0.0
            for _, d in schedule:
                acc += d
                if tc < acc:
                    bs.append(t - tc + acc)
                    break
        for at, dur in surges:
            if t < at:
                bs.append(at)
            elif t < at + dur:
                bs.append(at + dur)
        return min(bs) if bs else None

    offs, widx = [], []
    t = 0.0
    on, off_until = True, 0.0
    on_until = rng.exponential(args.burst_on_s) if bursty else None
    while len(offs) < n:
        if bursty and not on:
            t = off_until
            on = True
            on_until = t + rng.exponential(args.burst_on_s)
            continue
        rate, w = base_rate(t)
        if mult_fn is not None:
            rate *= mult_fn(t)
        if bursty:
            rate *= args.burst_mult
        gap = rng.exponential(1.0 / rate)
        b = next_boundary(t)
        if b is not None and b > t and t + gap > b:
            t = b                             # rate changes at b: redraw there
            continue
        if bursty and t + gap > on_until:
            t = on_until
            on = False
            off_until = t + rng.exponential(args.burst_off_s)
            continue
        t += gap
        offs.append(t)
        widx.append(w)
    return np.asarray(offs), widx


def run_load(front, args, chaos=None, autoscaler=None, supervisor=None) -> dict:
    from deepspeed_tpu.inference.serving import (AdmissionDeferredError,
                                                 AdmissionShedError,
                                                 QueueFullError)
    rng = np.random.default_rng(args.seed)
    n = args.requests
    prompts, sessions = make_prompts(args, rng)
    max_news = [int(x) for x in
                draw_lengths(rng, n, args.min_new, args.max_new,
                             getattr(args, "output_dist", None))]
    surges = tuple((ev.at, ev.duration) for ev in chaos.events
                   if ev.kind == "surge") if chaos is not None else ()
    # ONE surge implementation: the offered trace consults the schedule's own
    # load_multiplier (run-relative via its t0, which the caller creates at
    # run start)
    mult_fn = ((lambda t: chaos.load_multiplier(chaos.t0 + t))
               if chaos is not None else None)
    offs, widx = make_arrivals(args, rng, surges=surges, mult_fn=mult_fn)
    is_router = hasattr(front, "replicas")
    # parity references must outlive scale-down: replica 0 may detach mid-run,
    # but the engine object (shared params) stays valid through this binding.
    # Bound BEFORE the run clock starts: a hosted replica builds its parent
    # reference engine lazily on first access, and paying that build after t0
    # would read as queueing in the coordinated-omission-honest TTFT.
    ref_engine = (front.replicas[0].engine if is_router
                  else front.executor.engine)
    t0 = time.monotonic()
    arrivals = t0 + offs
    deadline_s = getattr(args, "deadline_s", None)
    # pending entries are mutable [ready_time, idx]: a rejected request backs
    # off independently (jittered), it never blocks later arrivals
    pending = [[float(arrivals[i]), i] for i in range(n)]
    handles = {}
    resubmits = 0
    shed = {}                       # idx -> retry_after hint (terminal sheds)
    deferred_resubmits = 0
    replica_seconds = 0.0
    last_tick = t0
    while pending or front.busy:
        if autoscaler is not None:
            autoscaler.step()
        if supervisor is not None:
            supervisor.step()       # respawn dead hosted replicas (backoff)
        if chaos is not None:
            # polled AFTER the scaler so a when=draining event sees the
            # RETIRING state the scaler just entered — the retire sweep
            # inside front.step() may detach an idle replica the same step
            chaos.poll(front)
        now = time.monotonic()
        replica_seconds += (now - last_tick) * (len(front.replicas)
                                                if is_router else 1)
        last_tick = now
        for entry in [e for e in pending if e[0] <= now]:
            idx = entry[1]
            kwargs = dict(max_new_tokens=max_news[idx], seed=idx)
            if is_router:
                kwargs["session"] = sessions[idx]
            if deadline_s is not None:
                kwargs["deadline_s"] = float(deadline_s)
            try:
                handles[idx] = front.submit(prompts[idx], **kwargs)
                pending.remove(entry)
            except AdmissionShedError as e:
                # SLO shed is terminal for this deadline: the router says the
                # request cannot finish in time — resubmitting the same doomed
                # deadline would only re-shed. The hint is recorded (a real
                # client would retry with a fresh deadline after it).
                shed[idx] = float(e.retry_after)
                pending.remove(entry)
            except AdmissionDeferredError as e:   # low-priority: come back
                deferred_resubmits += 1
                entry[0] = now + e.retry_after * (0.5 + float(rng.random()))
            except QueueFullError as e:   # backpressure: jittered client retry
                resubmits += 1
                entry[0] = now + e.retry_after * (0.5 + float(rng.random()))
        if front.busy or (is_router and getattr(front, "retiring_pending",
                                                False)):
            # retiring_pending: an idle scale-down still needs steps — only
            # the router's retire sweep detaches a RETIRING replica
            front.step()
        elif pending:
            # idle: sleep to the next event (arrival / retry window) instead of
            # spinning step() — a busy-wait would burn a core and fold its own
            # overhead into the latency numbers this benchmark reports
            time.sleep(max(0.0, min(e[0] for e in pending) - time.monotonic()))
    wall = time.monotonic() - t0
    if autoscaler is not None:
        # idle tail: a real deployment stays up after the storm — keep the
        # control loop running (bounded) so the scale-DOWN half of the cycle
        # is part of the run. Tail replica-seconds accrue to the run's bill
        # (they are real provisioned capacity).
        tail0 = time.monotonic()
        while (len(front.replicas) > autoscaler.config.min_replicas
               and time.monotonic() - tail0 < 8.0):
            autoscaler.step()
            if supervisor is not None:
                supervisor.step()
            if chaos is not None:
                chaos.poll(front)     # scale events mostly land in the tail;
                #   poll between the scaler's begin_retire and the router's
                #   retire sweep so when=draining can land
            front.step()
            now = time.monotonic()
            replica_seconds += (now - last_tick) * len(front.replicas)
            last_tick = now
            time.sleep(0.005)
    wall_total = time.monotonic() - t0
    snap = front.snapshot() if is_router else front.telemetry.snapshot()
    snap["wall_total_s"] = wall_total            # incl. the scale-down tail
    # coordinated-omission-honest latency: measured from the GENERATOR's
    # scheduled arrival, not the (possibly late) submit stamp — under
    # overload the client loop itself backs up, and submit-relative TTFT
    # would hide exactly that queueing
    e2e = {i: (handles[i].first_token_at - arrivals[i]) * 1e3
           for i in handles if handles[i].first_token_at is not None}
    e2es = list(e2e.values())
    snap["ttft_e2e_ms_p50"] = (float(np.percentile(e2es, 50))
                               if e2es else None)
    snap["ttft_e2e_ms_p95"] = (float(np.percentile(e2es, 95))
                               if e2es else None)
    snap["wall_s"] = wall
    snap["submitted"] = len(handles)
    snap["backpressure_events"] = resubmits      # client-side resubmissions
    snap["deferred_resubmits"] = deferred_resubmits
    snap["shed_client"] = len(shed)              # terminal SLO sheds
    snap["shed_retry_after_ok"] = all(v > 0 for v in shed.values())
    # replica-seconds: the autoscaler's own integration is authoritative when
    # one is attached (one quantity, one owner); the local integration covers
    # a run that has no autoscaler
    snap["replica_seconds"] = (autoscaler.replica_seconds
                               if autoscaler is not None else replica_seconds)
    snap["mean_replicas"] = (snap["replica_seconds"] / wall_total
                             if wall_total > 0 else None)
    snap["all_finished"] = all(h.done for h in handles.values())
    if chaos is not None:
        # a chaos run must never degrade to nothing: unfired events (e.g. a
        # when= trigger whose target replica never reached that state) fail
        # the run at the gate below
        snap["chaos_exhausted"] = chaos.exhausted
        snap["chaos_unfired"] = [f"{ev.kind}:replica={ev.replica},"
                                 f"when={ev.when},at={ev.at}"
                                 for ev in chaos.events if not ev.fired]
    if autoscaler is not None:
        snap["autoscale"] = autoscaler.report()
    if supervisor is not None:
        snap["hosts"] = supervisor.report()
    if any(w is not None for w in widx):
        # per-schedule-window percentiles (a window's TTFT under surge vs
        # the steady windows)
        schedule = args.schedule_windows
        snap["windows"] = []
        for w, (rate, dur) in enumerate(schedule):
            idxs = [i for i in handles if widx[i] == w]
            hs = [handles[i] for i in idxs]
            ttfts_w = [h.ttft * 1e3 for h in hs if h.ttft is not None]
            e2e_w = [e2e[i] for i in idxs if i in e2e]
            tpots_w = [h.tpot * 1e3 for h in hs if h.tpot is not None]

            def _p(xs, q):
                return float(np.percentile(np.asarray(xs), q)) if xs else None

            snap["windows"].append({
                "window": w, "rate": rate, "duration_s": dur,
                "requests": len(hs) + sum(1 for i in shed if widx[i] == w),
                "shed": sum(1 for i in shed if widx[i] == w),
                "completed": sum(1 for h in hs
                                 if h.state.value == "finished"),
                "ttft_ms_p50": _p(ttfts_w, 50),
                "ttft_ms_p95": _p(ttfts_w, 95),
                "ttft_e2e_ms_p50": _p(e2e_w, 50),
                "ttft_e2e_ms_p95": _p(e2e_w, 95),
                "tpot_ms_p50": _p(tpots_w, 50),
            })
    # no-loss accounting, present on BOTH paths (router already carries its own
    # retried/evicted; the single scheduler never retries)
    snap.setdefault("retried", 0)
    snap.setdefault("evicted", 0)
    if "lost" not in snap:
        snap["lost"] = (snap["submitted"] - snap.get("completed", 0)
                        - snap.get("cancelled", 0) - snap.get("expired", 0))
    if is_router:
        snap["tokens_per_sec"] = (snap["tokens_total"] / wall
                                  if wall > 0 else 0.0)
        # greedy chaos/scale acceptance: every request that survived an
        # eviction (replica death OR scale-down migration) must end
        # bit-identical to an unkilled per-request generate
        if chaos is not None or autoscaler is not None:
            verified, parity_ok = 0, True
            for idx, h in handles.items():
                if h.retried == 0 and h.evictions == 0:
                    continue
                ref = np.asarray(ref_engine.generate(
                    prompts[idx][None, :], max_new_tokens=max_news[idx]))
                verified += 1
                if not np.array_equal(h.result(),
                                      ref[0, prompts[idx].size:]):
                    parity_ok = False
            snap["parity_checked"] = verified
            snap["parity_ok"] = parity_ok
    # hit-vs-miss TTFT split + measured hit-rate (prefix-cache acceptance):
    # a request is a hit when its first token came from a restored-prefix
    # suffix prefill on whichever attempt produced it
    if args.prefix_cache or args.prefix_pool:
        done = [h for h in handles.values() if h.ttft is not None]
        hit_t = [h.ttft * 1e3 for h in done if h.prefix_hit_tokens > 0]
        miss_t = [h.ttft * 1e3 for h in done if h.prefix_hit_tokens == 0]

        def pct(xs, q):
            return float(np.percentile(np.asarray(xs), q)) if xs else None

        snap["prefix_trace"] = {
            "hit_requests": len(hit_t),
            "miss_requests": len(miss_t),
            "measured_hit_rate": (len(hit_t) / len(done) if done else 0.0),
            "ttft_hit_ms_p50": pct(hit_t, 50),
            "ttft_hit_ms_p95": pct(hit_t, 95),
            "ttft_miss_ms_p50": pct(miss_t, 50),
            "ttft_miss_ms_p95": pct(miss_t, 95),
        }
        if args.prefix_cache:
            snap["prefix_cache_report"] = front.prefix_cache_report()
    if args.verify_parity:
        # the bit-exactness gate: EVERY request's served tokens must equal the
        # cache-off per-request generate (greedy only — sampled streams are
        # seeded per request but generate uses a different key stream)
        bad = 0
        for idx, h in handles.items():
            ref = np.asarray(ref_engine.generate(
                prompts[idx][None, :], max_new_tokens=max_news[idx]))
            if not np.array_equal(h.result(), ref[0, prompts[idx].size:]):
                bad += 1
        snap["full_parity_checked"] = len(handles)
        snap["full_parity_bad"] = bad
        snap["parity_ok"] = snap.get("parity_ok", True) and bad == 0
    return snap


def host_config(args):
    """The one place loadgen args become a child-host spec (dims must mirror
    the parity reference engine's). Serving knobs cross the pipe as child
    argv: each child builds its own prefix cache / paged pool / watchdog."""
    from deepspeed_tpu.inference.serving import HostConfig
    return HostConfig(vocab_size=args.vocab_size,
                      max_seq_len=args.max_seq_len, n_embd=args.n_embd,
                      n_layer=args.n_layer, n_head=args.n_head,
                      slots=args.slots, chunk_size=args.chunk_size,
                      prefix_cache=args.prefix_cache,
                      prefix_cache_mb=(args.prefix_cache_mb
                                       if args.prefix_cache else None),
                      prefix_min_hit=(args.prefix_min_hit
                                      if args.prefix_cache else None),
                      prefix_tier_mb=(args.prefix_tier_mb
                                      if args.prefix_cache
                                      and args.prefix_tier_mb else None),
                      kv_page_size=args.kv_page_size,
                      chunk_deadline_s=args.chunk_deadline)


def spawn_hosts(args, n):
    """N subprocess replica hosts (spawns overlap; blocks until every
    versioned hello lands). ``--host-transport socket`` spawns children that
    carry protocol v1 over the CRC-framed TCP transport (serving.net) instead
    of the stdio pipe."""
    from deepspeed_tpu.inference.serving import (HostedReplica,
                                                 SocketHostedReplica)
    cls = (SocketHostedReplica if args.host_transport == "socket"
           else HostedReplica)
    hosts = [cls(host_config(args)) for _ in range(n)]
    for h in hosts:
        h.wait_ready()
    return hosts


def close_hosts(front):
    """Stop every hosted replica's child via the escalation ladder (a
    single-scheduler front is a no-op)."""
    for r in getattr(front, "replicas", ()):
        if getattr(r, "is_hosted", False):
            r.close()


def _build_router(args, serving_cfg, monitor):
    """Router (+ optional Autoscaler/ReplicaSupervisor) for a loadgen run.
    With ``--autoscale`` the router starts at ``--min-replicas`` and the
    autoscaler may grow it to ``--max-replicas`` through the engine factory
    (weights shared with replica 0 — bit-identical replicas). With
    ``--host-replicas`` (or ``--replica-endpoint``) the members are
    subprocess :class:`HostedReplica`\\ s under a :class:`ReplicaSupervisor`,
    and scale-ups attach hosts instead of engines."""
    from deepspeed_tpu.inference.serving import (Autoscaler, AutoscaleConfig,
                                                 HostedReplica,
                                                 ReplicaSupervisor, Router,
                                                 RouterConfig,
                                                 SocketHostedReplica,
                                                 SupervisorConfig)
    endpoints = args.replica_endpoint
    hosted = args.host_replicas or bool(endpoints)
    # with --autoscale an explicit --replicas sets the STARTING size (bounded
    # below by --min-replicas) rather than being silently discarded
    n0 = (max(args.min_replicas, args.replicas) if args.autoscale
          else args.replicas)
    if hosted:
        # adopt running socket children: each endpoint is one member,
        # dialed (not spawned) — geometry flags must match the remote's
        members = [SocketHostedReplica(host_config(args), endpoint=ep)
                   for ep in (endpoints or [])[:n0]]
        for m in members:
            m.wait_ready()
        if len(members) < n0:
            members += spawn_hosts(args, n0 - len(members))
    else:
        first = build_engine(args)
        members = [first] + [build_engine(args, params=first.params)
                             for _ in range(n0 - 1)]
    rcfg = RouterConfig(
        serving=serving_cfg, max_queue=args.max_queue,
        slo_admission=args.slo_admission,
        prefix_aware_routing=args.prefix_aware_routing)
    if args.smoke:
        if hosted:
            # heartbeats ride a 50ms child stream: a 0.15s flatline bound
            # would false-kill a briefly descheduled healthy child
            rcfg.suspect_after_s, rcfg.dead_after_s = 0.5, 1.5
        else:
            rcfg.suspect_after_s, rcfg.dead_after_s = 0.05, 0.15
        rcfg.recover_after_s, rcfg.max_attempts = 30.0, 4
        rcfg.retire_grace_s = 0.5
    front = Router(members, rcfg, monitor=monitor)
    supervisor = None
    if hosted:
        scfg = SupervisorConfig(max_restarts=args.max_restarts,
                                backoff_base_s=args.restart_backoff)
        if args.smoke:
            scfg.backoff_base_s = min(scfg.backoff_base_s, 0.3)
        supervisor = ReplicaSupervisor(front, scfg)
    autoscaler = None
    if args.autoscale:
        acfg = AutoscaleConfig(min_replicas=args.min_replicas,
                               max_replicas=args.max_replicas,
                               ttft_p95_slo_ms=args.ttft_slo_ms)
        if args.smoke:
            acfg.eval_interval_s = 0.02
            acfg.queue_high_per_replica = 4.0
            acfg.breach_evals, acfg.idle_evals = 3, 3
            acfg.cooldown_s, acfg.retire_grace_s = 0.45, 0.2
            acfg.up_cooldown_s = 0.1
            acfg.occupancy_low = 0.45   # slots=1 pools: per-replica share of
            #   a 0.8x-capacity trough spread over 2-3 replicas
        if hosted:
            # grow-by-spawn always spawns locally, matching the fleet's
            # transport (an endpoint fleet grows with a local socket
            # child — nobody listens at a new address)
            grown = (SocketHostedReplica
                     if args.host_transport == "socket" or endpoints
                     else HostedReplica)

            def factory():
                return grown(host_config(args))
        else:
            def factory():
                return build_engine(args, params=first.params)
        autoscaler = Autoscaler(front, factory, acfg)
    return front, autoscaler, supervisor


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="loadgen", description=__doc__)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="mean arrivals per second (Poisson)")
    ap.add_argument("--arrival", default="poisson",
                    help="poisson | bursty (Markov-modulated on/off Poisson) "
                         "| schedule:<rate@dur,...> (piecewise Poisson, e.g. "
                         "schedule:2@3,10@2,2@3, cycling) | "
                         "schedule+bursty:<rate@dur,...> (ON/OFF modulation "
                         "on top of the piecewise base rate)")
    ap.add_argument("--burst-on-s", type=float, default=0.5,
                    help="mean ON-state holding time (bursty)")
    ap.add_argument("--burst-off-s", type=float, default=1.0,
                    help="mean OFF-state holding time (bursty)")
    ap.add_argument("--burst-mult", type=float, default=4.0,
                    help="ON-state rate multiplier over --rate (bursty)")
    ap.add_argument("--prefix-pool", type=int, default=0,
                    help="draw system prompts from a pool of N shared "
                         "prefixes (0 = independent prompts)")
    ap.add_argument("--prefix-len", type=int, default=64,
                    help="shared-prefix length in tokens")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the radix prompt-prefix KV cache")
    ap.add_argument("--prefix-cache-mb", type=float, default=256.0,
                    help="prefix-cache HBM byte budget (MiB)")
    ap.add_argument("--prefix-min-hit", type=int, default=8,
                    help="minimum matched tokens for a cache hit")
    ap.add_argument("--prefix-tier-mb", type=float, default=0.0,
                    help="host-RAM spill rung under the prefix cache's HBM "
                         "budget (MiB; 0 = tier off): LRU-evicted slabs "
                         "spill to host and promote back on a later hit")
    ap.add_argument("--prefix-aware-routing", action="store_true",
                    help="router dispatch scores replicas by expected "
                         "prefill-tokens-saved (cache probe / gossiped "
                         "digests) against outstanding load; session "
                         "affinity demotes to a tiebreaker")
    ap.add_argument("--prefix-insert-on", default="prefill",
                    choices=("prefill", "completion"),
                    help="when a prompt's KV slab enters the trie")
    ap.add_argument("--verify-parity", action="store_true",
                    help="re-check EVERY request bit-identical vs cache-off "
                         "per-request generate (greedy acceptance gate)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON to this file")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk-size", type=int, default=8)
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="KV page size in tokens (default 16). "
                         "Must be a positive multiple of --chunk-size")
    ap.add_argument("--max-queue", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--min-new", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--prompt-dist", default=None,
                    help="mixed-length prompt tails: bimodal:<lo-lo>,<hi-hi>,"
                         "<p_hi> (e.g. bimodal:4-8,64-96,0.3); default = "
                         "uniform [--min-prompt, --max-prompt]")
    ap.add_argument("--output-dist", default=None,
                    help="mixed-length generation budgets, same grammar as "
                         "--prompt-dist; default uniform "
                         "[--min-new, --max-new]")
    ap.add_argument("--vocab-size", type=int, default=512)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--n-embd", type=int, default=128)
    ap.add_argument("--n-layer", type=int, default=4)
    ap.add_argument("--n-head", type=int, default=4)
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help=">=2 drives the multi-replica router")
    ap.add_argument("--host-replicas", action="store_true",
                    help="host each replica in its OWN supervised child "
                         "process (serving.host): replicas pump "
                         "concurrently, chaos kill/stall deliver real "
                         "SIGKILL/SIGSTOP, dead children respawn with "
                         "exponential backoff under --max-restarts")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="per-replica child respawn budget (hosted replicas)")
    ap.add_argument("--restart-backoff", type=float, default=0.5,
                    help="base seconds of the exponential respawn backoff")
    ap.add_argument("--host-transport", default="stdio",
                    choices=("stdio", "socket"),
                    help="hosted-replica transport: 'stdio' (default) = "
                         "JSONL over the child pipe; 'socket' = protocol v1 "
                         "in CRC-framed TCP (serving.net) with session-token "
                         "redial and the net:* chaos seam")
    ap.add_argument("--replica-endpoint", action="append", default=None,
                    metavar="HOST:PORT",
                    help="adopt an already-running socket replica child "
                         "(--serve-socket --listen) at this address; "
                         "repeatable — each endpoint is one router member")
    ap.add_argument("--autoscale", action="store_true",
                    help="attach the metrics-driven Autoscaler: start at "
                         "--min-replicas, scale within "
                         "[--min-replicas, --max-replicas]")
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=3)
    ap.add_argument("--ttft-slo-ms", type=float, default=None,
                    help="autoscaler scale-up signal: recent TTFT p95 above "
                         "this breaches (None = queue-depth signal only)")
    ap.add_argument("--slo-admission", action="store_true",
                    help="SLO-aware admission: shed requests whose estimated "
                         "completion misses their deadline, at admission")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (seconds from submission)")
    ap.add_argument("--chaos", default=None,
                    help="chaos spec (see inference.serving.chaos), e.g. "
                         "'kill:replica=1,when=busy;"
                         "stall:replica=0,when=busy,s=0.8;"
                         "surge:mult=4,at=1.0,s=2.0'")
    ap.add_argument("--chunk-deadline", type=float, default=None,
                    help="per-chunk watchdog deadline in seconds "
                         "(defaults to 0.3 in chaos mode)")
    ap.add_argument("--jsonl-metrics", default=None,
                    help="directory for the jsonl monitor backend")
    ap.add_argument("--trace-out", default=None,
                    help="enable request-scoped tracing; write a Perfetto-"
                         "loadable Chrome trace here at the end of the run")
    ap.add_argument("--flight-out", default=None,
                    help="enable the tail-latency flight recorder + anomaly "
                         "detector (implies tracing) and write the Perfetto-"
                         "loadable flight bundle here at the end of the run; "
                         "the JSON's detail gains the per-request attribution "
                         "breakdown (phase shares at p50 vs p99)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-long tiny-model run (used by the test suite)")
    args = ap.parse_args(argv)
    # length-dist grammar parsed up front (errors before any engine builds)
    try:
        args.prompt_dist = (parse_dist(args.prompt_dist)
                            if args.prompt_dist else None)
        args.output_dist = (parse_dist(args.output_dist)
                            if args.output_dist else None)
    except ValueError as e:
        ap.error(str(e))
    if args.kv_page_size is not None and (
            args.kv_page_size < 1
            or args.kv_page_size % args.chunk_size != 0):
        ap.error(f"--kv-page-size {args.kv_page_size} must be a positive "
                 f"multiple of --chunk-size {args.chunk_size}")
    if args.kv_page_size is None:
        args.kv_page_size = 16         # documented default
    # arrival-mode grammar: poisson | bursty | schedule[+bursty]:<windows>
    args.schedule_windows = None
    args.schedule_bursty = False
    if args.arrival.startswith("schedule+bursty:"):
        args.schedule_windows = parse_schedule(args.arrival.split(":", 1)[1])
        args.schedule_bursty = True
    elif args.arrival.startswith("schedule:"):
        args.schedule_windows = parse_schedule(args.arrival.split(":", 1)[1])
    elif args.arrival not in ("poisson", "bursty"):
        ap.error(f"unknown --arrival {args.arrival!r} (poisson | bursty | "
                 "schedule:<rate@dur,...> | schedule+bursty:<rate@dur,...>)")
    if args.smoke:
        args.requests = min(args.requests, 6)
        args.rate = 100.0
        args.slots, args.chunk_size, args.max_queue = 2, 4, 3
        args.min_prompt, args.max_prompt = 3, 8
        args.min_new, args.max_new = 2, 6
        args.vocab_size, args.max_seq_len = 96, 32
        args.n_embd, args.n_layer, args.n_head = 32, 2, 4
        if args.chaos:
            # the soak needs enough in-flight decode for kills/stalls to land
            # mid-request: longer generations, capacity for the retries
            args.requests, args.max_queue = 8, 8
            args.min_new, args.max_new, args.max_seq_len = 10, 16, 64
        if args.autoscale:
            # the control loop needs a workload that OUTLIVES several
            # evaluation periods: more requests, longer generations, queue
            # headroom — a burst the base smoke serves in ~5 steps gives a
            # scaler nothing to observe. One slot per replica pins capacity
            # low enough that the burst genuinely overloads a lone replica
            # (the paged pool made a 2-slot replica fast enough to drain the
            # old burst before the scaler saw a sustained breach)
            args.requests = max(args.requests, 24)
            args.max_queue = max(args.max_queue, 16)
            args.min_new, args.max_new = 8, 16
            args.max_seq_len = max(args.max_seq_len, 64)
            args.slots = 1
        if args.prefix_pool:
            # shared-prefix smoke: a couple of pool prompts, prefixes long
            # enough to clear the hit threshold, room in the KV cap
            args.requests = max(args.requests, 8)
            args.prefix_pool = min(args.prefix_pool, 2)
            args.prefix_len = min(args.prefix_len, 16)
            args.prefix_min_hit = min(args.prefix_min_hit, 8)
            args.max_queue = max(args.max_queue, 8)
            args.max_seq_len = max(args.max_seq_len,
                                   args.prefix_len + args.max_prompt
                                   + args.max_new + 8)
    if args.prefix_pool:
        need = args.prefix_len + args.max_prompt + args.max_new + 1
        if args.max_seq_len < need:
            ap.error(f"--max-seq-len {args.max_seq_len} too small for "
                     f"prefix({args.prefix_len}) + tail({args.max_prompt}) + "
                     f"new({args.max_new}); need >= {need}")
    if args.prompt_dist or args.output_dist:
        # nothing requires the second mode to be the longer one: a spec like
        # bimodal:64-96,4-8,0.3 is legal, so bound on the max of BOTH modes
        hi_p = (max(args.prompt_dist[1], args.prompt_dist[3])
                if args.prompt_dist else args.max_prompt)
        hi_n = (max(args.output_dist[1], args.output_dist[3])
                if args.output_dist else args.max_new)
        need = (args.prefix_len if args.prefix_pool else 0) + hi_p + hi_n + 1
        if args.max_seq_len < need:
            ap.error(f"--max-seq-len {args.max_seq_len} too small for the "
                     f"length dists' long mode; need >= {need}")
    if args.chaos:
        from deepspeed_tpu.inference.serving import parse_chaos as _pc
        has_replica_event = any(ev.kind != "surge" for ev in _pc(args.chaos))
        if has_replica_event and args.replicas < 2 and not args.autoscale:
            ap.error("--chaos replica events need --replicas >= 2 "
                     "(or --autoscale)")
        if has_replica_event and args.chunk_deadline is None:
            args.chunk_deadline = 0.3
    if args.replica_endpoint:
        # the endpoint list defines the fleet floor (each endpoint is one
        # adopted router member); an explicit larger --replicas tops up with
        # locally-spawned socket children
        args.replicas = max(args.replicas, len(args.replica_endpoint))
    if args.autoscale and args.max_replicas < args.min_replicas:
        ap.error("--max-replicas must be >= --min-replicas")
    if args.autoscale and args.replicas > args.max_replicas:
        ap.error(f"--replicas {args.replicas} exceeds --max-replicas "
                 f"{args.max_replicas}")

    from deepspeed_tpu.utils.fault_injection import apply_fault_env
    apply_fault_env()           # seeded schedule from a parent chaos harness

    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 ServingConfig)
    monitor = None
    if args.jsonl_metrics:
        from deepspeed_tpu.config.config import MonitorConfig
        from deepspeed_tpu.monitor import MonitorMaster
        monitor = MonitorMaster(MonitorConfig(jsonl_monitor={
            "enabled": True, "output_path": args.jsonl_metrics,
            "job_name": "loadgen"}))
    prefix_cfg = None
    if args.prefix_cache:
        from deepspeed_tpu.inference.serving import PrefixCacheConfig
        prefix_cfg = PrefixCacheConfig(
            max_bytes=int(args.prefix_cache_mb * 1024 * 1024),
            host_tier_bytes=int(args.prefix_tier_mb * 1024 * 1024),
            min_hit_tokens=args.prefix_min_hit,
            min_insert_tokens=args.prefix_min_hit,
            insert_on=args.prefix_insert_on)
    serving_cfg = ServingConfig(
        slots=args.slots, chunk_size=args.chunk_size, max_queue=args.max_queue,
        max_seq_len=args.max_seq_len, chunk_deadline_s=args.chunk_deadline,
        prefix_cache=prefix_cfg, kv_page_size=args.kv_page_size)
    from deepspeed_tpu.observability.trace import get_tracer
    tracer = None
    if args.trace_out or args.flight_out:
        tracer = get_tracer().enable(pid_label="loadgen")
    recorder = detector = None
    if args.flight_out:
        from deepspeed_tpu.observability import (AnomalyDetector,
                                                 FlightRecorder, get_registry)
        from deepspeed_tpu.observability.anomaly import install_detector
        # monitor= mirrors the per-request attribution events into
        # --jsonl-metrics (latency/e2e_ms + latency/phase/* rows per
        # completion) without double-writing the telemetry tags
        recorder = FlightRecorder(dump_path=args.flight_out,
                                  monitor=monitor).attach(tracer)
        detector = AnomalyDetector(recorder=recorder)
        install_detector(detector)
        get_registry().attach_monitor(detector)
    # SLO admission lives on the Router: --slo-admission must not silently
    # degrade to the admission-blind single-scheduler path
    if args.replicas > 1 or args.autoscale or args.slo_admission \
            or args.host_replicas:
        front, autoscaler, supervisor = _build_router(args, serving_cfg,
                                                      monitor)
    else:
        autoscaler = supervisor = None
        front = ContinuousBatchingScheduler(build_engine(args), serving_cfg,
                                            monitor=monitor)
    chaos = None
    if args.chaos:
        # built on EVERY front: a surge-only spec is legal against the single
        # scheduler (poll's surge branch never touches a replica), and a
        # chaos run must never silently degrade to nothing
        from deepspeed_tpu.inference.serving import ChaosSchedule, parse_chaos
        chaos = ChaosSchedule(parse_chaos(args.chaos))
    detail = run_load(front, args, chaos=chaos, autoscaler=autoscaler,
                      supervisor=supervisor)
    close_hosts(front)
    if recorder is not None:
        # "where did the p99 go": phase shares at p50 vs p99 over the run's
        # attribution rows, in the artifact next to the latency percentiles
        detail["attribution"] = recorder.breakdown()
    out = {"metric": "serving_tokens_per_sec",
           "value": detail["tokens_per_sec"], "unit": "tok/s",
           "vs_baseline": 0.0, "smoke": bool(args.smoke),
           "chaos": args.chaos, "detail": detail}
    ok = detail["all_finished"] and detail["lost"] == 0 \
        and detail.get("parity_ok", True) \
        and detail.get("chaos_exhausted", True)
    if args.prefix_pool and args.prefix_cache:
        # the prefix-cache acceptance gates ride the JSON
        trace = detail["prefix_trace"]
        hit_p50, miss_p50 = (trace["ttft_hit_ms_p50"],
                             trace["ttft_miss_ms_p50"])
        out["prefix_gates"] = {
            "hit_rate": trace["measured_hit_rate"],
            "hit_rate_ge_0p7": trace["measured_hit_rate"] >= 0.7,
            "ttft_hit_over_miss_p50": (hit_p50 / miss_p50
                                       if hit_p50 and miss_p50 else None),
            "hit_ttft_le_quarter_miss": bool(hit_p50 and miss_p50
                                             and hit_p50 <= 0.25 * miss_p50),
            "parity_ok": detail.get("parity_ok", True),
        }
    if recorder is not None:
        from deepspeed_tpu.observability import get_registry
        from deepspeed_tpu.observability.anomaly import install_detector
        path = recorder.dump(args.flight_out, reason="end_of_run")
        out["flight"] = {"path": path, "anomaly_trips": detector.trips,
                         **recorder.stats()}
        get_registry().detach_monitor(detector)
        install_detector(None)
        recorder.detach()
    if tracer is not None:
        if args.trace_out:
            n = tracer.export_chrome(args.trace_out)
            out["trace"] = {"path": args.trace_out, "spans": n,
                            "dropped": tracer.dropped}
        tracer.disable()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
