"""``deepspeed-serve``: the serving-subsystem entrypoint.

Two modes over the same frontend (a single scheduler, or — with
``--replicas N`` — the health-supervised multi-replica :class:`Router`):

- **stdin mode** (default): read one JSON request per line
  (``{"prompt": [ids...], "max_new_tokens": 16, "eos_token_id": null,
  "deadline_s": null, "seed": 0, "session": null}``), stream one JSON result per
  completed request to stdout (tokens + TTFT/TPOT + finish reason), then a final
  summary line. Backpressured submissions are retried after the scheduler's hint.
- **--selftest**: synthesize a small random-weight model and a burst of random
  requests; exit 0 iff every request completes. With ``--replicas >= 2`` the
  selftest is a kill-and-retry round trip: a replica is killed mid-decode and
  the run passes only if every request still completes with greedy outputs
  bit-identical to an unkilled run (checkpointless retry proven end-to-end).

``--prefix-cache`` enables the radix prompt-prefix KV cache (per replica:
shared system prompts skip prefill, greedy outputs bit-identical to cache-off;
``--prefix-cache-mb`` bounds the slab HBM budget).
``--autoscale --min-replicas N --max-replicas M`` attaches the elastic control
plane (``serving.autoscale``): replica count follows queue depth / recent TTFT
p95 with hysteresis + cooldown, scale-up warms through the RECOVERING probe,
scale-down retires gracefully (in-flight requests migrate bit-identically).
``--slo-admission`` sheds requests whose estimated completion misses their
``deadline_s`` at admission (an ``{"error": ...}`` line with the retry-after
hint) instead of letting them expire after burning decode steps.
``--host-replicas`` hosts each replica in its OWN supervised child process
(``serving.host``): replicas pump concurrently instead of sharing one serial
loop, chaos ``kill``/``stall`` deliver real SIGKILL/SIGSTOP, and a
``ReplicaSupervisor`` respawns dead children with exponential backoff under
``--max-restarts`` (exhausted budget pins the replica DEAD; survivors keep
serving). ``/statusz`` then carries child PIDs and restart counts.
``--chaos "<spec>"`` schedules replica kills/stalls (see ``serving.chaos``), and
a ``DS_TPU_FAULT_SPEC`` env (``utils.fault_injection.fault_env``) is armed at
startup — the hook chaos tests use to inject deterministically into
subprocess-hosted serve processes. Metrics go to the jsonl monitor backend when
``--jsonl-metrics DIR`` is given.

Observability (PR 10, ``docs/OBSERVABILITY.md``):

- ``--metrics-port P`` serves Prometheus text exposition at
  ``http://127.0.0.1:P/metrics`` from the process metrics registry (the same
  counters the BENCH JSON reports);
- ``--trace-out FILE`` enables the request-scoped span tracer and writes a
  Perfetto-loadable Chrome trace on exit (``FILE.jsonl`` alongside it when the
  path ends in ``.json``... pass a ``.jsonl`` path to stream spans instead);
- ``--profile-dir DIR [--profile-steps N]`` arms on-demand XLA profiler
  capture: ``kill -USR2 <pid>`` captures the next N decode chunks/prefills to
  DIR (TensorBoard/Perfetto-loadable device trace).
"""

import argparse
import json
import sys
import time
from typing import Optional

import numpy as np


def _build_engine(args, params=None):
    import jax.numpy as jnp

    from ...models.causal_lm import gpt2_cfg, llama_cfg
    from ..config import DeepSpeedInferenceConfig
    from ..engine import InferenceEngine
    family = {"gpt2": gpt2_cfg, "llama": llama_cfg}[args.family]
    cfg = family(vocab_size=args.vocab_size, max_seq_len=args.max_seq_len,
                 n_embd=args.n_embd, n_layer=args.n_layer, n_head=args.n_head,
                 dtype={"float32": jnp.float32, "bfloat16": jnp.bfloat16}
                 [args.dtype])
    engine = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype=args.dtype, max_out_tokens=args.max_seq_len,
        tensor_parallel={"tp_size": args.tp}), params=params)
    if args.checkpoint:
        engine.load_checkpoint(args.checkpoint)
    return engine


def _build_engines(args, n: int):
    """N replica engines with SHARED weights (replica 0's params are reused —
    bit-identical replicas, init cost paid once; params are never donated, so
    sharing the buffers is safe)."""
    first = _build_engine(args)
    return [first] + [_build_engine(args, params=first.params)
                      for _ in range(n - 1)]


def _close_hosts(front) -> None:
    """Stop every hosted replica's child through the escalation ladder (a
    no-op for in-process replicas / the single-scheduler front)."""
    for r in getattr(front, "replicas", []):
        if getattr(r, "is_hosted", False):
            r.close()


def _make_monitor(args) -> Optional[object]:
    if not args.jsonl_metrics:
        return None
    from ...config.config import MonitorConfig
    from ...monitor import MonitorMaster
    return MonitorMaster(MonitorConfig(jsonl_monitor={
        "enabled": True, "output_path": args.jsonl_metrics,
        "job_name": "deepspeed-serve"}))


def make_status_provider(front, autoscaler=None, recorder=None,
                         detector=None, supervisor=None):
    """``/statusz`` JSON assembler over a serving frontend (scheduler or
    router): replica health + outstanding work (hosted replicas add child
    PID + restart count), queue depth, degradation rung, paged-KV pressure,
    prefix hit rate, recent anomaly trips, the last autoscale decisions with
    their triggering signals, the replica supervisor's restart/pinned
    accounting, and the flight recorder's retention stats."""
    is_router = hasattr(front, "replicas")

    def status():
        doc = {"t": time.time(),
               "kind": "router" if is_router else "scheduler"}
        if is_router:
            tel = front.telemetry
            doc.update({
                "queue_depth": front.queue_depth,
                "draining": front.draining,
                "degradation_rung": front.degradation_rung.value,
                "degradation_rung_name": front.degradation_rung.name,
                "replicas": [
                    {"id": r.id,
                     "health": front.health[r.id].state.value,
                     "outstanding": r.outstanding,
                     "running": r.running,
                     "queued": r.queued,
                     "retiring": front.health[r.id].retiring,
                     **({"pid": r.child_pid, "restarts": r.restarts,
                         "prefix_hit_rate": (
                             r.scheduler.prefix_hit_rate
                             if r.scheduler.prefix_cache_report().get(
                                 "child") else None)}
                        if getattr(r, "is_hosted", False) else {}),
                     **({"severed": r.severed,
                         "reconnects": r.reconnects,
                         "rtt_ms": r.rtt_ms()}
                        if getattr(r, "is_socket", False) else {})}
                    for r in front.replicas],
                "retired_replicas": list(front.retired),
                "counters": {
                    "submitted": tel.submitted, "completed": tel.completed,
                    "retried": tel.retried, "evicted": tel.evicted,
                    "rejected": tel.rejected, "shed": tel.shed,
                    "deferred": tel.deferred, "expired": tel.expired,
                    "handed_off": tel.handed_off},
            })
            # a hosted replica's pages are its child's: only the pools in
            # this process have page counts to add up
            paged = [r.scheduler.executor.pool.stats() for r in front.replicas
                     if not getattr(r, "is_hosted", False)]
            if paged:
                doc["pages"] = {
                    "pages_in_use": sum(p["pages_in_use"] for p in paged),
                    "total_pages": sum(p["total_pages"] for p in paged),
                    "page_fragmentation": (
                        float(np.mean([p["page_fragmentation"]
                                       for p in paged]))),
                    "prefix_shared_pages": sum(p["prefix_shared_pages"]
                                               for p in paged)}
            if any(r.scheduler.prefix_cache is not None
                   for r in front.replicas):
                rep = front.prefix_cache_report()
                doc["prefix_hit_rate"] = rep.get("hit_rate")
            # fleet KV economy (PR 19): admission-level hit rate + tiered
            # byte/movement counters across in-process AND hosted replicas
            # (hosted numbers come from heartbeat gossip)
            if front._kv_economy_enabled():
                kv = front.kv_economy_report()
                doc["kv_economy"] = {
                    "fleet_hit_rate": kv["fleet_hit_rate"],
                    "prefill_tokens_skipped": kv["prefill_tokens_skipped"],
                    "cached_bytes": kv["cached_bytes"],
                    "spilled_bytes": kv["spilled_bytes"],
                    "spills_total": kv["spills_total"],
                    "promotions_total": kv["promotions_total"],
                    "prefix_routed": kv["prefix_routed"],
                    "prefix_saved_tokens": kv["prefix_saved_tokens"]}
            specs = [r.scheduler.telemetry.spec for r in front.replicas
                     if getattr(r.scheduler.telemetry, "spec_enabled", False)]
            if specs:
                proposed = sum(s.proposed for s in specs)
                doc["speculative"] = {
                    "proposed": proposed,
                    "accepted": sum(s.accepted for s in specs),
                    "acceptance_rate": (sum(s.accepted for s in specs)
                                        / proposed if proposed else 0.0),
                    "passes_per_token": (
                        sum(s.rounds for s in specs)
                        / max(1, sum(s.tokens for s in specs)))}
        else:
            tel = front.telemetry
            pool = front.executor.pool
            doc.update({
                "queue_depth": front.queue_depth,
                "slot_occupancy": pool.occupancy,
                "counters": {"completed": tel.completed,
                             "rejected": tel.rejected,
                             "cancelled": tel.cancelled,
                             "expired": tel.expired,
                             "evicted": tel.evicted,
                             "tokens_total": tel.tokens_total},
            })
            doc["pages"] = pool.stats()
            if front.prefix_cache is not None:
                doc["prefix_hit_rate"] = front.prefix_hit_rate
            if getattr(tel, "spec_enabled", False):
                s = tel.spec
                doc["speculative"] = {
                    "proposed": s.proposed, "accepted": s.accepted,
                    "acceptance_rate": s.acceptance_rate,
                    "passes_per_token": s.passes_per_token}
        if autoscaler is not None:
            doc["autoscale"] = {
                "target_replicas": autoscaler.target_replicas,
                "scale_ups": autoscaler.scale_ups,
                "scale_downs": autoscaler.scale_downs,
                "last_decisions": list(autoscaler.decisions)[-5:]}
        if supervisor is not None:
            doc["hosts"] = supervisor.report()
        if detector is not None:
            doc["anomalies"] = {"trips": detector.trips,
                                "recent": list(detector.recent)[-8:]}
        if recorder is not None:
            doc["flight"] = recorder.stats()
        return doc

    return status


def make_health_provider(front):
    """``/healthz`` liveness/readiness: the process answering IS liveness;
    readiness = at least one LIVE replica AND the degradation ladder below
    ADMISSION_CLOSED (a router that rejects every submission is alive but not
    ready). The single-scheduler path is ready whenever it answers."""
    is_router = hasattr(front, "replicas")

    def health():
        if not is_router:
            return True, {"live": True, "ready": True, "kind": "scheduler"}
        from .router import DegradationRung, ReplicaState
        live = sum(1 for r in front.replicas
                   if front.health[r.id].state == ReplicaState.LIVE)
        rung = front.degradation_rung
        ready = (live >= 1
                 and rung.value < DegradationRung.ADMISSION_CLOSED.value
                 and not front.draining)
        return ready, {"live": True, "ready": ready, "kind": "router",
                       "live_replicas": live,
                       "degradation_rung": rung.value,
                       "draining": front.draining}

    return health


def _result_line(h) -> str:
    return json.dumps({
        "id": h.id, "state": h.state.value, "finish_reason": h.finish_reason,
        "tokens": [int(t) for t in h.tokens],
        "ttft_ms": None if h.ttft is None else h.ttft * 1e3,
        "tpot_ms": None if h.tpot is None else h.tpot * 1e3,
    })


def _serve_stdin(sched, out=sys.stdout, inp=None, chaos=None,
                 autoscaler=None, supervisor=None):
    """Streaming serve loop: requests are admitted as their lines arrive (a
    reader thread feeds a queue, so a client may keep the pipe open and read
    results before sending more) and each result is emitted the moment its
    request completes. A malformed or inadmissible line fails alone — an
    ``{"error": ...}`` line is emitted and serving continues.

    ``sched`` is any frontend with the scheduler protocol (``submit`` /
    ``step`` / ``busy`` / ``telemetry``) — a single
    :class:`ContinuousBatchingScheduler` or a multi-replica :class:`Router`
    (router-only fields like ``session`` are forwarded when present).
    ``chaos`` is an optional :class:`~.chaos.ChaosSchedule` polled every loop.
    """
    import queue as _queue
    import threading

    from .router import AdmissionShedError
    from .scheduler import QueueFullError
    inp = inp if inp is not None else sys.stdin
    is_router = hasattr(sched, "replicas")
    lines: "_queue.Queue" = _queue.Queue()
    _EOF = object()

    def _reader():
        for line in inp:
            lines.put(line)
        lines.put(_EOF)

    threading.Thread(target=_reader, daemon=True).start()
    handles, pending, eof = [], [], False
    not_before = 0.0
    while not eof or pending or sched.busy:
        if is_router and sched.draining:
            break                            # SIGTERM: graceful drain below
        if chaos is not None:
            chaos.poll(sched)
        if autoscaler is not None:
            autoscaler.step()
        if supervisor is not None:
            supervisor.step()       # respawn dead hosted replicas (backoff)
        while True:                          # drain whatever the reader has
            try:
                line = lines.get_nowait()
            except _queue.Empty:
                break
            if line is _EOF:
                eof = True
                break
            if line.strip():
                pending.append(line.strip())
        while pending and time.monotonic() >= not_before:
            try:
                req = json.loads(pending[0])
                kwargs = dict(max_new_tokens=req.get("max_new_tokens"),
                              eos_token_id=req.get("eos_token_id"),
                              deadline_s=req.get("deadline_s"),
                              seed=req.get("seed", 0))
                if is_router:
                    kwargs["session"] = req.get("session")
                    kwargs["priority"] = req.get("priority", 0)
                handles.append(sched.submit(
                    np.asarray(req["prompt"], np.int32), **kwargs))
                pending.pop(0)
            except AdmissionShedError as e:  # SLO shed is TERMINAL for this
                # line: its deadline re-anchors at every resubmission, so a
                # deadline below bare service time would re-shed forever and
                # head-of-line-block every later request — fail it with the
                # hint and keep serving (checked before its QueueFullError
                # parent, which IS worth resubmitting)
                out.write(json.dumps({"error": f"shed: {e}",
                                      "retry_after": e.retry_after,
                                      "line": pending.pop(0)[:200]}) + "\n")
            except QueueFullError as e:      # backpressure: drain, then resubmit
                not_before = time.monotonic() + e.retry_after
                break
            except Exception as e:           # bad line: fail it, keep serving
                out.write(json.dumps({"error": f"{type(e).__name__}: {e}",
                                      "line": pending.pop(0)[:200]}) + "\n")
        if sched.busy or (is_router and getattr(sched, "retiring_pending",
                                                False)):
            # an idle scale-down still needs steps: only the router's retire
            # sweep detaches a RETIRING replica, and idle is exactly when
            # scale-downs happen
            sched.step()
        elif not eof or pending:
            time.sleep(0.01)                 # idle: await input, don't spin
        for h in [h for h in handles if h.done]:
            out.write(_result_line(h) + "\n")
            handles.remove(h)
    if is_router and sched.draining:
        # graceful drain: finish in-flight chunks, then emit a hand-off spec
        # per unfinished request (re-submittable on another router) and an
        # error line per never-admitted client line — nothing silently dropped
        for spec in sched.drain():
            out.write(json.dumps({"handoff": spec}) + "\n")
        for line in pending:
            out.write(json.dumps({"error": "draining", "line": line[:200]})
                      + "\n")
        for h in handles:
            out.write(_result_line(h) + "\n")
    return (sched.snapshot() if is_router else sched.telemetry.snapshot())


def _selftest(sched, n_requests: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    handles = []
    from .scheduler import QueueFullError
    reqs = [(rng.integers(0, vocab, size=int(rng.integers(3, 12))).astype(np.int32),
             int(rng.integers(2, 10))) for _ in range(n_requests)]
    while reqs or sched.busy:
        while reqs:
            prompt, max_new = reqs[0]
            try:
                handles.append(sched.submit(prompt, max_new_tokens=max_new))
                reqs.pop(0)
            except QueueFullError:
                break
        sched.step()
    ok = all(h.state.value == "finished" for h in handles)
    return ok, sched.telemetry.snapshot()


def _selftest_router(router, engines, n_requests: int, vocab: int,
                     seed: int = 0):
    """Kill-and-retry round trip: submit a burst of greedy requests, kill one
    replica the moment it is mid-decode, and require (1) every request
    completes, (2) at least one was evicted+retried, (3) every output is
    bit-identical to the unkilled per-request ``generate`` reference."""
    from .chaos import ChaosEvent, ChaosSchedule
    from .scheduler import QueueFullError
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, vocab, size=int(rng.integers(4, 10))
                          ).astype(np.int32),
             int(rng.integers(8, 16))) for _ in range(n_requests)]
    victim = len(router.replicas) - 1
    chaos = ChaosSchedule([ChaosEvent(kind="kill", replica=victim,
                                      when="busy")])
    pending = list(reqs)
    handles = []
    while pending or router.busy:
        chaos.poll(router)
        while pending:
            prompt, max_new = pending[0]
            try:
                handles.append(router.submit(prompt, max_new_tokens=max_new))
                pending.pop(0)
            except QueueFullError:
                break
        router.step()
    snap = router.snapshot()
    ok = all(h.state.value == "finished" for h in handles)
    retried = sum(h.retried for h in handles)
    parity = True
    for h, (prompt, max_new) in zip(handles, reqs):
        ref = engines[0].generate(prompt[None, :], max_new_tokens=max_new)
        if not np.array_equal(h.result(), np.asarray(ref)[0, prompt.size:]):
            parity = False
    snap["kill_fired"] = chaos.exhausted
    snap["retried_requests"] = retried
    snap["parity_ok"] = parity
    ok = ok and parity and snap["lost"] == 0 and chaos.exhausted and retried > 0
    return ok, snap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="deepspeed-serve", description=__doc__)
    ap.add_argument("--family", default="gpt2", choices=("gpt2", "llama"))
    ap.add_argument("--vocab-size", type=int, default=256)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--n-embd", type=int, default=64)
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--n-head", type=int, default=4)
    ap.add_argument("--dtype", default="float32", choices=("float32", "bfloat16"))
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--checkpoint", default=None,
                    help="training checkpoint dir to serve")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk-size", type=int, default=8)
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="KV page size in tokens (default 16). "
                         "Must be a positive multiple of --chunk-size so "
                         "page boundaries stay chunk-aligned")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative decoding: every decode chunk becomes "
                         "one draft-propose / one-pass-verify round (n-gram "
                         "self-speculation — greedy output is bit-identical, "
                         "sampled stays exactly target-distributed)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per verify window (default 4)")
    ap.add_argument("--spec-ngram-max", type=int, default=4,
                    help="longest suffix n-gram the proposer matches "
                         "(default 4; tried down to 1)")
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=1,
                    help=">=2 serves through the multi-replica router")
    ap.add_argument("--host-replicas", action="store_true",
                    help="host each replica in its OWN supervised child "
                         "process (serving.host): replicas pump concurrently "
                         "instead of sharing one serial loop, chaos kills/"
                         "stalls deliver real SIGKILL/SIGSTOP, and a "
                         "ReplicaSupervisor respawns dead children with "
                         "exponential backoff under --max-restarts")
    ap.add_argument("--host-transport", default="stdio",
                    choices=("stdio", "socket"),
                    help="hosted-replica transport: 'stdio' (default) = "
                         "JSONL over the child's stdin/stdout pipe; "
                         "'socket' = the same protocol v1 carried in "
                         "length-prefixed CRC-framed TCP (serving.net) with "
                         "session-token redial, so a severed connection "
                         "evicts-and-retries instead of killing the child")
    ap.add_argument("--replica-endpoint", action="append", default=None,
                    metavar="HOST:PORT",
                    help="adopt an ALREADY-RUNNING socket replica child "
                         "(started with --serve-socket --listen) at this "
                         "address instead of spawning one; repeatable — each "
                         "endpoint becomes one router member. Implies the "
                         "hosted-router path; geometry flags must match the "
                         "remote child's")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="per-replica child respawn budget (hosted replicas; "
                         "exhausted -> pinned DEAD, survivors keep serving)")
    ap.add_argument("--restart-backoff", type=float, default=0.5,
                    help="base seconds of the exponential respawn backoff")
    ap.add_argument("--autoscale", action="store_true",
                    help="metrics-driven autoscaling: start at --min-replicas "
                         "and let the control plane scale within "
                         "[--min-replicas, --max-replicas] from queue depth "
                         "and recent TTFT p95")
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--slo-admission", action="store_true",
                    help="SLO-aware admission: requests whose estimated "
                         "completion misses their deadline_s are shed at "
                         "admission with a load-adaptive retry_after")
    ap.add_argument("--chaos", default=None,
                    help="chaos spec, e.g. 'kill:replica=1,at=0.5;"
                         "stall:replica=0,when=busy,s=0.6' (see serving.chaos)")
    ap.add_argument("--chunk-deadline", type=float, default=None,
                    help="per-chunk watchdog deadline in seconds")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the radix prompt-prefix KV cache (shared "
                         "system prompts skip prefill; greedy outputs stay "
                         "bit-identical to cache-off)")
    ap.add_argument("--prefix-cache-mb", type=float, default=256.0,
                    help="prefix-cache HBM byte budget (MiB)")
    ap.add_argument("--prefix-tier-mb", type=float, default=0.0,
                    help="host-RAM rung under the HBM budget (MiB, 0 = off): "
                         "LRU-evicted prefix entries spill here as dense "
                         "slabs and promote back on a later hit (a slab "
                         "copy instead of a re-prefill)")
    ap.add_argument("--prefix-min-hit", type=int, default=8,
                    help="minimum matched tokens for a cache hit")
    ap.add_argument("--prefix-aware-routing", action="store_true",
                    help="score dispatch by expected prefill-tokens-saved "
                         "(in-process trie probe / hosted heartbeat digest "
                         "gossip) against outstanding load; session affinity "
                         "demotes to a tiebreaker")
    ap.add_argument("--jsonl-metrics", default=None,
                    help="directory for the jsonl monitor backend")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics exposition on this port")
    ap.add_argument("--trace-out", default=None,
                    help="enable request-scoped tracing; write a "
                         "Perfetto-loadable Chrome trace here on exit")
    ap.add_argument("--flight-out", default=None,
                    help="enable the tail-latency flight recorder + anomaly "
                         "detector (implies tracing); write the Perfetto-"
                         "loadable flight bundle here on exit — SIGUSR1, "
                         "router drain, and anomaly trips write numbered "
                         "siblings (SIGUSR2 stays the XLA profiler)")
    ap.add_argument("--profile-dir", default=None,
                    help="arm on-demand XLA profiler capture to this logdir "
                         "(trigger with SIGUSR2)")
    ap.add_argument("--profile-steps", type=int, default=4,
                    help="decode chunks/prefills per profiler capture")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--requests", type=int, default=8,
                    help="selftest request count")
    args = ap.parse_args(argv)

    # a seeded fault schedule may have been serialized into our environment by
    # a parent chaos harness (utils.fault_injection.fault_env)
    from ...utils.fault_injection import apply_fault_env
    apply_fault_env()

    # observability spine: tracer / flight recorder / Prometheus exposition /
    # status plane / profiler capture
    from ...observability import (AnomalyDetector, FlightRecorder,
                                  configure_capture, get_registry, get_tracer,
                                  start_metrics_server)
    from ...observability.anomaly import install_detector
    tracer = None
    if args.trace_out or args.flight_out:
        tracer = get_tracer().enable(pid_label="deepspeed-serve")
        if args.trace_out and args.trace_out.endswith(".jsonl"):
            tracer.stream_to(args.trace_out)
    recorder = detector = None
    if args.flight_out:
        recorder = FlightRecorder(dump_path=args.flight_out).attach(tracer)
        recorder.install_sigusr1()          # SIGUSR2 stays the XLA profiler
        detector = AnomalyDetector(recorder=recorder)
        install_detector(detector)
        get_registry().attach_monitor(detector)
    metrics_server = None
    # the front doesn't exist yet when the port opens: the providers read a
    # late-bound slot, and /healthz honestly reports not-ready until it lands
    _providers = {"status": None, "health": None}

    def _statusz():
        fn = _providers["status"]
        return fn() if fn is not None else {"starting": True}

    def _healthz():
        fn = _providers["health"]
        if fn is None:
            return False, {"live": True, "ready": False, "starting": True}
        return fn()

    if args.metrics_port is not None:
        metrics_server = start_metrics_server(args.metrics_port,
                                              status_provider=_statusz,
                                              health_provider=_healthz)
        print(json.dumps({"metrics_port": metrics_server.server_port}),
              file=sys.stderr)
    if args.profile_dir:
        configure_capture(args.profile_dir, num_ticks=args.profile_steps)

    def _obs_epilogue():
        # every exit path (selftest included) must land the trace/bundle the
        # user asked for and release the exposition port
        if recorder is not None:
            path = recorder.dump(args.flight_out, reason="exit")
            print(json.dumps({"flight_out": path, **recorder.stats()}),
                  file=sys.stderr)
            get_registry().detach_monitor(detector)
            install_detector(None)
            recorder.detach()
        if tracer is not None:
            if args.trace_out and not args.trace_out.endswith(".jsonl"):
                n = tracer.export_chrome(args.trace_out)
                print(json.dumps({"trace_out": args.trace_out, "spans": n}),
                      file=sys.stderr)
            tracer.close_stream()
        if metrics_server is not None:
            metrics_server.shutdown()

    from .prefix_cache import PrefixCacheConfig
    from .scheduler import ContinuousBatchingScheduler, ServingConfig
    prefix_cfg = None
    if args.prefix_cache:
        prefix_cfg = PrefixCacheConfig(
            max_bytes=int(args.prefix_cache_mb * 1024 * 1024),
            host_tier_bytes=int(args.prefix_tier_mb * 1024 * 1024),
            min_hit_tokens=args.prefix_min_hit,
            min_insert_tokens=args.prefix_min_hit)
    if args.kv_page_size < 1 or args.kv_page_size % args.chunk_size != 0:
        raise SystemExit(
            f"--kv-page-size {args.kv_page_size} must be a positive multiple "
            f"of --chunk-size {args.chunk_size} (page boundaries stay "
            "chunk-aligned)")
    serving_cfg = ServingConfig(slots=args.slots, chunk_size=args.chunk_size,
                                max_queue=args.max_queue,
                                max_seq_len=args.max_seq_len,
                                chunk_deadline_s=args.chunk_deadline,
                                prefix_cache=prefix_cfg,
                                kv_page_size=args.kv_page_size,
                                speculate=args.speculate, spec_k=args.spec_k,
                                spec_ngram_max=args.spec_ngram_max)
    monitor = _make_monitor(args)
    if recorder is not None:
        # mirror per-request attribution events into the monitor backend
        # (telemetry already feeds both monitor and registry directly)
        recorder.monitor = monitor
    chaos = None
    autoscaler = None
    supervisor = None
    # SLO admission lives on the Router: a bare --slo-admission must not
    # silently degrade to the admission-blind single-scheduler path
    if args.replicas > 1 or args.autoscale or args.slo_admission \
            or args.host_replicas or args.replica_endpoint:
        from .autoscale import Autoscaler, AutoscaleConfig
        from .chaos import ChaosSchedule, parse_chaos
        from .router import Router, RouterConfig
        if args.autoscale and args.replicas > args.max_replicas:
            raise SystemExit(f"--replicas {args.replicas} exceeds "
                             f"--max-replicas {args.max_replicas}")
        # with --autoscale an explicit --replicas sets the STARTING size
        # (bounded below by --min-replicas), it is not silently discarded
        n0 = (max(args.min_replicas, args.replicas) if args.autoscale
              else args.replicas)
        rcfg = RouterConfig(serving=serving_cfg, max_queue=args.max_queue,
                            slo_admission=args.slo_admission,
                            prefix_aware_routing=args.prefix_aware_routing)
        if args.host_replicas or args.replica_endpoint:
            from .host import (HostConfig, HostedReplica, ReplicaSupervisor,
                               SocketHostedReplica, SupervisorConfig)
            if args.checkpoint:
                raise SystemExit("--host-replicas serves the deterministic-"
                                 "init model; --checkpoint does not cross "
                                 "the pipe")
            if args.dtype != "float32" or args.tp != 1:
                raise SystemExit("--host-replicas children build float32 "
                                 "tp=1 engines (the determinism contract "
                                 "behind bit-exact retry parity)")
            # serving knobs cross the pipe as child argv (HostConfig.dims):
            # each child builds its own prefix cache / paged pool / watchdog
            hcfg = HostConfig(
                family=args.family, vocab_size=args.vocab_size,
                max_seq_len=args.max_seq_len, n_embd=args.n_embd,
                n_layer=args.n_layer, n_head=args.n_head, slots=args.slots,
                chunk_size=args.chunk_size,
                prefix_cache=args.prefix_cache,
                prefix_cache_mb=(args.prefix_cache_mb
                                 if args.prefix_cache else None),
                prefix_tier_mb=(args.prefix_tier_mb
                                if args.prefix_cache and args.prefix_tier_mb
                                else None),
                prefix_min_hit=(args.prefix_min_hit
                                if args.prefix_cache else None),
                kv_page_size=args.kv_page_size,
                chunk_deadline_s=args.chunk_deadline)
            if args.replica_endpoint:
                # adopt running children: the endpoint list IS the fleet
                members = [SocketHostedReplica(hcfg, endpoint=ep)
                           for ep in args.replica_endpoint]
            elif args.host_transport == "socket":
                members = [SocketHostedReplica(hcfg) for _ in range(n0)]
            else:
                members = [HostedReplica(hcfg) for _ in range(n0)]
            for m in members:
                m.wait_ready()
            engines = None
            # autoscale grow-by-spawn always spawns locally — even an
            # endpoint fleet grows with a local socket child, not a dial
            # to an address nobody is listening on
            if args.replica_endpoint or args.host_transport == "socket":
                engine_factory = lambda: SocketHostedReplica(hcfg)  # noqa: E731
            else:
                engine_factory = lambda: HostedReplica(hcfg)   # noqa: E731
            if args.selftest:
                # looser than the in-process selftest: heartbeats ride a
                # 50ms child stream, and a 0.15s flatline bound would
                # false-kill a briefly descheduled healthy child
                rcfg.suspect_after_s, rcfg.dead_after_s = 0.5, 1.5
                rcfg.recover_after_s, rcfg.max_attempts = 30.0, 4
        else:
            engines = _build_engines(args, n0)
            members = engines
            engine_factory = lambda: _build_engine(   # noqa: E731
                args, params=engines[0].params)
            if args.selftest:
                # tight health thresholds: the kill-and-retry round trip
                # should prove itself in ~a second, not wait out production
                # timeouts
                rcfg.suspect_after_s, rcfg.dead_after_s = 0.05, 0.15
                rcfg.recover_after_s, rcfg.max_attempts = 30.0, 4
        front = Router(members, rcfg, monitor=monitor)
        front.install_sigterm_drain()      # SIGTERM = graceful drain
        if args.host_replicas or args.replica_endpoint:
            supervisor = ReplicaSupervisor(front, SupervisorConfig(
                max_restarts=args.max_restarts,
                backoff_base_s=args.restart_backoff))
        if args.autoscale:
            autoscaler = Autoscaler(
                front, engine_factory,
                AutoscaleConfig(min_replicas=args.min_replicas,
                                max_replicas=args.max_replicas))
        if args.chaos:
            chaos = ChaosSchedule(parse_chaos(args.chaos))
        _providers["status"] = make_status_provider(
            front, autoscaler=autoscaler, recorder=recorder,
            detector=detector, supervisor=supervisor)
        _providers["health"] = make_health_provider(front)
        if args.selftest:
            ref_engines = (engines if engines is not None
                           else [members[0].engine])
            ok, snap = _selftest_router(front, ref_engines, args.requests,
                                        args.vocab_size)
            _close_hosts(front)
            print(json.dumps({"selftest_ok": ok, **snap}))
            _obs_epilogue()
            return 0 if ok else 1
    else:
        if args.chaos:
            raise SystemExit("--chaos needs --replicas >= 2")
        if args.host_replicas:
            raise SystemExit("--host-replicas serves through the router")
        engine = _build_engine(args)
        front = ContinuousBatchingScheduler(engine, serving_cfg,
                                            monitor=monitor)
        _providers["status"] = make_status_provider(front, recorder=recorder,
                                                    detector=detector)
        _providers["health"] = make_health_provider(front)
        if args.selftest:
            ok, snap = _selftest(front, args.requests, args.vocab_size)
            print(json.dumps({"selftest_ok": ok, **snap}))
            _obs_epilogue()
            return 0 if ok else 1
    snap = _serve_stdin(front, chaos=chaos, autoscaler=autoscaler,
                        supervisor=supervisor)
    _close_hosts(front)
    print(json.dumps(snap), file=sys.stderr)
    _obs_epilogue()
    return 0


if __name__ == "__main__":
    sys.exit(main())
