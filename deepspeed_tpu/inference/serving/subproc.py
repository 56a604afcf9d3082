"""Subprocess-hosted serving replica: a real process boundary for the router.

The router's in-process :class:`~.router.EngineReplica` simulates death with a
flag; this module hosts the same engine + scheduler stack in a CHILD process so
a replica can die by real ``SIGKILL`` and the recovery model is proven
end-to-end. It exists because the router's whole recovery design is
**prefix-only**: the parent's view of a replica is nothing but the token
prefixes streamed back so far, so after a kill the request continues
bit-identically by re-prefilling ``prompt + streamed_prefix`` anywhere else —
no shared memory, no checkpoint, no device state crosses the process boundary.
:mod:`.host` builds full Router membership (async submit/harvest, heartbeat
watchdog, supervised respawn) on top of this pipe.

Protocol v1 (JSONL over stdin/stdout, every line flushed — the stream must be
truthful at the instant a SIGKILL lands):

- child → ``{"ready": true, "proto": 1, "pid": p, "faults_armed": N,
  "cap": c, "max_prompt_len": m, "slots": s}`` once the engine is built
  (the **versioned hello**: the parent refuses a proto it does not speak —
  :class:`HostProtocolError` — instead of mis-parsing a drifted stream;
  ``faults_armed`` from :func:`~...utils.fault_injection.apply_fault_env`,
  the ``DS_TPU_FAULT_SPEC`` env contract, same as ``deepspeed-serve``);
- child → ``{"hb": n, "t": wall, "busy": b, "running": r, "queued": q,
  "free_slots": f, "occupancy": o, "rss_bytes": m}`` — a heartbeat every
  ``--hb-interval`` from a dedicated child thread (a scheduler step
  legitimately blocks for seconds inside a first-shape XLA compile; a
  main-loop heartbeat would read as a flatline). The parent stamps replica
  liveness from these CHILD messages, not from its own pump: pipe silence IS
  the death signal — SIGSTOP/SIGKILL silence it, while a wedged dispatch
  stays covered by the scheduler's own chunk watchdog, whose failures stream
  as per-request error states;
- parent → ``{"id": i, "prompt": [...], "max_new_tokens": n, "seed": s,
  "eos_token_id": e|null, "deadline_s": d|absent, "trace_id": t|absent,
  "parent_span": p|absent}`` submits a request (``trace_id``/``parent_span``
  propagate the parent's span context: the child's tracer joins its spans to
  that trace, so a subprocess-hosted replica's prefill/decode-chunk spans land
  on the SAME trace id as the frontend's);
- parent → ``{"cmd": "cancel", "id": i}`` cancels an in-flight request;
- child → ``{"id": i, "tokens": [...], "done": bool, "state": "...",
  "finish_reason": "..."}`` after every scheduler step in which request ``i``
  gained tokens (cumulative prefix, not a delta — idempotent under
  lost/duplicated reads);
- child → ``{"spans": [...]}`` whenever traced spans finished since the last
  step (each span dict is ``observability.trace`` wire format; the parent
  ingests them into its own tracer under a per-host lane);
- parent → ``{"cmd": "stop"}`` (or EOF, or SIGTERM) drains and exits 0.

**Malformed-line quarantine**: a garbled line in either direction is counted
and reported (child answers ``{"badline": ..., "error": ...}``; the parent
keeps a bounded sample in ``quarantined``/``quarantined_sample``) — it never
crashes the peer. One bad line loses one message, not the replica.

**Stop escalation ladder** (:meth:`SubprocessReplica.stop`): drain (stop cmd,
``drain_s`` deadline) → ``SIGTERM`` grace (``term_s``; the child handles
SIGTERM as a graceful drain too) → ``SIGKILL``. A wedged child can no longer
hang the caller — the ladder always terminates.

Determinism contract: the child builds its engine with the same fixed init
seed as an in-parent engine of identical dims, so the parent can compute
bit-exact references with its OWN engine — weights never cross the pipe.

Run as ``python -m deepspeed_tpu.inference.serving.subproc --vocab-size ...``
(the parent-side :class:`SubprocessReplica` wraps spawn/stream/kill).
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

#: wire-protocol version carried in the hello line; the parent refuses any
#: other value (HostProtocolError) rather than mis-parse a drifted stream
PROTO_VERSION = 1


class HostProtocolError(RuntimeError):
    """The child spoke a pipe protocol the parent does not (hello missing a
    ``proto`` field, or carrying an unsupported version)."""


def _rss_bytes() -> int:
    try:
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return 0


def _cache_gossip(sched) -> dict:
    """Additive heartbeat payload: the child cache's KV economy + the digest
    ladder of its resident prefixes, so the parent's prefix-aware dispatch can
    score this replica without a round trip. ``{}`` when the cache is off
    (field absent keeps pre-PR-19 heartbeats byte-compatible)."""
    pc = sched.prefix_cache
    if pc is None:
        return {}
    try:
        t = sched.telemetry
        s = pc.stats()
        return {"cache": {
            "hits": int(t.prefix_hits), "misses": int(t.prefix_misses),
            "hit_tokens": int(t.prefix_hit_tokens),
            "cached_bytes": int(s["cached_bytes"]),
            "spilled_bytes": int(s["spilled_bytes"]),
            "spills": int(s["spills"]),
            "promotions": int(s["promotions"]),
            "entries": int(s["entries"]),
            "host_entries": int(s["host_entries"]),
            "digests": pc.digest_report(),
        }}
    except Exception:
        return {}                   # gossip is best-effort; hb must not die


def child_main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="serving.subproc")
    ap.add_argument("--family", default="gpt2", choices=("gpt2", "llama"))
    ap.add_argument("--vocab-size", type=int, default=96)
    ap.add_argument("--max-seq-len", type=int, default=64)
    ap.add_argument("--n-embd", type=int, default=32)
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--n-head", type=int, default=4)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--chunk-size", type=int, default=2)
    ap.add_argument("--hb-interval", type=float, default=0.05)
    ap.add_argument("--prefix-cache", action="store_true")
    # per-child serving knobs (HostConfig ships these across the spawn —
    # PR 16: parent flags now DO cross the pipe instead of being refused)
    ap.add_argument("--prefix-cache-mb", type=float, default=None)
    ap.add_argument("--prefix-tier-mb", type=float, default=None)
    ap.add_argument("--prefix-min-hit", type=int, default=4)
    ap.add_argument("--kv-page-size", type=int, default=None)
    ap.add_argument("--chunk-deadline", type=float, default=None)
    # socket transport (net.py): serve protocol v1 over framed TCP instead of
    # the stdio pipe — --listen "host:port"/"port" (0 = ephemeral, announced
    # as a {"listening": port} bootstrap line on stdout) or --connect
    # "host:port" (the child runs the dial/backoff loop)
    ap.add_argument("--serve-socket", action="store_true")
    ap.add_argument("--listen", default=None)
    ap.add_argument("--connect", default=None)
    args = ap.parse_args(argv)

    # protocol v1 state shared with the transport: the socket IO (when
    # enabled) must exist BEFORE the heavy jax import so the bootstrap line
    # lands fast and early frames buffer while the engine builds
    lines: List[str] = []
    eof = threading.Event()
    term = threading.Event()        # SIGTERM = graceful drain (ladder rung)
    signal.signal(signal.SIGTERM, lambda signum, frame: term.set())
    sock_io = None
    if args.serve_socket:
        from .net import ChildSocketIO
        sock_io = ChildSocketIO(lines=lines, term=term, listen=args.listen,
                                connect=args.connect)

    import jax.numpy as jnp

    from ...observability.trace import SpanContext, get_tracer
    from ...utils.fault_injection import apply_fault_env
    from ..config import DeepSpeedInferenceConfig
    from ..engine import InferenceEngine
    from ...models.causal_lm import gpt2_cfg, llama_cfg
    from .prefix_cache import PrefixCacheConfig
    from .scheduler import ContinuousBatchingScheduler, ServingConfig

    armed = apply_fault_env()       # DS_TPU_FAULT_SPEC: seeded child schedule
    family = {"gpt2": gpt2_cfg, "llama": llama_cfg}[args.family]
    engine = InferenceEngine(
        family(vocab_size=args.vocab_size, max_seq_len=args.max_seq_len,
               n_embd=args.n_embd, n_layer=args.n_layer, n_head=args.n_head,
               dtype=jnp.float32),
        DeepSpeedInferenceConfig(dtype="float32",
                                 max_out_tokens=args.max_seq_len))
    prefix = None
    if args.prefix_cache:
        prefix = PrefixCacheConfig(
            min_hit_tokens=args.prefix_min_hit,
            min_insert_tokens=args.prefix_min_hit, insert_on="prefill")
        if args.prefix_cache_mb is not None:
            prefix.max_bytes = int(args.prefix_cache_mb * 1024 * 1024)
        if args.prefix_tier_mb is not None:
            prefix.host_tier_bytes = int(args.prefix_tier_mb * 1024 * 1024)
    page_kw = ({"kv_page_size": args.kv_page_size}
               if args.kv_page_size is not None else {})
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=args.slots, chunk_size=args.chunk_size,
        max_seq_len=args.max_seq_len, prefix_cache=prefix,
        chunk_deadline_s=args.chunk_deadline,
        **page_kw))

    out = sys.stdout
    emit_lock = threading.Lock()

    def emit(obj):
        if sock_io is not None:     # framed TCP transport (net.py)
            with emit_lock:
                sock_io.emit(obj)
            return
        with emit_lock:             # hb thread + main loop share the pipe
            out.write(json.dumps(obj) + "\n")
            out.flush()             # every line visible before any SIGKILL

    emit({"ready": True, "proto": PROTO_VERSION, "pid": os.getpid(),
          "faults_armed": armed, "cap": sched.cap,
          "max_prompt_len": sched.executor.max_prompt_len,
          "slots": args.slots})

    # heartbeat THREAD, not a main-loop tick: a scheduler step legitimately
    # blocks for seconds inside a first-shape XLA compile or a long chunk, and
    # main-loop heartbeats would read as a flatline to the parent's
    # pipe-silence watchdog (the in-process router's post-step re-stamp has no
    # equivalent across a pipe). The thread proves PROCESS liveness — SIGSTOP/
    # SIGKILL silence it — while a wedged dispatch stays covered by the
    # scheduler's own chunk watchdog, whose failures stream as request errors.
    hb_stop = threading.Event()

    def hb_loop():
        seq = 0
        while not hb_stop.is_set():
            seq += 1
            try:
                pool = sched.executor.pool
                emit({"hb": seq, "t": time.time(), "busy": bool(sched.busy),
                      "running": len(sched.active_requests),
                      "queued": sched.queue_depth,
                      "free_slots": int(pool.free_slots),
                      "occupancy": float(pool.occupancy),
                      "rss_bytes": _rss_bytes(),
                      # per-child cache economics for the parent's /statusz
                      # (None = cache disabled in this child)
                      "prefix_hit_rate": (float(sched.prefix_hit_rate)
                                          if sched.prefix_cache is not None
                                          else None),
                      # additive v1 field (PR 19): cache gossip for
                      # prefix-aware routing + the fleet KV-economy rollup.
                      # Old parents ignore unknown hb fields; absent on
                      # cache-less children
                      **_cache_gossip(sched)})
            except (BrokenPipeError, ValueError, OSError):
                return              # parent went away: nothing to report to
            hb_stop.wait(args.hb_interval)

    threading.Thread(target=hb_loop, daemon=True).start()

    if sock_io is None:
        def reader():
            for line in sys.stdin:
                if line.strip():
                    lines.append(line.strip())
            eof.set()

        threading.Thread(target=reader, daemon=True).start()
    tracer = get_tracer()
    handles: Dict[int, object] = {}
    reported: Dict[int, int] = {}
    stop = False
    while not stop or sched.busy:
        while lines:
            raw = lines.pop(0)
            try:
                req = json.loads(raw)
            except (ValueError, TypeError) as e:
                # malformed-line quarantine: one bad line loses one message,
                # never the replica — report it and keep serving
                emit({"badline": raw[:200], "error": type(e).__name__})
                continue
            if req.get("cmd") == "stop":
                stop = True
                continue
            if req.get("cmd") == "cancel":
                h = handles.get(int(req.get("id", -1)))
                if h is not None:
                    h.cancel()
                continue
            if req.get("cmd") == "cancel_all":
                # a fresh socket connection superseded a severed one: the
                # parent evicted the in-flight work with prefixes, so free
                # its slots here instead of leaking them to orphans
                for h in list(handles.values()):
                    h.cancel()
                continue
            ctx = None
            if req.get("trace_id"):
                # parent propagated a span context: join its trace (enabling
                # lazily keeps the un-traced soak at zero cost)
                if not tracer.enabled:
                    tracer.enable(pid_label=f"subproc{os.getpid()}")
                ctx = SpanContext(str(req["trace_id"]),
                                  str(req.get("parent_span") or ""))
            try:
                h = sched.submit(req["prompt"],
                                 max_new_tokens=req.get("max_new_tokens"),
                                 eos_token_id=req.get("eos_token_id"),
                                 deadline_s=req.get("deadline_s"),
                                 seed=req.get("seed", 0), trace_ctx=ctx)
            except Exception as e:
                # an inadmissible request fails alone (the parent pre-checks
                # admission, so this is belt-and-braces, not a normal path)
                # — and it fails TERMINALLY: a quarantine report alone would
                # leave the parent's handle open forever (no timeout, no
                # retry); a per-id error state routes it through the router's
                # standard replica-failure retry instead
                emit({"badline": raw[:200], "error": f"{type(e).__name__}: "
                                                     f"{e}"[:200]})
                if "id" in req:
                    emit({"id": int(req["id"]), "tokens": [], "done": True,
                          "state": "cancelled", "finish_reason": "error",
                          "prefix_hit_tokens": 0})
                continue
            handles[int(req["id"])] = h
        if eof.is_set() or term.is_set():
            stop = True
        if sched.busy:
            sched.step()
        elif not stop:
            time.sleep(0.005)
        for rid, h in list(handles.items()):
            n = len(h.tokens)
            if n != reported.get(rid) or h.done:
                reported[rid] = n
                emit({"id": rid, "tokens": [int(t) for t in h.tokens],
                      "done": bool(h.done), "state": h.state.value,
                      "finish_reason": h.finish_reason,
                      "prefix_hit_tokens": h.prefix_hit_tokens})
                if h.done:
                    del handles[rid]
        if tracer.enabled:
            finished = tracer.drain()
            if finished:
                # every line flushed: spans streamed BEFORE any SIGKILL lands
                emit({"spans": finished})
    hb_stop.set()
    emit({"summary": sched.telemetry.snapshot()})
    if sock_io is not None:
        sock_io.close()
    return 0


def refuse_chip_child(env) -> None:
    """The one-process-per-chip rule for replica children on THIS host (see
    ``utils.device.claims_chips``): the child inherits the parent's platform,
    and a parent that serves, or built a reference engine, already holds the
    chips the child would claim."""
    from ...utils.device import claims_chips
    if claims_chips(env):
        raise RuntimeError(
            "a hosted replica child on this host would claim its TPU chips, "
            "which libtpu gives to one process at a time. On a chip host run "
            "in-process replicas (one process drives every local chip, one "
            "replica per device) or attach a child started on its own host "
            "by endpoint; for a CPU run set JAX_PLATFORMS=cpu.")


class SubprocessReplica:
    """Parent-side handle on a subprocess-hosted replica.

    Spawns the child, streams its JSONL progress on a reader thread, and keeps
    the per-request **token prefixes** — the only state the recovery model is
    allowed to use. ``sigkill()`` is a real ``SIGKILL``: no atexit, no flush,
    no goodbye; whatever was streamed is all the parent has, exactly like a
    preempted TPU host. ``stop()`` is the escalation ladder: drain deadline →
    SIGTERM grace → SIGKILL (a wedged child cannot hang the caller)."""

    def __init__(self, repo_root: str, env: Optional[Dict[str, str]] = None,
                 prefix_cache: bool = False, cmd: Optional[List[str]] = None,
                 **dims):
        jax_child = cmd is None         # a cmd override is a jax-free stub
        if cmd is None:
            cmd = [sys.executable, "-m",
                   "deepspeed_tpu.inference.serving.subproc"]
            for k, v in dims.items():
                cmd += [f"--{k.replace('_', '-')}", str(v)]
            if prefix_cache:
                cmd += ["--prefix-cache"]
        full_env = dict(os.environ)
        try:
            # the determinism contract is self-enforcing: the child must draw
            # the same init bits as the parent's reference engine, and
            # jax_threefry_partitionable changes them — propagate the
            # parent's setting (programmatic config does not inherit)
            import jax
            full_env.setdefault(
                "JAX_THREEFRY_PARTITIONABLE",
                "1" if jax.config.jax_threefry_partitionable else "0")
        except Exception:
            pass                    # parent never imported jax: child default
        if env:
            full_env.update(env)
        if jax_child:
            refuse_chip_child(full_env)
        self.proc = subprocess.Popen(
            cmd, cwd=repo_root, env=full_env, text=True,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL)
        self.ready: Optional[Dict] = None
        self.hb: Optional[Dict] = None           # last heartbeat line
        self.last_line_at: Optional[float] = None  # monotonic stamp of ANY
        #   well-formed child line — the host's pipe-liveness signal
        self.progress: Dict[int, Dict] = {}      # id -> last streamed line
        # malformed-line quarantine (both directions): counted + sampled,
        # never fatal — one bad line loses one message, not the replica
        self.quarantined = 0                     # child → parent garbage
        self.quarantined_sample: Optional[str] = None
        self.child_quarantined = 0               # child-reported bad input
        self.escalations = 0                     # stop-ladder rungs climbed
        # traced submissions: id -> (trace_id, parent_span, t_submit) — what
        # abandon_open_lanes needs to force-close a killed child's lanes
        self._trace_ctx: Dict[int, tuple] = {}
        # child-side finished spans: bounded drop-oldest, same contract as
        # the tracer's own ring — a traced soak must not grow a Python list
        # forever on the parent
        self.spans: "deque" = deque(maxlen=200_000)
        self.spans_dropped = 0
        self.summary: Optional[Dict] = None
        self._lock = threading.Lock()
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self):
        for line in self.proc.stdout:
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                with self._lock:
                    self.quarantined += 1
                    self.quarantined_sample = line[:200]
                continue
            with self._lock:
                self.last_line_at = time.monotonic()
                if "ready" in obj:
                    self.ready = obj
                elif "hb" in obj:
                    obj["_rx_t"] = time.time()   # pipe-lag measurement anchor
                    self.hb = obj
                elif "badline" in obj:
                    self.child_quarantined += 1
                elif "summary" in obj:
                    self.summary = obj["summary"]
                elif "spans" in obj:
                    overflow = (len(self.spans) + len(obj["spans"])
                                - self.spans.maxlen)
                    if overflow > 0:
                        self.spans_dropped += overflow
                    self.spans.extend(obj["spans"])
                elif "id" in obj:
                    rid = int(obj["id"])
                    self.progress[rid] = obj
                    if obj.get("done"):
                        # completed lanes need no abandon context: without
                        # this, _trace_ctx grows one entry per traced
                        # request for the life of the replica
                        self._trace_ctx.pop(rid, None)

    def wait_ready(self, timeout: float = 120.0) -> Dict:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            with self._lock:
                if self.ready is not None:
                    if self.ready.get("proto") != PROTO_VERSION:
                        raise HostProtocolError(
                            f"child hello carries proto="
                            f"{self.ready.get('proto')!r}; this parent "
                            f"speaks proto={PROTO_VERSION}")
                    return self.ready
            if self.proc.poll() is not None:
                raise RuntimeError("subprocess replica died during startup")
            time.sleep(0.02)
        raise TimeoutError("subprocess replica never became ready")

    def submit(self, rid: int, prompt, max_new_tokens: int, seed: int = 0,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               parent_span: Optional[str] = None) -> None:
        req = {"id": int(rid), "prompt": [int(t) for t in prompt],
               "max_new_tokens": int(max_new_tokens), "seed": int(seed),
               "eos_token_id": eos_token_id}
        if deadline_s is not None:
            req["deadline_s"] = float(deadline_s)
        if trace_id:
            req["trace_id"] = trace_id
            req["parent_span"] = parent_span
            self._trace_ctx[int(rid)] = (trace_id, parent_span,
                                         time.monotonic())
        self.proc.stdin.write(json.dumps(req) + "\n")
        self.proc.stdin.flush()

    def cancel(self, rid: int) -> None:
        """Ask the child to cancel request ``rid`` (best-effort: a dead pipe
        is already the stronger cancellation)."""
        try:
            self.proc.stdin.write(json.dumps({"cmd": "cancel",
                                              "id": int(rid)}) + "\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError):
            pass

    def abandon_open_lanes(self, tracer) -> List[int]:
        """Force-close a killed child's in-flight request lanes.

        The child's ``replica_request`` spans were still OPEN when the SIGKILL
        landed — they never committed, so the dead lane would be a hole in the
        trace. The parent knows the span context it handed each request, so it
        commits one ``state=abandoned`` ``replica_request`` span per undone
        traced request — the same force-close the in-process router performs
        at absorb time — and the flight recorder's retention/attribution see
        the abandoned lane joined to the retry attempt by trace id. Returns
        the request ids closed."""
        from ...observability.trace import SpanContext
        now = time.monotonic()
        closed = []
        for rid, (tid, pspan, t0) in list(self._trace_ctx.items()):
            # every entry is consumed: done lanes need no closing span, and a
            # second abandon call must not re-emit spans for lanes this one
            # already force-closed (pop, not del: the reader thread prunes
            # done lanes concurrently)
            self._trace_ctx.pop(rid, None)
            if self.done(rid):
                continue
            ctx = SpanContext(str(tid), str(pspan or ""))
            tracer.record_span(
                "replica_request", ctx, t0, now,
                attrs={"state": "abandoned", "reason": "sigkill",
                       "request_id": rid,
                       "tokens_streamed": len(self.tokens(rid))},
                tid="subproc-abandoned")
            closed.append(rid)
        return closed

    def take_spans(self) -> List[Dict]:
        """Child-side spans streamed so far (drained); ingest into the parent
        tracer to join the cross-process trace."""
        with self._lock:
            out = list(self.spans)
            self.spans.clear()
        return out

    def tokens(self, rid: int) -> List[int]:
        """The streamed prefix — all the parent may know about a request."""
        with self._lock:
            obj = self.progress.get(int(rid))
            return list(obj["tokens"]) if obj else []

    def done(self, rid: int) -> bool:
        with self._lock:
            obj = self.progress.get(int(rid))
            return bool(obj and obj["done"])

    def wait_tokens(self, rid: int, n: int, timeout: float = 180.0
                    ) -> List[int]:
        """Block until request ``rid`` has streamed >= n tokens (or finished)."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            toks = self.tokens(rid)
            if len(toks) >= n or self.done(rid):
                return toks
            if self.proc.poll() is not None:
                return toks          # died: the streamed prefix is the answer
            time.sleep(0.02)
        raise TimeoutError(f"request {rid}: {len(self.tokens(rid))}/{n} "
                           "tokens before timeout")

    def sigkill(self) -> None:
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)

    def stop(self, drain_s: float = 10.0, term_s: float = 5.0) -> int:
        """Stop escalation ladder: drain (stop cmd, ``drain_s`` deadline) →
        SIGTERM grace (``term_s``) → SIGKILL. Always returns — a wedged child
        (stalled, stopped, or ignoring its stdin) can no longer hang the
        caller on an unbounded ``wait``. ``escalations`` counts the rungs
        climbed past the graceful drain."""
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write(json.dumps({"cmd": "stop"}) + "\n")
                self.proc.stdin.flush()
                self.proc.stdin.close()
            except (BrokenPipeError, OSError):
                pass
            try:
                self.proc.wait(timeout=drain_s)
            except subprocess.TimeoutExpired:
                self.escalations += 1
                try:
                    self.proc.send_signal(signal.SIGTERM)
                except ProcessLookupError:
                    pass
                try:
                    self.proc.wait(timeout=term_s)
                except subprocess.TimeoutExpired:
                    # SIGKILL works even on a SIGSTOPped child (SIGTERM does
                    # not deliver until SIGCONT) — the ladder's backstop
                    self.escalations += 1
                    try:
                        self.proc.send_signal(signal.SIGKILL)
                    except ProcessLookupError:
                        pass
                    self.proc.wait(timeout=30)
        return self.proc.returncode

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None


if __name__ == "__main__":
    sys.exit(child_main())
