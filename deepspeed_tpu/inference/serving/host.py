"""Process-parallel replica hosts: supervised subprocess replicas as
first-class :class:`~.router.Router` members.

The in-process :class:`~.router.EngineReplica` pump is SERIAL — N replicas on
one host add zero machine parallelism, which left half of the PR 12 autoscale
claim unmeasurable ("static-min breaches the latency gate the autoscaled
router holds"). This module closes that gap: a :class:`HostedReplica` hosts
the engine + scheduler stack in its OWN child process (the
:mod:`.subproc` JSONL pipe), pumps itself concurrently with every other
replica, and presents the exact replica surface the router, autoscaler, chaos
harness, and telemetry already speak:

- **async submit/harvest** — ``submit`` writes one JSONL line and returns a
  :class:`HostedHandle` immediately; the child decodes on its own clock and
  streams cumulative token prefixes back; ``step()`` (the router's pump slot)
  only *harvests* — it never blocks on child compute;
- **child-stamped heartbeats** — replica liveness is the child's own
  heartbeat/progress stream, not the parent's serial pump: ``step()`` copies
  the pipe's last-line stamp into ``last_heartbeat``, so pipe silence ages the
  replica through the existing ``LIVE→SUSPECT→DEAD`` machine exactly like a
  flatlined in-process replica (the **pipe-silence watchdog**). A vanished
  process fast-fails the heartbeat instead of waiting out ``dead_after_s``;
- **real-signal chaos** — ``kill(sig="KILL"|"TERM")`` delivers the actual
  signal; ``stall(s)`` is ``SIGSTOP``/``SIGCONT`` (the chaos harness routes
  in-process flag semantics here automatically);
- **supervision** — :class:`ReplicaSupervisor` respawns dead children with
  exponential backoff under a bounded restart budget (mirroring the
  launcher's ``--max_restarts`` semantics); a respawned replica re-enters
  service through the router's existing ``DEAD→RECOVERING`` half-open
  warm-probe path, and an exhausted budget pins the replica DEAD while the
  router keeps serving on the survivors. Restart/backoff/RSS/pipe-lag
  telemetry is declared in ``observability.schema`` (``host/*``);
- **prefix-only recovery** — unchanged: the parent's view of a replica is the
  streamed token prefixes, so retry after any of the above is bit-identical
  to an unkilled run (the determinism contract lets :attr:`HostedReplica.engine`
  lazily build a parent-side reference engine with identical weights —
  weights never cross the pipe).

The per-child prefix cache is internal to the child — the parent never holds
a trie handle (``scheduler.prefix_cache`` reads ``None``), so chaos
``when=restore`` remains an in-process-replica trigger. What DOES cross the
pipe (PR 19, additive heartbeat field ``cache``) is the cache's gossip: hit
economics, tiered-cache byte/movement counters, and the digest ladder of
resident prefixes, which the router's prefix-aware dispatch scores with
:func:`~.prefix_cache.match_from_digests` instead of a probe round trip.

Threading: like the router — drive :meth:`ReplicaSupervisor.step` from the
same loop as ``router.step()`` (``deepspeed-serve --host-replicas`` and the
loadgen do exactly that). The pipe reader threads only fill buffers.
"""

import itertools
import os
import signal
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ...observability import flight as obs_flight
from ...observability.metrics import RegistryFeed
from ...observability.trace import get_tracer
from ...utils.logging import logger
from .router import ReplicaDeadError, ReplicaState
from .scheduler import QueueFullError, RequestState, validate_admission
from .subproc import SubprocessReplica


def _default_repo_root() -> str:
    import deepspeed_tpu
    return os.path.dirname(os.path.dirname(
        os.path.abspath(deepspeed_tpu.__file__)))


@dataclass
class HostConfig:
    """Child-process dims + parent-side supervision knobs for one host."""
    family: str = "gpt2"               # gpt2 | llama (child builds fp32 —
    #   the determinism contract behind parent-side parity references)
    vocab_size: int = 96
    max_seq_len: int = 64
    n_embd: int = 32
    n_layer: int = 2
    n_head: int = 4
    slots: int = 2
    chunk_size: int = 2
    hb_interval_s: float = 0.05        # child heartbeat period
    startup_grace_s: float = 120.0     # boot window (jax import + engine
    #   build + XLA warm inside the child): the parent keeps the heartbeat
    #   alive while the process exists and the hello has not landed — the
    #   pipe-silence watchdog arms only once the child is ready
    stop_drain_s: float = 10.0         # stop ladder rung 1: graceful drain
    stop_term_s: float = 5.0           # stop ladder rung 2: SIGTERM grace
    default_max_new_tokens: int = 32
    retry_after_s: float = 0.25        # backpressure hint on a full host
    repo_root: Optional[str] = None
    env: Optional[Dict[str, str]] = None
    cmd_override: Optional[List[str]] = None   # tests: replace the child argv
    #   (protocol/supervision lanes run against stub children, no jax import)
    # ---------------------------------------- per-child serving knobs (PR 16)
    # these cross the spawn as child argv — the parent-side refusal to
    # combine --prefix-cache with --host-replicas is lifted: each child owns
    # its cache/pool and reports hit-rate economics in its heartbeat
    prefix_cache: bool = False
    prefix_cache_mb: Optional[float] = None
    prefix_tier_mb: Optional[float] = None   # host-RAM rung under the HBM
    #   budget (PR 19): evicted device entries spill here and promote back
    prefix_min_hit: Optional[int] = None
    kv_page_size: Optional[int] = None
    chunk_deadline_s: Optional[float] = None
    # ----------------------------------------------- socket transport (PR 16)
    socket_mode: str = "listen"        # SocketHostedReplica spawn wiring:
    #   "listen" = child binds an ephemeral port, parent dials it;
    #   "connect" = parent listens, child dials (--connect)

    def dims(self) -> Dict:
        d = {"family": self.family, "vocab_size": self.vocab_size,
             "max_seq_len": self.max_seq_len, "n_embd": self.n_embd,
             "n_layer": self.n_layer, "n_head": self.n_head,
             "slots": self.slots, "chunk_size": self.chunk_size,
             "hb_interval": self.hb_interval_s}
        for key, val in (("prefix_cache_mb", self.prefix_cache_mb),
                         ("prefix_tier_mb", self.prefix_tier_mb),
                         ("prefix_min_hit", self.prefix_min_hit),
                         ("kv_page_size", self.kv_page_size),
                         ("chunk_deadline", self.chunk_deadline_s)):
            if val is not None:
                d[key] = val
        return d


def reference_engine(config: HostConfig):
    """Parent-side engine bit-identical to the child's (same family/dims,
    fp32, same fixed init seed) — the parity checks and drain-handoff
    references compute against it; weights never cross the pipe."""
    import jax.numpy as jnp

    from ...models.causal_lm import gpt2_cfg, llama_cfg
    from ..config import DeepSpeedInferenceConfig
    from ..engine import InferenceEngine
    family = {"gpt2": gpt2_cfg, "llama": llama_cfg}[config.family]
    return InferenceEngine(
        family(vocab_size=config.vocab_size, max_seq_len=config.max_seq_len,
               n_embd=config.n_embd, n_layer=config.n_layer,
               n_head=config.n_head, dtype=jnp.float32),
        DeepSpeedInferenceConfig(dtype="float32",
                                 max_out_tokens=config.max_seq_len))


class HostedHandle:
    """Parent-side view of one request on a hosted replica: the
    ``RequestHandle`` surface the router touches, filled from the child's
    streamed JSONL progress lines (cumulative prefixes — the only state the
    recovery model may use)."""

    def __init__(self, host, rid: int, prompt, max_new_tokens: int,
                 eos_token_id, deadline_s, seed: int):
        self._host = host
        self.id = int(rid)
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.deadline_s = deadline_s
        self.seed = int(seed)
        self.arrival = time.monotonic()
        self.state = RequestState.QUEUED
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None
        self.ttft: Optional[float] = None
        self.tpot: Optional[float] = None
        self.slot: Optional[int] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.prefix_hit_tokens = 0
        self._cancel = False
        self._span = None        # replica-side spans live in the child; the
        #   router's absorb path tolerates None here

    def cancel(self) -> None:
        self._cancel = True
        self._host._cancel_request(self.id)

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.EXPIRED, RequestState.EVICTED)

    def result(self) -> np.ndarray:
        return np.asarray(self.tokens, dtype=np.int32)

    def output_ids(self) -> np.ndarray:
        return np.concatenate([self.prompt.astype(np.int32), self.result()])


class _HostPoolView:
    """The KV-pool slice of the replica surface (occupancy/slot accounting)
    from the child's heartbeat stream."""

    def __init__(self, host):
        self._host = host

    @property
    def free_slots(self) -> int:
        return self._host.free_slots

    @property
    def occupancy(self) -> float:
        hb = self._host.hb
        if hb is not None and "occupancy" in hb:
            return float(hb["occupancy"])
        slots = max(1, self._host.config.slots)
        return min(1.0, self._host.outstanding / slots)


class _HostExecutorView:
    def __init__(self, host):
        self._host = host
        self.pool = _HostPoolView(host)

    @property
    def max_prompt_len(self) -> int:
        ready = self._host._rep.ready if self._host._rep else None
        if ready and "max_prompt_len" in ready:
            return int(ready["max_prompt_len"])
        return self._host.config.max_seq_len - 1   # executor default

    @property
    def chunk_warm(self) -> bool:
        """True once THIS child process streamed a token (chaos ``when=busy``
        requires a warm replica so kills land mid-decode, not mid-compile)."""
        return self._host._warm

    def stall_next(self, seconds: float) -> None:
        # the chaos harness's stall hook: a hosted replica wedges by real
        # SIGSTOP (SIGCONT after the window), not by an in-process sleep
        self._host.stall(seconds)


class _HostTelemetryView:
    def __init__(self, host):
        self._host = host

    @property
    def tokens_total(self) -> int:
        return self._host._tokens_total


class _HostSchedulerView:
    """The scheduler-shaped surface the router/autoscaler/chaos/status plane
    read off a replica. Parent-side accounting only — the child's scheduler
    is the truth, mirrored through hello/heartbeat/progress lines."""

    def __init__(self, host):
        self._host = host
        self.executor = _HostExecutorView(host)
        self.telemetry = _HostTelemetryView(host)
        self.prefix_cache = None       # per-child caches are child-internal

    @property
    def cap(self) -> int:
        ready = self._host._rep.ready if self._host._rep else None
        if ready and "cap" in ready:
            return int(ready["cap"])
        return self._host.config.max_seq_len

    @property
    def queue_depth(self) -> int:
        return self._host.queued

    @property
    def busy(self) -> bool:
        return self._host.outstanding > 0

    @property
    def active_requests(self) -> List[HostedHandle]:
        return list(self._host._handles.values())

    def evict_all(self, reason: str = "evicted") -> List[HostedHandle]:
        """Whole-replica eviction (breaker death / drain / retire-grace) —
        delegated to the host, whose transport knows whether the process or
        merely the connection is the casualty."""
        return self._host.evict_all(reason)

    @property
    def prefix_hit_rate(self) -> float:
        """The child's admission-level hit rate, mirrored off its heartbeat
        (0.0 while the child's cache is disabled or before the first hb)."""
        hb = self._host.hb
        if hb is not None and hb.get("prefix_hit_rate") is not None:
            return float(hb["prefix_hit_rate"])
        return 0.0

    def prefix_cache_report(self) -> Dict:
        hb = self._host.hb
        if hb is not None and hb.get("prefix_hit_rate") is not None:
            rep = {"enabled": True, "child": True,
                   "hit_rate": float(hb["prefix_hit_rate"])}
            # PR 19 gossip: the child's KV economy rides the heartbeat so the
            # router's fleet aggregation covers hosted replicas too (stale hb
            # → stale numbers, never an error)
            cache = hb.get("cache")
            if isinstance(cache, dict):
                for key in ("hits", "misses", "hit_tokens", "cached_bytes",
                            "spilled_bytes", "spills", "promotions",
                            "entries", "host_entries"):
                    if key in cache:
                        rep[key] = cache[key]
            return rep
        return {"enabled": False}


class HostedReplica:
    """A subprocess-hosted replica as a first-class Router member (the
    ``EngineReplica`` contract over the :mod:`.subproc` pipe)."""

    #: Router duck-type marker: objects carrying this join the replica set
    #: as themselves instead of being wrapped in an in-process EngineReplica
    replica_protocol = True
    is_hosted = True

    def __init__(self, config: Optional[HostConfig] = None,
                 replica_id: int = -1, wait_ready: bool = False):
        self.config = config or HostConfig()
        self.id = int(replica_id)
        self.scheduler = _HostSchedulerView(self)
        self._ids = itertools.count()
        self._handles: Dict[int, HostedHandle] = {}
        self._rep: Optional[SubprocessReplica] = None
        self._engine = None
        self._killed = False
        self._stopped = False
        self._warm = False
        self._tokens_total = 0
        self.restarts = 0              # stamped by the supervisor
        self.last_heartbeat = time.monotonic()
        self.last_pump_attempt = self.last_heartbeat
        self._spawned_at = self.last_heartbeat
        self._last_step_at = 0.0
        self._stall_timer: Optional[threading.Timer] = None
        self._tracer = get_tracer()
        self._spawn()
        if wait_ready:
            self.wait_ready()

    def bind(self, replica_id: int) -> None:
        """Router attach point: ids are router-assigned, monotonic, never
        reused."""
        self.id = int(replica_id)

    # -------------------------------------------------------------- lifecycle
    def _spawn(self) -> None:
        cfg = self.config
        self._rep = SubprocessReplica(
            cfg.repo_root or _default_repo_root(), env=cfg.env,
            prefix_cache=cfg.prefix_cache,
            cmd=list(cfg.cmd_override) if cfg.cmd_override else None,
            **(cfg.dims() if cfg.cmd_override is None else {}))
        self._killed = False
        self._warm = False
        self._spawned_at = time.monotonic()
        self.last_heartbeat = self._spawned_at

    def wait_ready(self, timeout: float = 180.0) -> Dict:
        return self._rep.wait_ready(timeout)

    @property
    def ready(self) -> bool:
        return self._rep is not None and self._rep.ready is not None

    @property
    def hb(self) -> Optional[Dict]:
        return self._rep.hb if self._rep is not None else None

    @property
    def child_pid(self) -> Optional[int]:
        return self._rep.proc.pid if self._rep is not None else None

    @property
    def quarantined(self) -> int:
        r = self._rep
        return (r.quarantined + r.child_quarantined) if r is not None else 0

    @property
    def alive(self) -> bool:
        return (not self._killed and self._rep is not None
                and self._rep.proc.poll() is None)

    # ------------------------------------------------------------------ chaos
    def kill(self, sig: str = "KILL") -> None:
        """Real-signal death: ``KILL`` is the preempted-host model (no flush,
        no goodbye), ``TERM`` lets the child drain in-flight work before
        exiting (the stream stays truthful either way)."""
        self._cancel_stall()
        rep = self._rep
        if rep is None or rep.proc.poll() is not None:
            self._killed = True
            return
        signum = {"KILL": signal.SIGKILL,
                  "TERM": signal.SIGTERM}[str(sig).upper()]
        try:
            rep.proc.send_signal(signum)
        except ProcessLookupError:
            pass
        if signum == signal.SIGKILL:
            try:
                rep.proc.wait(timeout=30)
            except Exception:
                pass
        self._killed = True

    def evict_all(self, reason: str = "evicted") -> List["HostedHandle"]:
        """Whole-replica eviction (breaker death / drain / retire-grace). The
        child's device state is unrecoverable from the parent (prefix-only
        recovery), so eviction of a live child = kill; the supervisor owns
        any respawn. Open handles finalize EVICTED with their streamed
        prefixes — exactly what the router's requeue absorbs."""
        self.kill(sig="KILL")
        return self._fail_open_handles(reason)

    def stall(self, seconds: float) -> None:
        """Wedge the child with SIGSTOP for ``seconds`` (SIGCONT after): its
        heartbeat stream goes silent and the pipe-silence watchdog ages the
        replica exactly like a wedged TPU host."""
        rep = self._rep
        if rep is None or rep.proc.poll() is not None:
            return
        try:
            os.kill(rep.proc.pid, signal.SIGSTOP)
        except ProcessLookupError:
            return
        self._cancel_stall()

        def _cont(pid=rep.proc.pid):
            try:
                os.kill(pid, signal.SIGCONT)
            except ProcessLookupError:
                pass

        self._stall_timer = threading.Timer(float(seconds), _cont)
        self._stall_timer.daemon = True
        self._stall_timer.start()

    def _cancel_stall(self) -> None:
        if self._stall_timer is not None:
            self._stall_timer.cancel()
            self._stall_timer = None
            rep = self._rep
            if rep is not None and rep.proc.poll() is None:
                try:                   # never leave a child stopped forever
                    os.kill(rep.proc.pid, signal.SIGCONT)
                except ProcessLookupError:
                    pass

    def revive(self) -> None:
        """Chaos/supervisor respawn: a FRESH process (the old one's HBM state
        is gone with it — same contract as ``EngineReplica.revive``)."""
        self.respawn()

    def respawn(self) -> None:
        """Replace the child with a fresh process. The dead child's in-flight
        trace lanes are force-closed (``state=abandoned``) so the flight
        recorder sees the complete dead lane joined to the retry attempt."""
        self._cancel_stall()
        rep = self._rep
        if rep is not None:
            if self._tracer.enabled:
                try:
                    rep.abandon_open_lanes(self._tracer)
                except Exception:
                    pass
            self._ingest_spans()
            if rep.proc.poll() is None:
                # can't trust a replica being respawned to drain: hard-kill
                try:
                    rep.proc.send_signal(signal.SIGKILL)
                    rep.proc.wait(timeout=30)
                except (ProcessLookupError, Exception):
                    pass
        self._fail_open_handles("respawn")
        self._spawn()

    def close(self) -> int:
        """Graceful shutdown through the stop escalation ladder (detach /
        drain path). Returns the child's exit code."""
        self._stopped = True
        self._cancel_stall()
        if self._rep is None:
            return 0
        self._ingest_spans()
        return self._rep.stop(drain_s=self.config.stop_drain_s,
                              term_s=self.config.stop_term_s)

    # ------------------------------------------------------------------- work
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None, seed: int = 0,
               trace_ctx=None) -> HostedHandle:
        if not self.alive:
            raise ReplicaDeadError(f"hosted replica {self.id} is dead")
        prompt, max_new = validate_admission(
            prompt, max_new_tokens, self.config.default_max_new_tokens,
            self.scheduler.executor.max_prompt_len, self.scheduler.cap)
        if self.available <= 0:
            raise QueueFullError(self.config.retry_after_s)
        rid = next(self._ids)
        h = HostedHandle(self, rid, prompt, max_new, eos_token_id, deadline_s,
                         seed)
        self._handles[rid] = h
        try:
            self._rep.submit(
                rid, prompt, max_new_tokens=max_new, seed=seed,
                eos_token_id=eos_token_id, deadline_s=deadline_s,
                trace_id=trace_ctx.trace_id if trace_ctx is not None else None,
                parent_span=trace_ctx.span_id if trace_ctx is not None
                else None)
        except QueueFullError:
            # write-side backpressure (socket link's bounded out-buffer): the
            # request never left the parent — drop the handle, let the
            # router's admission backpressure absorb it
            del self._handles[rid]
            raise
        return h

    def step(self, now: Optional[float] = None) -> bool:
        """The router's pump slot — HARVEST ONLY, never blocks on child
        compute: sync streamed progress into handles, ingest child spans,
        and maintain the pipe-silence watchdog. Always returns False: the
        parent's serial pump must never stamp this replica's heartbeat —
        liveness is the child's own stream."""
        now = time.monotonic() if now is None else now
        rep = self._rep
        if rep is None:
            return False
        # harvest FIRST: the child flushes every line before a SIGKILL can
        # land, so progress (even a final done) already sitting in the reader
        # buffer must reach the handles before the death path evicts them —
        # failing first would re-decode tokens the pipe truthfully delivered
        self._sync_handles(now)
        pipe_dead = rep.proc.poll() is not None
        if pipe_dead and not self._stopped:
            if self._handles:
                self._fail_open_handles("killed")
            if not self._killed:
                self._killed = True
            # fast-fail: the process is GONE — flatline the heartbeat now
            # instead of waiting out dead_after_s of silence
            self.last_heartbeat = min(self.last_heartbeat, now - 3600.0)
        elif not self._killed:
            t = rep.last_line_at
            if t is not None and t > self.last_heartbeat:
                self.last_heartbeat = t      # child-stamped liveness
            elif (rep.ready is None
                  and now - self._spawned_at < self.config.startup_grace_s):
                # boot window (jax import + engine build): keep the heartbeat
                # alive while the process exists; the watchdog arms at ready
                self.last_heartbeat = now
            elif now - self._last_step_at < 0.001:
                # the router loop is SPINNING (back-to-back steps with
                # nothing new on the pipe): yield the core to the children.
                # A loop doing real work elsewhere (another replica's
                # dispatch/harvest) shows an inter-step gap and pays nothing.
                time.sleep(0.002)
        self._last_step_at = time.monotonic()
        self._ingest_spans()
        return False

    def _sync_handles(self, now: float) -> None:
        rep = self._rep
        for rid, h in list(self._handles.items()):
            line = rep.progress.get(rid)
            if not line:
                continue
            toks = line.get("tokens") or []
            if len(toks) > len(h.tokens):
                if h.first_token_at is None:
                    h.first_token_at = now
                    h.ttft = now - h.arrival
                    h.prefix_hit_tokens = int(line.get("prefix_hit_tokens")
                                              or 0)
                self._tokens_total += len(toks) - len(h.tokens)
                h.tokens = [int(t) for t in toks]
                h.state = RequestState.RUNNING
                self._warm = True
            if line.get("done") and not h.done:
                try:
                    h.state = RequestState(line.get("state", "finished"))
                except ValueError:
                    h.state = RequestState.FINISHED
                h.finish_reason = line.get("finish_reason") or h.state.value
                h.finished_at = now
                if (h.first_token_at is not None and len(h.tokens) > 1
                        and now > h.first_token_at):
                    h.tpot = (now - h.first_token_at) / (len(h.tokens) - 1)
                del self._handles[rid]

    def _fail_open_handles(self, reason: str,
                           only: Optional[List[int]] = None
                           ) -> List[HostedHandle]:
        """Finalize every open handle EVICTED with its streamed prefix (the
        router's requeue path absorbs exactly these tokens). ``only`` limits
        the sweep to specific request ids (the socket link's per-sever-epoch
        eviction)."""
        now = time.monotonic()
        out = []
        for rid, h in list(self._handles.items()):
            if only is not None and rid not in only:
                continue
            if not h.done:
                h.state = RequestState.EVICTED
                h.finish_reason = reason
                h.finished_at = now
            out.append(h)
            del self._handles[rid]
        return out

    def _cancel_request(self, rid: int) -> None:
        if self._rep is not None and self.alive:
            self._rep.cancel(rid)

    def _ingest_spans(self) -> None:
        rep = self._rep
        if rep is None or not rep.spans:
            return
        # child lanes join the parent trace under one host label per replica
        self._tracer.ingest(rep.take_spans(), pid_label=f"host{self.id}")

    # ---------------------------------------------------------------- metrics
    @property
    def engine(self):
        """Lazily-built parent-side reference engine, bit-identical to the
        child's (determinism contract) — what parity checks generate against.
        """
        if self._engine is None:
            self._engine = reference_engine(self.config)
        return self._engine

    @property
    def outstanding(self) -> int:
        return len(self._handles)

    @property
    def running(self) -> int:
        """Open handles actively decoding (>= 1 token streamed) — parent-side
        truth, fresher than the heartbeat's lagged count; chaos ``when=busy``
        keys off this so a kill lands mid-decode deterministically."""
        return sum(1 for h in self._handles.values() if h.tokens)

    @property
    def queued(self) -> int:
        return max(0, self.outstanding - self.running)

    @property
    def free_slots(self) -> int:
        return max(0, self.config.slots - self.outstanding)

    @property
    def available(self) -> int:
        """Dispatch headroom: at most ``slots`` concurrent requests per host
        (queueing stays central, in the router) — and nothing before the
        child's versioned hello lands."""
        if not self.ready or not self.alive:
            return 0
        return self.free_slots

    def pipe_lag_ms(self) -> Optional[float]:
        """Last heartbeat's wall-clock transit+age: how stale the parent's
        view of this child is."""
        hb = self.hb
        if hb is None or "_rx_t" not in hb:
            return None
        return max(0.0, (hb["_rx_t"] - float(hb["t"])) * 1e3)


class SocketHostedReplica(HostedReplica):
    """A :class:`HostedReplica` whose protocol v1 rides the framed-TCP
    transport (:mod:`.net`) instead of the stdio pipe — the same recovery
    semantics across a MACHINE boundary.

    Three wirings (``HostConfig.socket_mode`` + ``endpoint``):

    - ``socket_mode="listen"`` (default): spawn the child with
      ``--serve-socket --listen 127.0.0.1:0`` and dial the bootstrap port;
    - ``socket_mode="connect"``: parent listens, child dials
      (``--connect``) — the wiring for children behind NAT;
    - ``endpoint="host:port"``: dial an externally started child
      (``deepspeed-serve --replica-endpoint``); there is no local process,
      so "kill" means sever + redial and supervision respawns the LINK.

    On a severed connection ``step()`` immediately evicts in-flight requests
    WITH their streamed prefixes (the checkpointless-retry path — recovery
    stays bit-exact) while the link's reconnect machine redials with bounded
    exponential backoff; the frozen heartbeat ages the replica through
    LIVE→SUSPECT→DEAD exactly like pipe silence. A dead CHILD respawns via
    the supervisor; a dead CONNECTION redials via the link — the
    respawn-or-redial split. ``net_fault`` exposes the chaos transport seam
    (``net:replica=i,mode=partition|delay=<ms>|drop=<p>``)."""

    is_socket = True

    def __init__(self, config: Optional[HostConfig] = None,
                 replica_id: int = -1, wait_ready: bool = False,
                 endpoint: Optional[str] = None, net=None):
        self._endpoint = endpoint
        self._net = net                # Optional[net.NetConfig]
        super().__init__(config, replica_id, wait_ready)

    def _spawn(self) -> None:
        from .net import SocketReplicaLink
        cfg = self.config
        if self._rep is not None:
            self._rep.close()          # release the old link's IO + sockets
        spawn_args = (cfg.dims()
                      if cfg.cmd_override is None and self._endpoint is None
                      else {})
        self._rep = SocketReplicaLink(
            cfg.repo_root or _default_repo_root(), env=cfg.env,
            prefix_cache=cfg.prefix_cache,
            cmd=list(cfg.cmd_override) if cfg.cmd_override else None,
            endpoint=self._endpoint,
            child_dials=(cfg.socket_mode == "connect"),
            net=self._net, **spawn_args)
        self._killed = False
        self._warm = False
        self._spawned_at = time.monotonic()
        self.last_heartbeat = self._spawned_at

    # ------------------------------------------------------------------ chaos
    def kill(self, sig: str = "KILL") -> None:
        if self._endpoint is not None:
            # no local process to signal: the connection is the only lever —
            # sever now (step() evicts with prefixes), let the reconnect
            # machine redial; the router re-admits through RECOVERING
            self._cancel_stall()
            self._rep.force_sever("chaos-kill")
            return
        super().kill(sig)

    def stall(self, seconds: float) -> None:
        if self._endpoint is not None:
            # SIGSTOP cannot cross the network: a partition window is the
            # transport-native wedge (silence both ways, then recovery)
            self._rep.net_fault("partition", 0.0, seconds)
            return
        super().stall(seconds)

    def net_fault(self, mode: str, value: float, duration_s: float) -> None:
        """Chaos transport seam (``net:`` grammar): partition | delay | drop
        injected at the parent side of the link."""
        self._rep.net_fault(mode, value, duration_s)

    def force_sever(self, why: str = "forced") -> None:
        """Cut the connection NOW (the live process keeps running): in-flight
        work evicts with prefixes on the next step and the reconnect machine
        redials with the session token — the sever-resume probe the net
        tests drive directly."""
        if self._rep is not None:
            self._rep.force_sever(why)

    def evict_all(self, reason: str = "evicted") -> List["HostedHandle"]:
        """The respawn-vs-redial split at the breaker: when the CONNECTION is
        the known casualty (severed, or a net fault in force) and the child
        process is alive, eviction must not kill the process — open handles
        finalize EVICTED with prefixes, the link severs so the reconnect
        machine redials with the session token, and the re-hello's
        ``cancel_all`` frees the child's orphaned slots. Anything else (true
        heartbeat wedge, drain, retire-grace) keeps the kill semantics: a
        child the parent cannot trust is replaced, not reasoned with."""
        rep = self._rep
        if rep is not None and self.alive \
                and (rep.severed or rep.fault_active):
            if not rep.severed:
                rep.force_sever(f"breaker-evict ({reason})")
            return self._fail_open_handles(reason)
        return super().evict_all(reason)

    # ------------------------------------------------------------------- pump
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None, seed: int = 0,
               trace_ctx=None) -> HostedHandle:
        # stamp the link's sever epoch BEFORE the wire enqueue: a sever that
        # races the enqueue leaves the handle in the old epoch and step()
        # evicts it (the frame may never have left this side), while a handle
        # minted after a quick redial is never swept by the stale sever
        epoch = self._rep.sever_count if self._rep is not None else 0
        h = super().submit(prompt, max_new_tokens=max_new_tokens,
                           eos_token_id=eos_token_id, deadline_s=deadline_s,
                           seed=seed, trace_ctx=trace_ctx)
        h.sever_epoch = epoch
        return h

    def step(self, now: Optional[float] = None) -> bool:
        rep = self._rep
        if rep is not None and not self._stopped and self._handles:
            # sever eviction: whatever was in flight on a severed connection
            # finalizes EVICTED with its streamed prefix — the router's
            # checkpointless retry re-prefills prompt+prefix elsewhere,
            # bit-exact, while the link redials in the background. Keyed on
            # the per-handle sever EPOCH, not the live ``severed`` flag: the
            # IO thread can win the redial race between two parent steps, and
            # the resumed hello's cancel_all would then turn the guaranteed
            # eviction into a child-side cancel. Runs BEFORE the harvest so a
            # post-resume cancelled terminal never beats the eviction; the
            # streamed prefix is folded in here from the same progress lines
            # the harvest would have read.
            count = rep.sever_count
            tnow = time.monotonic()
            stale = []
            for rid, h in list(self._handles.items()):
                if getattr(h, "sever_epoch", 0) >= count or h.done:
                    continue
                line = rep.progress.get(rid) or {}
                toks = line.get("tokens") or []
                if len(toks) > len(h.tokens):
                    if h.first_token_at is None:
                        h.first_token_at = tnow
                        h.ttft = tnow - h.arrival
                        h.prefix_hit_tokens = int(
                            line.get("prefix_hit_tokens") or 0)
                    self._tokens_total += len(toks) - len(h.tokens)
                    h.tokens = [int(t) for t in toks]
                    self._warm = True
                if h._cancel or (line.get("done")
                                 and line.get("state") != "cancelled"):
                    # a real terminal (finished/expired, flushed before the
                    # sever) or a parent-initiated cancel: the harvest applies
                    # it — only in-flight casualties evict
                    continue
                stale.append(rid)
            if stale:
                self._fail_open_handles("severed", only=stale)
        return super().step(now)

    @property
    def available(self) -> int:
        rep = self._rep
        if rep is not None and rep.severed:
            return 0                   # no dispatch into a severed link
        return super().available

    # ---------------------------------------------------------------- surface
    @property
    def severed(self) -> bool:
        return bool(self._rep is not None and self._rep.severed)

    @property
    def reconnects(self) -> int:
        return self._rep.reconnects if self._rep is not None else 0

    @property
    def session(self) -> Optional[str]:
        return self._rep.session if self._rep is not None else None

    @property
    def resumed_last(self) -> Optional[bool]:
        """Whether the link's most recent hello resumed the child's prior
        session (vs a fresh one after a child restart); ``None`` while
        severed — the verdict belongs to the NEXT hello."""
        return self._rep.resumed_last if self._rep is not None else None

    def rtt_ms(self) -> Optional[float]:
        return self._rep.rtt_last_ms if self._rep is not None else None


@dataclass
class SupervisorConfig:
    max_restarts: int = 3          # per-replica respawn budget (the launcher's
    #   --max_restarts semantics: bounded, then pinned DEAD)
    backoff_base_s: float = 0.5    # exponential: base * 2^restarts, capped
    backoff_max_s: float = 30.0
    emit_interval_s: float = 0.25  # telemetry cadence (step() is called from
    #   the hot serving loop)


@dataclass
class _SupervisedState:
    restarts: int = 0
    due: Optional[float] = None    # scheduled respawn time (backoff running)
    backoff_s: float = 0.0
    pinned: bool = False
    backoffs: List[float] = field(default_factory=list)


class ReplicaSupervisor:
    """The supervision tree over a router's hosted replicas: respawn dead
    children with exponential backoff under a bounded restart budget;
    re-admission flows through the router's existing ``DEAD→RECOVERING``
    half-open warm probe (one probe request before real traffic). An
    exhausted budget pins the replica DEAD — the router keeps serving on the
    survivors, and every decision lands in the flight recorder's journal."""

    def __init__(self, router, config: Optional[SupervisorConfig] = None):
        self.router = router
        self.config = config or SupervisorConfig()
        self.state: Dict[int, _SupervisedState] = {}
        self.restarts_total = 0
        self.pinned: List[int] = []
        self._feed = RegistryFeed()
        self._ticks = 0
        self._last_emit: Optional[float] = None

    def step(self, now: Optional[float] = None) -> List[int]:
        """One supervision sweep; returns the replica ids respawned."""
        now = time.monotonic() if now is None else now
        cfg = self.config
        respawned: List[int] = []
        backoff_now = 0.0
        rss_max = 0.0
        lag_max = 0.0
        for r in list(self.router.replicas):
            if not getattr(r, "is_hosted", False):
                continue
            st = self.state.setdefault(r.id, _SupervisedState())
            hb = r.hb
            if hb is not None:
                rss_max = max(rss_max, float(hb.get("rss_bytes") or 0))
                lag = r.pipe_lag_ms()
                if lag is not None:
                    lag_max = max(lag_max, lag)
            h = self.router.health.get(r.id)
            if h is None or st.pinned or h.retiring \
                    or getattr(self.router, "draining", False):
                continue
            if h.state != ReplicaState.DEAD or r.alive:
                st.due = None          # healthy (or already respawned and
                continue               # recovering): no backoff pending
            if st.restarts >= cfg.max_restarts:
                st.pinned = True
                self.pinned.append(r.id)
                logger.error(f"[supervisor] replica {r.id}: restart budget "
                             f"exhausted after {st.restarts} respawn(s); "
                             "pinned DEAD")
                obs_flight.journal("host_pinned", replica=r.id,
                                   restarts=st.restarts)
                continue
            if st.due is None:
                st.backoff_s = min(cfg.backoff_max_s,
                                   cfg.backoff_base_s * (2 ** st.restarts))
                st.backoffs.append(st.backoff_s)
                st.due = now + st.backoff_s
                logger.warning(f"[supervisor] replica {r.id} dead; respawn "
                               f"#{st.restarts + 1} in {st.backoff_s:.2f}s")
                obs_flight.journal("host_backoff", replica=r.id,
                                   backoff_s=round(st.backoff_s, 3),
                                   restarts=st.restarts)
            if now >= st.due:
                st.due = None
                st.restarts += 1
                self.restarts_total += 1
                r.respawn()
                r.restarts = st.restarts
                respawned.append(r.id)
                logger.warning(f"[supervisor] replica {r.id} respawned "
                               f"(child pid {r.child_pid}, restart "
                               f"{st.restarts}/{cfg.max_restarts})")
                obs_flight.journal("host_restart", replica=r.id,
                                   restarts=st.restarts,
                                   child_pid=r.child_pid)
            else:
                backoff_now = max(backoff_now, st.due - now)
        self._ticks += 1
        if (self._last_emit is None
                or now - self._last_emit >= cfg.emit_interval_s):
            self._last_emit = now
            self._feed.record_events([
                ("host/restarts_total", float(self.restarts_total),
                 self._ticks),
                ("host/backoff_s", float(backoff_now), self._ticks),
                ("host/child_rss_bytes", float(rss_max), self._ticks),
                ("host/pipe_lag_ms", float(lag_max), self._ticks),
            ])
        return respawned

    def report(self) -> Dict:
        """``/statusz``-shaped summary: per-replica restart counts, pending
        backoffs, and the pinned set."""
        return {"restarts_total": self.restarts_total,
                "pinned": list(self.pinned),
                "replicas": {rid: {"restarts": st.restarts,
                                   "pinned": st.pinned,
                                   "backoff_s": st.backoff_s if st.due
                                   else 0.0}
                             for rid, st in self.state.items()}}
