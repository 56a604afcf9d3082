"""Elastic serving control plane: metrics-driven autoscaling + SLO admission.

DeepSpeed's elasticity pillar (elastic agent, bounded restarts, launcher-level
scale changes) re-imagined for the serving path: the loop from **live metrics**
(router queue depth, recent TTFT p95, slot occupancy) to **replica count and
admission decisions** is closed here, so a load swing changes capacity instead
of latency, and a doomed request is turned away at the front door instead of
expiring after burning decode steps.

Three pieces:

- :class:`ServiceTimeEstimator` — a small online model of what serving one
  request costs *right now*: EWMA first-token latency, EWMA seconds-per-token,
  the observed EOS fraction (how much of the requested budget is actually
  generated before EOS), and a windowed completion drain rate. It powers both
  the SLO admission check (``Router.submit`` sheds requests whose estimated
  completion misses their deadline — cheap, before prefill) and the
  load-adaptive ``retry_after`` hint on every backpressure rejection.
  The estimator refuses to guess blind: until ``min_observations``
  completions it reports ``None`` and admission never sheds.
- :class:`Autoscaler` — evaluated each pump step against hysteresis +
  cooldown: ``breach_evals`` consecutive breaching evaluations (queue depth
  per live replica above ``queue_high_per_replica``, or recent TTFT p95 above
  ``ttft_p95_slo_ms``) add a replica (spawned from ``engine_factory``, warmed
  through the router's RECOVERING half-open probe path — it serves one probe
  request before taking real load); ``idle_evals`` consecutive idle
  evaluations (empty queue, mean occupancy below ``occupancy_low``) retire the
  least-loaded replica through :meth:`~.router.Router.begin_retire`, whose
  drain/hand-off machinery migrates in-flight requests bit-identically
  (``lost == 0`` is the asserted contract). ``cooldown_s`` after any action
  keeps the scaler from fighting itself — or the circuit breaker.
- **replica-seconds accounting** — attached replicas integrated over wall
  time: the provisioned-capacity cost an autoscaled run is judged against a
  static-N deployment on (``Autoscaler.report()["replica_seconds"]``).

Decisions are observable end to end: ``autoscale/scale_up_total`` /
``autoscale/scale_down_total`` / ``autoscale/replica_seconds`` counters and
the ``router/target_replicas`` gauge in the metrics registry, plus one
``autoscale/scale_up|scale_down`` tracer span per decision (cat
``autoscale``) carrying the triggering signals — the Perfetto view shows
*why* capacity changed next to the request lanes that caused it.

Threading: like the router, single-threaded — call :meth:`Autoscaler.step`
from the same loop that drives ``router.step()`` (the loadgen and
``deepspeed-serve --autoscale`` do exactly that).
"""

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from ...observability import flight as obs_flight
from ...observability.metrics import RegistryFeed
from ...observability.trace import CAT_AUTOSCALE, get_tracer
from ...utils.logging import logger
from .router import ReplicaState, Router
from .telemetry import window_rate


@dataclass
class EstimatorConfig:
    alpha: float = 0.25            # EWMA weight for new observations
    min_observations: int = 3      # never shed before this many completions
    drain_window: int = 64         # completion timestamps for the drain rate
    drain_horizon_s: float = 10.0  # ignore completions older than this
    # cold-start priors are deliberately absent: an estimator with no
    # evidence returns None and the admission layer admits (never shed blind)


class ServiceTimeEstimator:
    """Online service-time model fed by completed requests.

    ``observe`` on every completion; ``estimate_s`` answers "if this request
    were admitted now, when would it finish?" as::

        wait_s  = queue_depth / drain_rate          (queue ahead of it)
        serve_s = ttft_ewma + expected_tokens * tpot_ewma

    ``ttft_ewma`` is measured arrival→first-token under recent load, so it
    already folds in historical queue wait — the explicit ``wait_s`` term
    makes the estimate respond to a queue that is *growing faster than the
    EWMA adapts*. The overlap biases the estimate conservative (sheds a
    borderline request rather than admitting a doomed one), which is the
    right direction for an admission gate.
    """

    def __init__(self, config: Optional[EstimatorConfig] = None):
        self.config = config or EstimatorConfig()
        self.ttft_s: Optional[float] = None    # EWMA arrival -> first token
        self.tpot_s: Optional[float] = None    # EWMA seconds per decode token
        self.eos_frac: Optional[float] = None  # EWMA generated / budget
        self.observations = 0
        self._finishes: deque = deque(maxlen=self.config.drain_window)

    def _ewma(self, old: Optional[float], new: float) -> float:
        a = self.config.alpha
        return new if old is None else (1 - a) * old + a * new

    def observe(self, ttft_s: Optional[float], tpot_s: Optional[float],
                generated: int, budget: int,
                now: Optional[float] = None) -> None:
        """One completed request: latency stats + how much of its budget it
        actually used (EOS statistics)."""
        now = time.monotonic() if now is None else now
        if ttft_s is not None:
            self.ttft_s = self._ewma(self.ttft_s, float(ttft_s))
        if tpot_s is not None:
            self.tpot_s = self._ewma(self.tpot_s, float(tpot_s))
        if budget > 0:
            self.eos_frac = self._ewma(
                self.eos_frac, min(1.0, float(generated) / float(budget)))
        self.observations += 1
        self._finishes.append(now)

    @property
    def ready(self) -> bool:
        return (self.observations >= self.config.min_observations
                and self.ttft_s is not None)

    def drain_rate(self, now: Optional[float] = None) -> Optional[float]:
        """Recent completions per second (None until two finishes land inside
        the horizon — a stale window must not report an ancient rate)."""
        now = time.monotonic() if now is None else now
        return window_rate(self._finishes, now, self.config.drain_horizon_s)

    def expected_tokens(self, max_new_tokens: int) -> float:
        """Requested budget discounted by the observed EOS fraction."""
        frac = 1.0 if self.eos_frac is None else self.eos_frac
        return max(1.0, float(max_new_tokens) * frac)

    def estimate_s(self, max_new_tokens: int, queue_depth: int = 0,
                   now: Optional[float] = None) -> Optional[float]:
        """Estimated admission→completion seconds, or None when not ready."""
        if not self.ready:
            return None
        serve = self.ttft_s + self.expected_tokens(max_new_tokens) \
            * (self.tpot_s or 0.0)
        rate = self.drain_rate(now)
        wait = queue_depth / rate if rate else 0.0
        return wait + serve

    def snapshot(self) -> Dict:
        return {"ready": self.ready, "observations": self.observations,
                "ttft_s": self.ttft_s, "tpot_s": self.tpot_s,
                "eos_frac": self.eos_frac,
                "drain_rate": self.drain_rate()}


@dataclass
class AutoscaleConfig:
    min_replicas: int = 1
    max_replicas: int = 4
    eval_interval_s: float = 0.05      # signal sampling period
    queue_high_per_replica: float = 3.0  # queued reqs per live replica -> up
    ttft_p95_slo_ms: Optional[float] = None  # recent-TTFT breach -> up
    ttft_window_min: int = 5           # recent TTFTs needed before the p95
    #   signal is trusted (a 1-sample "p95" is noise)
    signal_horizon_s: float = 10.0     # TTFT-p95 freshness: with no completion
    #   inside this window the p95 signal reads None — a surge's frozen tail
    #   must not pin breach=True forever after traffic stops (the scale-down
    #   half of the loop would deadlock at max_replicas)
    occupancy_low: float = 0.35        # smoothed live occupancy below -> down
    occupancy_alpha: float = 0.3       # EWMA weight for the occupancy signal
    #   (instantaneous occupancy of a small slot pool is nearly 0-or-1; the
    #   raw sample would reset the idle streak on every busy blink)
    breach_evals: int = 2              # consecutive breaches before scale-up
    idle_evals: int = 8                # consecutive idles before scale-down
    cooldown_s: float = 1.0            # quiet period before a SCALE-DOWN
    up_cooldown_s: Optional[float] = None  # quiet period before a SCALE-UP;
    #   None = cooldown_s / 4 — scale out fast (latency is bleeding), scale
    #   in slow (tearing capacity down too eagerly re-breaches immediately)
    retire_grace_s: float = 2.0        # in-flight drain window on scale-down

    @property
    def effective_up_cooldown_s(self) -> float:
        return (self.cooldown_s / 4.0 if self.up_cooldown_s is None
                else self.up_cooldown_s)

    def __post_init__(self):
        if self.min_replicas < 1:
            raise ValueError(f"min_replicas must be >= 1, "
                             f"got {self.min_replicas}")
        if self.max_replicas < self.min_replicas:
            raise ValueError(f"max_replicas ({self.max_replicas}) < "
                             f"min_replicas ({self.min_replicas})")


class Autoscaler:
    """Closes the metrics→capacity loop over a :class:`~.router.Router`.

    ``engine_factory`` is called once per scale-up and must return an engine
    whose weights are bit-identical to the existing replicas' (share replica
    0's params — the same contract ``_build_engines`` uses; the retry/drain
    parity guarantees assume it). Replicas it adds enter through the
    RECOVERING half-open probe path, so a cold replica proves itself on one
    request before taking real load.
    """

    def __init__(self, router: Router, engine_factory: Callable[[], object],
                 config: Optional[AutoscaleConfig] = None):
        self.router = router
        self.engine_factory = engine_factory
        self.config = cfg = config or AutoscaleConfig()
        if len(router.replicas) < cfg.min_replicas:
            raise ValueError(
                f"router starts with {len(router.replicas)} replica(s), "
                f"below min_replicas={cfg.min_replicas}")
        self.target_replicas = len(router.replicas)
        self.scale_ups = 0
        self.scale_downs = 0
        self.replica_seconds = 0.0
        self.decisions: deque = deque(maxlen=256)   # bounded decision log
        self._breach = 0
        self._idle = 0
        self._occ_ewma: Optional[float] = None
        self._evals = 0
        self._last_eval: Optional[float] = None
        self._last_tick: Optional[float] = None
        self._last_action: Optional[float] = None
        self._feed = RegistryFeed()
        self._tracer = get_tracer()

    # ----------------------------------------------------------------- signals
    def _active(self) -> List:
        """Replicas counted toward capacity: attached, not DEAD, not retiring
        (a retiring replica still drains but takes no new work)."""
        out = []
        for r in self.router.replicas:
            h = self.router.health[r.id]
            if h.state != ReplicaState.DEAD and not h.retiring:
                out.append(r)
        return out

    def signals(self, now: Optional[float] = None) -> Dict:
        now = time.monotonic() if now is None else now
        active = self._active()
        tel = self.router.telemetry
        recent = list(tel.recent_ttft_ms)
        finishes = self.router.estimator._finishes
        fresh = bool(finishes) and \
            now - finishes[-1] <= self.config.signal_horizon_s
        ttft_p95 = (float(np.percentile(recent, 95))
                    if fresh and len(recent) >= self.config.ttft_window_min
                    else None)
        occ = (float(np.mean([r.scheduler.executor.pool.occupancy
                              for r in active])) if active else 1.0)
        return {"queue_depth": self.router.queue_depth,
                "active_replicas": len(active),
                "attached_replicas": len(self.router.replicas),
                "ttft_p95_ms": ttft_p95, "occupancy": occ,
                "occupancy_ewma": self._occ_ewma}

    # ------------------------------------------------------------------- loop
    def step(self, now: Optional[float] = None) -> Optional[str]:
        """Accumulate replica-seconds every call; evaluate the policy at
        ``eval_interval_s``. Returns the action taken ("up"/"down") or None."""
        now = time.monotonic() if now is None else now
        if getattr(self.router, "draining", False):
            # SIGTERM drain owns the replica set from here: a scale-up racing
            # the drain flag would raise RouterDrainingError out of the
            # serving loop and skip the hand-off block entirely
            return None
        if self._last_tick is not None and now > self._last_tick:
            # retiring replicas still hold HBM until detached: they count
            self.replica_seconds += \
                (now - self._last_tick) * len(self.router.replicas)
        self._last_tick = now
        if (self._last_eval is not None
                and now - self._last_eval < self.config.eval_interval_s):
            return None
        self._last_eval = now
        return self._evaluate(now)

    def _evaluate(self, now: float) -> Optional[str]:
        cfg = self.config
        sig = self.signals(now)
        self._evals += 1
        self._emit(sig)
        n = sig["active_replicas"]
        a = cfg.occupancy_alpha
        self._occ_ewma = (sig["occupancy"] if self._occ_ewma is None
                          else (1 - a) * self._occ_ewma
                          + a * sig["occupancy"])
        sig["occupancy_ewma"] = self._occ_ewma
        breach = (sig["queue_depth"] > cfg.queue_high_per_replica * max(1, n)
                  or (cfg.ttft_p95_slo_ms is not None
                      and sig["ttft_p95_ms"] is not None
                      and sig["ttft_p95_ms"] > cfg.ttft_p95_slo_ms))
        idle = (not breach and sig["queue_depth"] == 0
                and self._occ_ewma < cfg.occupancy_low)
        # hysteresis: consecutive-evaluation counters, each reset by the other
        self._breach = self._breach + 1 if breach else 0
        self._idle = self._idle + 1 if idle else 0
        since_action = (None if self._last_action is None
                        else now - self._last_action)
        # the ceiling bounds ATTACHED capacity too: a DEAD replica may later
        # recover through the breaker, and active-only accounting would let
        # the set grow past max_replicas in the meantime
        n_attached = len([r for r in self.router.replicas
                          if not self.router.health[r.id].retiring])
        if (self._breach >= cfg.breach_evals
                and (since_action is None
                     or since_action >= cfg.effective_up_cooldown_s)
                and n < cfg.max_replicas and n_attached < cfg.max_replicas):
            return self._scale_up(now, sig)
        if (self._idle >= cfg.idle_evals
                and (since_action is None or since_action >= cfg.cooldown_s)
                and n > cfg.min_replicas):
            return self._scale_down(now, sig)
        return None

    # ---------------------------------------------------------------- actions
    def _scale_up(self, now: float, sig: Dict) -> str:
        span = self._tracer.begin("autoscale/scale_up", cat=CAT_AUTOSCALE,
                                  tid="autoscale", attrs=dict(sig))
        engine = self.engine_factory()
        replica = self.router.add_replica(engine, warm=True)
        self.scale_ups += 1
        self.target_replicas = sig["active_replicas"] + 1
        self._last_action = now
        self._breach = self._idle = 0
        self.decisions.append({"t": now, "action": "up",
                               "replica": replica.id, **sig})
        self._tracer.end_span(span, attrs={"replica": replica.id,
                                           "target": self.target_replicas})
        obs_flight.journal("scale_up", replica=replica.id,
                           target=self.target_replicas, **sig)
        logger.info(f"[autoscale] scale UP -> replica {replica.id} "
                    f"(queue={sig['queue_depth']}, "
                    f"ttft_p95={sig['ttft_p95_ms']}, "
                    f"active={sig['active_replicas']})")
        self._emit(sig)
        return "up"

    def _scale_down(self, now: float, sig: Dict) -> Optional[str]:
        # least-loaded LIVE victim; never the last min_replicas
        cands = [r for r in self._active()
                 if self.router.health[r.id].state == ReplicaState.LIVE]
        if len(cands) <= self.config.min_replicas:
            return None
        victim = min(cands, key=lambda r: (r.outstanding, -r.id))
        span = self._tracer.begin("autoscale/scale_down", cat=CAT_AUTOSCALE,
                                  tid="autoscale",
                                  attrs={**sig, "replica": victim.id})
        # deliberately NOT forwarding this evaluation's (possibly injected)
        # `now`: the retire grace deadline is checked by Router.step's clock,
        # and a synthetic scaler clock against the router's real one would
        # expire the grace window instantly (or never)
        self.router.begin_retire(victim.id,
                                 grace_s=self.config.retire_grace_s)
        self.scale_downs += 1
        self.target_replicas = max(self.config.min_replicas,
                                   sig["active_replicas"] - 1)
        self._last_action = now
        self._breach = self._idle = 0
        self.decisions.append({"t": now, "action": "down",
                               "replica": victim.id, **sig})
        self._tracer.end_span(span, attrs={"target": self.target_replicas})
        obs_flight.journal("scale_down", replica=victim.id,
                           target=self.target_replicas, **sig)
        logger.info(f"[autoscale] scale DOWN -> retiring replica {victim.id} "
                    f"(occupancy={sig['occupancy']:.2f}, "
                    f"active={sig['active_replicas']})")
        self._emit(sig)
        return "down"

    # -------------------------------------------------------------- telemetry
    def _emit(self, sig: Dict) -> None:
        self._feed.record_events([
            ("router/target_replicas", float(self.target_replicas),
             self._evals),
            ("autoscale/scale_up_total", float(self.scale_ups), self._evals),
            ("autoscale/scale_down_total", float(self.scale_downs),
             self._evals),
            ("autoscale/replica_seconds", float(self.replica_seconds),
             self._evals),
        ])

    @property
    def transient_s(self) -> float:
        """The control loop's documented reaction window: how long a breach
        can legitimately go unanswered (detection + up-cooldown) plus the
        retire grace on the way down. Benches use it as the latency allowance
        an autoscaled lane gets over an always-provisioned one."""
        cfg = self.config
        return (cfg.breach_evals * cfg.eval_interval_s
                + cfg.effective_up_cooldown_s + cfg.retire_grace_s)

    def report(self) -> Dict:
        """BENCH-JSON-shaped summary of what the control loop did."""
        return {"target_replicas": self.target_replicas,
                "transient_s": self.transient_s,
                "attached_replicas": len(self.router.replicas),
                "scale_ups": self.scale_ups,
                "scale_downs": self.scale_downs,
                "replica_seconds": self.replica_seconds,
                "evaluations": self._evals,
                "decisions": list(self.decisions),
                "estimator": (self.router.estimator.snapshot()
                              if self.router.estimator is not None else None)}
