"""TPU-native serving subsystem: continuous batching over a paged KV pool,
behind a health-supervised multi-replica router.

Layers (bottom-up):

- :mod:`kv_pool` — :class:`PagedKVPool`: one global pool of fixed-size KV
  pages behind static-shape per-slot page tables — page-count admission,
  refcounted zero-copy prefix sharing (copy-on-write boundary page), donated
  movers;
- :mod:`executor` — :class:`ChunkedDecodeExecutor`: compiled fixed-shape decode
  chunks of K steps over the slot-batch (one compile per (slots, pages, page,
  cap, chunk, sampling) key), per-slot prefill bucketed by prompt length, optional per-chunk
  watchdog deadline (:class:`ChunkTimeoutError`);
- :mod:`prefix_cache` — :class:`PrefixCache`: radix/trie index over token-ID
  prefixes whose entries hold shared pool pages (LRU under an HBM byte budget,
  exact match by token; evicted entries spill to a host tier as numpy slabs);
  a hit binds the pages into the slot's table and prefills only the suffix,
  so shared system prompts skip prefill;
- :mod:`scheduler` — :class:`ContinuousBatchingScheduler`: bounded request queue
  with admission control, backpressure (reject-with-retry-after), deadlines,
  cancellation, slot recycling between chunks, per-replica prefix-cache
  lookup/insert, and whole-replica eviction (``evict_all``) for the router's
  checkpointless retry;
- :mod:`router` — :class:`Router`: N engine replicas behind one admission queue
  with least-outstanding dispatch, session affinity, the
  LIVE→SUSPECT→DEAD→RECOVERING health state machine, checkpointless request
  retry and SIGTERM graceful drain;
- :mod:`host` — :class:`HostedReplica` + :class:`ReplicaSupervisor`:
  process-parallel replica hosts — the same stack in supervised child
  processes over the :mod:`subproc` JSONL pipe (async submit/harvest,
  child-stamped heartbeats, real-signal chaos, bounded-backoff respawn
  through the router's RECOVERING warm probe) so replica count finally buys
  machine parallelism;
- :mod:`net` — :class:`SocketHostedReplica` over a length-prefixed framed
  TCP transport carrying the same protocol v1 (per-frame CRC + quarantine/
  resync, versioned hello with session tokens, reconnect state machine with
  sever-evict-redial semantics, network chaos seam) — the fleet's recovery
  semantics made transport-independent;
- :mod:`autoscale` — :class:`Autoscaler` + :class:`ServiceTimeEstimator`: the
  elastic control plane — live metrics (queue depth, recent TTFT p95,
  occupancy) drive replica count with hysteresis + cooldown, and the online
  service-time estimator powers SLO-aware admission (shed infeasible
  deadlines at the front door) and the load-adaptive ``retry_after`` hint;
- :mod:`chaos` — scripted replica kills/stalls/surges for the chaos soak
  harness;
- :mod:`telemetry` — :class:`ServingTelemetry`: per-request TTFT/TPOT, queue
  depth, slot occupancy and tokens/sec through ``MonitorMaster``
  (:class:`~.router.RouterTelemetry` adds per-replica health/retry/eviction).
"""

from .autoscale import (Autoscaler, AutoscaleConfig, EstimatorConfig,
                        ServiceTimeEstimator)
from .chaos import ChaosEvent, ChaosSchedule, parse_chaos
from .host import (HostConfig, HostedReplica, ReplicaSupervisor,
                   SocketHostedReplica, SupervisorConfig)
from .net import FrameDecoder, NetConfig, SocketReplicaLink, encode_frame
from .executor import ChunkedDecodeExecutor, ChunkTimeoutError
from .kv_pool import PagedKVPool
from .prefix_cache import PrefixCache, PrefixCacheConfig
from .router import (AdmissionDeferredError, AdmissionShedError,
                     DegradationRung, EngineReplica, ReplicaDeadError,
                     ReplicaState, Router, RouterConfig, RouterDrainingError,
                     RouterRequest, RouterRequestState, RouterTelemetry)
from .scheduler import (ContinuousBatchingScheduler, QueueFullError,
                        RequestHandle, RequestState, ServingConfig)
from .telemetry import ServingTelemetry

__all__ = [
    "ChunkedDecodeExecutor", "ChunkTimeoutError", "PagedKVPool",
    "PrefixCache", "PrefixCacheConfig",
    "ContinuousBatchingScheduler", "QueueFullError", "RequestHandle",
    "RequestState", "ServingConfig", "ServingTelemetry",
    "Router", "RouterConfig", "RouterRequest", "RouterRequestState",
    "RouterTelemetry", "EngineReplica", "ReplicaState", "ReplicaDeadError",
    "RouterDrainingError", "ChaosEvent", "ChaosSchedule", "parse_chaos",
    "Autoscaler", "AutoscaleConfig", "EstimatorConfig", "ServiceTimeEstimator",
    "AdmissionShedError", "AdmissionDeferredError", "DegradationRung",
    "HostConfig", "HostedReplica", "ReplicaSupervisor", "SupervisorConfig",
    "SocketHostedReplica", "SocketReplicaLink", "NetConfig", "FrameDecoder",
    "encode_frame",
]
