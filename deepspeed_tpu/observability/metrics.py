"""Bounded process-wide metrics registry.

One registry per process, one declared tag schema (``observability.schema``),
three instrument kinds:

- :class:`Counter` — a cumulative total (emissions carry the running value,
  matching the existing ``*_total`` event streams);
- :class:`Gauge` — last-write-wins sample;
- :class:`Histogram` — **fixed log-bucket** distribution: O(1) memory however
  long the soak, p50/p95/p99 derived from bucket counts (the replacement for
  the grow-forever ``ttfts``/``tpots`` Python lists serving telemetry carried
  before PR 10).

``MonitorMaster`` is one export backend (attach with :meth:`MetricsRegistry.
attach_monitor`); Prometheus text exposition is another
(:meth:`MetricsRegistry.prometheus_text`, served by
:func:`start_metrics_server` behind ``deepspeed-serve --metrics-port``).
Telemetry emitters route their ``(tag, value, step)`` events through
:func:`record_events`, which is a no-op-cheap loop when nothing is attached.
"""

import math
import re
import threading
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from . import schema

Event = Tuple[str, float, int]


class Counter:
    """Cumulative total. ``inc`` for owned counting, ``set_total`` when the
    emitter already tracks the running total (the existing event streams)."""

    kind = schema.COUNTER

    def __init__(self):
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n

    def set_total(self, v: float) -> None:
        # monotone: a replayed/stale event must not rewind the total
        if v > self.value:
            self.value = float(v)


class Gauge:
    kind = schema.GAUGE

    def __init__(self):
        self.value: Optional[float] = None

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Fixed log-bucket histogram: bucket ``i`` covers
    ``(lo * growth**(i-1), lo * growth**i]``, plus an underflow bucket for
    values ``<= lo`` (zeros and negatives land there too). Memory is one int64
    vector regardless of observation count; percentiles interpolate within the
    covering bucket, so relative error is bounded by ``growth - 1``.
    """

    kind = schema.HISTOGRAM

    def __init__(self, lo: float = 1e-3, hi: float = 1e7,
                 growth: float = 1.08):
        if not (lo > 0 and hi > lo and growth > 1):
            raise ValueError(f"bad histogram shape lo={lo} hi={hi} g={growth}")
        self.lo, self.growth = float(lo), float(growth)
        self._log_lo, self._log_g = math.log(lo), math.log(growth)
        n = int(math.ceil((math.log(hi) - self._log_lo) / self._log_g))
        self.counts = np.zeros(n + 2, np.int64)   # [underflow, n buckets, overflow]
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def _bucket(self, v: float) -> int:
        if v <= self.lo:
            return 0
        i = int(math.ceil((math.log(v) - self._log_lo) / self._log_g))
        return min(i, len(self.counts) - 1)

    def observe(self, v: float) -> None:
        v = float(v)
        self.counts[self._bucket(v)] += 1
        self.count += 1
        self.total += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def bucket_upper_bounds(self) -> np.ndarray:
        n = len(self.counts)
        ups = self.lo * self.growth ** np.arange(n - 1)
        return np.concatenate([ups, [np.inf]])

    def percentile(self, q: float) -> Optional[float]:
        """Percentile ``q`` in [0, 100] from bucket counts (log-linear
        interpolation inside the covering bucket; clamped to observed
        min/max so tails stay honest)."""
        if self.count == 0:
            return None
        rank = q / 100.0 * (self.count - 1)
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if rank < cum + c:
                if i == 0:
                    est = self.lo
                elif i == len(self.counts) - 1:
                    est = self.max
                else:
                    hi = self.lo * self.growth ** i
                    lo = hi / self.growth
                    frac = (rank - cum + 0.5) / c
                    est = lo * (hi / lo) ** min(max(frac, 0.0), 1.0)
                return float(min(max(est, self.min), self.max))
            cum += c
        return float(self.max)

    @property
    def mean(self) -> Optional[float]:
        return self.total / self.count if self.count else None


_KIND_CLS = {schema.COUNTER: Counter, schema.GAUGE: Gauge,
             schema.HISTOGRAM: Histogram}


class MetricsRegistry:
    """Process-wide instrument table keyed by concrete tag.

    ``record(tag, value)`` consults the schema for the tag's kind and updates
    (or lazily creates) the matching instrument; an undeclared tag raises —
    the runtime face of the tag-schema lint. ``attach_monitor`` forwards every
    recorded event to a ``MonitorMaster``-shaped backend, making the legacy
    monitor fan-out one export path among several.
    """

    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._monitors: List[object] = []
        # re-entrant: making an instrument allocates, an allocation may start
        # a collection, and the collector's hook (``trace._gc_hook``) asks
        # for its own two instruments on the same thread
        self._lock = threading.RLock()

    # -------------------------------------------------------------- instruments
    def _get(self, tag: str, kind: Optional[str] = None):
        inst = self._metrics.get(tag)
        if inst is None:
            declared = schema.kind_of(tag)
            if kind is not None and kind != declared:
                raise TypeError(f"tag {tag!r} is declared {declared}, "
                                f"not {kind}")
            with self._lock:
                inst = self._metrics.setdefault(tag, _KIND_CLS[declared]())
        elif kind is not None and inst.kind != kind:
            raise TypeError(f"tag {tag!r} is a {inst.kind}, not {kind}")
        return inst

    def counter(self, tag: str) -> Counter:
        return self._get(tag, schema.COUNTER)

    def gauge(self, tag: str) -> Gauge:
        return self._get(tag, schema.GAUGE)

    def histogram(self, tag: str) -> Histogram:
        return self._get(tag, schema.HISTOGRAM)

    # ------------------------------------------------------------------ events
    def attach_monitor(self, monitor) -> None:
        if monitor is not None and monitor not in self._monitors:
            self._monitors.append(monitor)

    def detach_monitor(self, monitor) -> None:
        if monitor in self._monitors:
            self._monitors.remove(monitor)

    def record(self, tag: str, value: float, step: int = 0) -> None:
        inst = self._get(tag)
        if inst.kind == schema.COUNTER:
            inst.set_total(value)
        elif inst.kind == schema.GAUGE:
            inst.set(value)
        else:
            inst.observe(value)
        for m in self._monitors:
            if getattr(m, "enabled", False):
                m.write_events([(tag, float(value), int(step))])

    def record_events(self, events: Iterable[Event]) -> None:
        for tag, value, step in events:
            self.record(tag, value, step)

    # ---------------------------------------------------------------- export
    def snapshot(self) -> Dict[str, Dict]:
        out = {}
        for tag, inst in sorted(self._metrics.items()):
            if inst.kind == schema.HISTOGRAM:
                out[tag] = {"kind": inst.kind, "count": inst.count,
                            "sum": inst.total, "min": inst.min,
                            "max": inst.max,
                            "p50": inst.percentile(50),
                            "p95": inst.percentile(95),
                            "p99": inst.percentile(99)}
            else:
                out[tag] = {"kind": inst.kind, "value": inst.value}
        return out

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()

    def prometheus_text(self) -> str:
        """Prometheus text exposition format 0.0.4. Tag paths map to metric
        names (``/`` and ``.`` become ``_``); the ``replica{i}`` segment maps
        to a ``replica`` label so per-replica series share one metric family."""
        lines: List[str] = []
        seen_meta = set()
        for tag in sorted(self._metrics):
            inst = self._metrics[tag]
            name, labels = _prom_name(tag)
            pattern = schema.resolve(tag)
            help_text = schema.TAGS[pattern][1] if pattern else ""
            if name not in seen_meta:
                seen_meta.add(name)
                lines.append(f"# HELP {name} {help_text}")
                lines.append(f"# TYPE {name} {inst.kind}")
            lab = ("{" + ",".join(f'{k}="{v}"' for k, v in labels) + "}"
                   if labels else "")
            if inst.kind == schema.HISTOGRAM:
                cum = 0
                for ub, c in zip(inst.bucket_upper_bounds(), inst.counts):
                    if c == 0 or math.isinf(ub):
                        continue
                    cum += int(c)
                    ext = ([*labels, ("le", f"{ub:.6g}")])
                    lines.append(
                        f"{name}_bucket{{"
                        + ",".join(f'{k}="{v}"' for k, v in ext)
                        + f"}} {cum}")
                lines.append(f"{name}_bucket{{"
                             + ",".join(f'{k}="{v}"'
                                        for k, v in [*labels, ("le", "+Inf")])
                             + f"}} {inst.count}")
                lines.append(f"{name}_sum{lab} {inst.total:.6g}")
                lines.append(f"{name}_count{lab} {inst.count}")
            else:
                v = inst.value if inst.value is not None else 0.0
                lines.append(f"{name}{lab} {v:.6g}")
        return "\n".join(lines) + "\n"


_REPLICA_SEG = re.compile(r"replica(\d+)")


def _prom_name(tag: str) -> Tuple[str, List[Tuple[str, str]]]:
    labels: List[Tuple[str, str]] = []

    def sub(m):
        labels.append(("replica", m.group(1)))
        return "replica"

    flat = _REPLICA_SEG.sub(sub, tag)
    name = re.sub(r"[^a-zA-Z0-9_:]", "_", flat)
    return name.lower(), labels


class RegistryFeed:
    """Per-emitter bridge from cumulative event streams to the registry.

    Telemetry emitters publish *their own* running totals (``serving/
    completed_total`` restarts at 0 for every scheduler, and N router replicas
    each count privately). Feeding those straight into one process-wide
    counter makes ``/metrics`` a max-of-emitters, not a total — so each
    emitter owns a feed that remembers its last-reported value per counter
    tag and contributes the **delta**; the registry counter then sums across
    replicas and across successive runs. Gauges and histograms pass through
    unchanged (last-write / per-event semantics are already correct there).
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._registry = registry if registry is not None else _registry
        self._last: Dict[str, float] = {}

    def record_events(self, events: Iterable[Event]) -> None:
        reg = self._registry
        for tag, value, step in events:
            inst = reg._get(tag)
            if inst.kind == schema.COUNTER:
                prev = self._last.get(tag, 0.0)
                delta = float(value) - prev
                if delta > 0:
                    inst.inc(delta)
                self._last[tag] = float(value)
                for m in reg._monitors:
                    if getattr(m, "enabled", False):
                        m.write_events([(tag, float(value), int(step))])
            else:
                reg.record(tag, value, step)


_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _registry


def record_events(events: Iterable[Event]) -> None:
    """Module-level fast path for SINGLE-OWNER emitters (one engine per
    process publishing ``Train/*`` / ``inference/*``). Multi-instance
    emitters (per-replica serving/router telemetry) must use a
    :class:`RegistryFeed` so their counters sum instead of max-merging."""
    _registry.record_events(events)


# --------------------------------------------------------------- /metrics HTTP
def start_metrics_server(port: int, registry: Optional[MetricsRegistry] = None,
                         host: str = "127.0.0.1", status_provider=None,
                         health_provider=None):
    """Serve the observability HTTP plane on a daemon thread:

    - ``GET /metrics`` — Prometheus text exposition from ``registry``;
    - ``GET /statusz`` — live status JSON from ``status_provider()`` (replica
      health, outstanding work, pages, prefix hit rate, degradation rung,
      recent anomalies, last autoscale decisions — whatever the provider
      assembles); 404 when no provider is wired;
    - ``GET /healthz`` — liveness/readiness: ``health_provider()`` returns
      ``(ready, payload)``; the response is the payload JSON with status 200
      when ready, 503 when not. Without a provider the process being able to
      answer IS the liveness check: 200 ``{"live": true, "ready": true}``.

    Returns the ``http.server`` instance — ``server_port`` holds the bound
    port (pass ``port=0`` for an ephemeral one), ``shutdown()`` stops it."""
    import json as _json
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    reg = registry or _registry

    class Handler(BaseHTTPRequestHandler):
        def _send(self, code: int, body: bytes, ctype: str) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path = self.path.split("?")[0].rstrip("/")
            if path in ("", "/metrics"):
                self._send(200, reg.prometheus_text().encode(),
                           "text/plain; version=0.0.4; charset=utf-8")
                return
            if path == "/statusz":
                if status_provider is None:
                    self._send(404, b"no status provider wired\n",
                               "text/plain")
                    return
                try:
                    doc = status_provider()
                except Exception as e:   # a broken provider must not 500-loop
                    doc = {"error": f"{type(e).__name__}: {e}"}
                self._send(200, (_json.dumps(doc) + "\n").encode(),
                           "application/json")
                return
            if path == "/healthz":
                if health_provider is None:
                    ready, doc = True, {"live": True, "ready": True}
                else:
                    try:
                        ready, doc = health_provider()
                    except Exception as e:
                        ready, doc = False, {"live": True, "ready": False,
                                             "error":
                                             f"{type(e).__name__}: {e}"}
                self._send(200 if ready else 503,
                           (_json.dumps(doc) + "\n").encode(),
                           "application/json")
                return
            self.send_response(404)
            self.end_headers()

        def log_message(self, *args):     # stay quiet on the serving stdout
            pass

    server = ThreadingHTTPServer((host, int(port)), Handler)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="ds-metrics-http").start()
    return server
