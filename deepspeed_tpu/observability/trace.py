"""One span API, two sinks: the profiler's clock and the request-scoped ring;
and its second half, :func:`scope`, for regions INSIDE a compiled program.

Every instrumented region of the program is ONE call, ``tracer.span(name,
**attrs)``, under a name declared once in ``observability/schema.py: SPANS``.
The call always opens a ``jax.profiler.TraceAnnotation(name, **attrs)`` (a
TraceMe: an atomic load when no profiler is armed, its attributes formatted
only when one is), so under ``jax.profiler`` the span lands in the xplane as an
event with its attributes as ``stats``, on the one clock the device's ``XLA
Modules`` and ``XLA Ops`` share. When the tracer is ``enabled`` the same call
also records the span in the ring below, under the same name with the same
attributes; request-scoped spans carry ``request_id`` (train steps ``step``) in
both sinks, which is how a ring export and an xplane are joined.

Four kinds of span:

- **scoped** (:meth:`Tracer.span`): a ``with`` block on one thread. Both
  sinks. Without ``parent`` it nests (in the ring) under the innermost scoped
  span open on the thread, else roots a fresh trace id. Attributes known only
  at the end go in through :meth:`Span.set` (``TraceAnnotation.set_metadata``).
  The span's ``t0``/``t1`` are ``time.monotonic`` stamps the caller may read,
  so a latency the program reports and the span that covers it share stamps.
- **unscoped** (:meth:`Tracer.begin` / :meth:`start_span` / :meth:`end_span`,
  :meth:`record_span`, :meth:`instant`): a request's root lives from
  ``submit`` to retirement across many steps and interleaves with other
  requests' roots, and a queue wait is known only in retrospect; a TraceMe is
  bound to one thread's stack and can hold neither. Ring only, ``None`` /
  no-op while the tracer is disabled.
- **phases** (:meth:`Tracer.phase`): the few set-up regions of a process's
  life (engine construction, the first call of each compiled program). Set-up
  runs before any profiler is armed and with the tracer off, so these are
  kept unconditionally in :attr:`Tracer.phases` with ``time.monotonic`` start
  and end (and go to both sinks like any scoped span).
- **pauses** (:meth:`Tracer.record_pause`): the rare moments in which the
  HOST stood still: a collection of python's garbage collector (``host.gc``,
  stamped by the one ``gc.callbacks`` hook below) and a decode chunk whose
  fetch or turnaround ran far over its running median (``host.stall``, found
  by ``serving/telemetry.py`` in retrospect). Every benchmark run has the
  ring off and a profiler armed for 3 of its 51 seconds, so these are kept
  unconditionally too, in :attr:`Tracer.pauses`: the second kept list, same
  record shape as a phase, a bounded drop-oldest deque of its own so that a
  server's week of pauses never pushes out its set-up phases.

The ring answers "where did this one request's 1.9 s go?": the serving column
(``replica_request`` -> ``queue_wait`` / ``serving.admit`` {``serving.prefix_lookup``,
``serving.page_table``, ``serving.prefill`` ...} / ``decode_chunk`` x N /
``retire``, router attempts as linked spans) and the training step share one
**trace id per request/step**. Its constraints, in order:

1. **Disabled is near-zero cost.** The tracer starts disabled; a scoped site
   then pays the inactive TraceMe and two clock reads, an unscoped site one
   method call that returns ``None``.
2. **Enabled does not change the schedule.** No span fetches a device value
   or waits for the device; a span covers what the host did.
3. **Bounded.** Finished spans land in a drop-oldest ring (``max_spans``);
   drops are counted, never silent.
4. **Cross-process joinable.** A ``SpanContext`` is two strings
   (``trace_id``, ``span_id``) that serialize over the ``serving/subproc.py``
   JSONL pipe; the child's spans carry the parent's trace id and
   :meth:`Tracer.ingest` merges them into the parent's buffer under the
   child's pid lane. Ring timestamps are wall-clock micros (``time.time``-
   anchored, advanced by ``time.monotonic``) so lanes from different
   processes line up.

A span covers what the HOST did. What the DEVICE did inside a compiled
program is named by :func:`scope` (names declared in ``schema.py: SCOPES``): a
``jax.named_scope`` that exists only while jax traces the function and lands
in each op's ``op_name`` metadata, which a profiler trace keeps per device op.
It costs nothing in the executed program, so it has no switch.

Ring exports: Chrome-trace-event JSON (``{"traceEvents": [...]}``; load in
Perfetto / ``chrome://tracing``) and a JSONL stream (one finished span per
line) for tailing.
"""

import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

from . import metrics, schema

# span categories (Chrome "cat" field) — one per subsystem lane
CAT_SERVING = "serving"
CAT_ROUTER = "router"
CAT_TRAIN = "train"
CAT_AUTOSCALE = "autoscale"
CAT_SETUP = "setup"
CAT_HOST = "host"

#: set-up phases kept per process (tens in practice: one per engine stage and
#: one per compiled serving program)
MAX_PHASES = 1024

#: host pauses kept per process, newest last (tens a minute at most: full
#: collections, young ones of a millisecond and more, stalled chunks)
MAX_PAUSES = 2048

#: a young collection (generation 0 or 1) is kept from this pause on; a full
#: one (generation 2) always
GC_KEEP_MS = 1.0


class SpanContext:
    """The cross-boundary identity of a span: what you put on a wire."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def to_dict(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @staticmethod
    def from_dict(d) -> Optional["SpanContext"]:
        if not d or not d.get("trace_id"):
            return None
        return SpanContext(str(d["trace_id"]), str(d.get("span_id", "")))


class OpenSpan:
    """A started-but-unfinished span (kept on the owning handle/engine)."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id", "t0",
                 "attrs", "tid")

    def __init__(self, name, cat, trace_id, span_id, parent_id, t0, attrs,
                 tid):
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.attrs = attrs or {}
        self.tid = tid

    @property
    def ctx(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)


class Span:
    """A scoped span: the profiler's annotation and, while the tracer is
    enabled, its ring twin (``open``, else ``None``). ``t0``/``t1`` are the
    ``time.monotonic`` stamps of entry and exit."""

    __slots__ = ("_tracer", "_ann", "open", "name", "attrs", "t0", "t1",
                 "_keep")

    def __init__(self, tracer, name, open_span, attrs, keep=False):
        self._tracer = tracer
        self._ann = TraceAnnotation(name, **attrs)
        self.open = open_span
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = None
        self._keep = keep

    # a Span stands in as ``parent=`` of another span (see ``_parent_of``)
    @property
    def trace_id(self):
        return self.open.trace_id if self.open is not None else None

    @property
    def span_id(self):
        return self.open.span_id if self.open is not None else None

    @property
    def cat(self):
        return self.open.cat if self.open is not None else CAT_SERVING

    def set(self, **attrs) -> None:
        """Attributes known only at the end (counts, outcomes)."""
        self._ann.set_metadata(**attrs)
        self.attrs.update(attrs)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.monotonic()
        if self.open is not None:
            self.open.t0 = self.t0
        if self.open is not None or self._keep:
            self._tracer._stack().append(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.monotonic()
        if exc_type is not None:
            self.set(error=exc_type.__name__)
        if self.open is not None or self._keep:
            stack = self._tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
            if self._keep:
                self._tracer._keep_phase(self, stack)
            if self.open is not None:
                self.open.attrs = self.attrs
                self._tracer.end_span(self.open, t1=self.t1)
        self._ann.__exit__(exc_type, exc, tb)
        return False


def _parent_of(parent) -> tuple:
    """(trace_id, span_id) from an OpenSpan / Span / SpanContext / None."""
    if parent is None or parent.trace_id is None:
        return None, None
    return parent.trace_id, getattr(parent, "span_id", None)


class Tracer:
    """Process-wide span recorder. ``enable()`` before the run; instrument
    sites call through unconditionally and pay ~nothing while disabled."""

    def __init__(self, max_spans: int = 200_000):
        self.enabled = False
        self.max_spans = int(max_spans)
        self._spans: "deque[Dict]" = deque(maxlen=self.max_spans)
        self.dropped = 0
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._pid_label = f"pid{os.getpid()}"
        self._stream = None
        # span sinks: callables fed every FINISHED span (the flight recorder's
        # attachment point). Empty-list check per commit — near-zero when none.
        self._sinks: List = []
        # wall-anchored monotonic clock: cross-process lanes align on wall
        # time, in-process durations stay monotonic
        self._mono0 = time.monotonic()
        self._wall0 = time.time()
        # scoped spans open on each thread, innermost last (ring nesting)
        self._local = threading.local()
        # set-up phases: kept whether or not the tracer is enabled
        self._phases: "deque[Dict]" = deque(maxlen=MAX_PHASES)
        # host pauses: kept likewise, in a deque of their own
        self._pauses: "deque[Dict]" = deque(maxlen=MAX_PAUSES)
        self.pauses_dropped = 0

    # ------------------------------------------------------------------ admin
    def enable(self, pid_label: Optional[str] = None,
               max_spans: Optional[int] = None) -> "Tracer":
        if max_spans is not None and max_spans != self.max_spans:
            self.max_spans = int(max_spans)
            with self._lock:
                self._spans = deque(self._spans, maxlen=self.max_spans)
        if pid_label:
            self._pid_label = pid_label
        self.enabled = True
        return self

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def stream_to(self, path: str) -> None:
        """Also append every finished span to ``path`` as one JSON line."""
        self._stream = open(path, "a", buffering=1)

    def close_stream(self) -> None:
        if self._stream is not None:
            self._stream.close()
            self._stream = None

    # ------------------------------------------------------------------ sinks
    def add_sink(self, fn) -> None:
        """Register a callable fed every finished span dict (commit order,
        ingested spans included). The flight recorder attaches here; a sink
        must be fast and must never raise."""
        if fn not in self._sinks:
            self._sinks.append(fn)

    def remove_sink(self, fn) -> None:
        if fn in self._sinks:
            self._sinks.remove(fn)

    # ------------------------------------------------------------------ clock
    def ts_us(self, mono: Optional[float] = None) -> float:
        """Wall-anchored timestamp in µs from a ``time.monotonic`` reading."""
        m = time.monotonic() if mono is None else mono
        return (self._wall0 + (m - self._mono0)) * 1e6

    # ------------------------------------------------------------------- spans
    def _new_id(self) -> str:
        return f"{os.getpid():x}.{next(self._ids):x}"

    def new_trace_id(self) -> str:
        return f"t{os.getpid():x}.{next(self._ids):x}.{os.urandom(3).hex()}"

    def begin(self, name: str, cat: str = CAT_SERVING,
              ctx: Optional[SpanContext] = None, attrs: Optional[Dict] = None,
              t0: Optional[float] = None, tid: Optional[str] = None
              ) -> Optional[OpenSpan]:
        """Open a ROOT-scoped span. With ``ctx`` (a propagated parent), the new
        span joins that trace under that parent; otherwise a fresh trace id is
        minted — this is the request/step scope boundary."""
        if not self.enabled:
            return None
        trace_id, parent_id = _parent_of(ctx)
        if trace_id is None:
            trace_id = self.new_trace_id()
        return OpenSpan(name, cat, trace_id, self._new_id(), parent_id,
                        time.monotonic() if t0 is None else t0, attrs,
                        tid or threading.current_thread().name)

    def start_span(self, name: str, parent=None, cat: Optional[str] = None,
                   attrs: Optional[Dict] = None, t0: Optional[float] = None,
                   tid: Optional[str] = None) -> Optional[OpenSpan]:
        """Open a child span under ``parent`` (OpenSpan or SpanContext)."""
        if not self.enabled or parent is None:
            return None
        trace_id, parent_id = _parent_of(parent)
        return OpenSpan(name, cat or getattr(parent, "cat", CAT_SERVING),
                        trace_id, self._new_id(), parent_id,
                        time.monotonic() if t0 is None else t0, attrs,
                        tid or threading.current_thread().name)

    def end_span(self, open_span: Optional[OpenSpan],
                 t1: Optional[float] = None,
                 attrs: Optional[Dict] = None) -> None:
        if open_span is None:
            return
        if attrs:
            open_span.attrs.update(attrs)
        t1 = time.monotonic() if t1 is None else t1
        self._commit(open_span.name, open_span.cat, open_span.trace_id,
                     open_span.span_id, open_span.parent_id,
                     self.ts_us(open_span.t0),
                     max((t1 - open_span.t0) * 1e6, 0.0),
                     open_span.attrs, open_span.tid)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current(self) -> Optional[Span]:
        """The innermost scoped span open on this thread that the ring or the
        phase list keeps, or ``None``: what a span opened on ANOTHER thread
        (the chunk watchdog's worker) passes as ``parent``."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def span(self, name: str, parent=None, cat: Optional[str] = None,
             **attrs) -> Span:
        """A scoped span (``with tracer.span(...) as sp``): always a profiler
        annotation, and a ring span while the tracer is enabled. ``parent``
        (a request's root, a :class:`Span`, a :class:`SpanContext`) places the
        ring span on that trace; without one it nests under the thread's
        innermost open span, else roots a fresh trace id."""
        if not self.enabled:
            return Span(self, name, None, attrs)
        return Span(self, name, self._open_under(name, parent, cat, attrs),
                    attrs)

    def _open_under(self, name, parent, cat, attrs) -> OpenSpan:
        if parent is None or parent.trace_id is None:
            parent = self.current()
        if parent is not None and parent.trace_id is not None:
            return self.start_span(name, parent, cat, attrs)
        return self.begin(name, cat or CAT_SERVING, None, attrs)

    def phase(self, name: str, **attrs) -> Span:
        """A set-up phase: a scoped span that is ALSO kept in :attr:`phases`
        whether or not the tracer is enabled (set-up runs before anyone could
        enable it)."""
        open_span = self._open_under(name, None, CAT_SETUP, attrs) \
            if self.enabled else None
        return Span(self, name, open_span, attrs, keep=True)

    def _keep_phase(self, span: Span, stack: list) -> None:
        parent = next((s.name for s in reversed(stack) if s._keep), None)
        self._phases.append({"name": span.name, "t0": span.t0, "t1": span.t1,
                             "parent": parent, "attrs": dict(span.attrs)})

    @property
    def phases(self) -> List[Dict]:
        """Set-up phases finished so far, in order of completion: ``name``,
        ``t0``/``t1`` (``time.monotonic``), ``parent`` (the enclosing phase's
        name or ``None``), ``attrs``."""
        return list(self._phases)

    def record_pause(self, name: str, t0: float, t1: float, ring: bool = True,
                     **attrs) -> None:
        """A host pause between two ``time.monotonic`` readings, known only
        when it is over: kept in :attr:`pauses` whether or not the tracer is
        enabled and, while it is (and ``ring``), committed to the ring as the
        root of a trace of its own. The collector's hook passes ``ring=False``:
        it runs wherever an allocation happens to, the ring's lock included."""
        if len(self._pauses) == self._pauses.maxlen:
            self.pauses_dropped += 1
        self._pauses.append({"name": name, "t0": t0, "t1": t1,
                             "attrs": attrs})
        if self.enabled and ring:
            self._commit(name, CAT_HOST, self.new_trace_id(), self._new_id(),
                         None, self.ts_us(t0), max((t1 - t0) * 1e6, 0.0),
                         attrs, threading.current_thread().name)

    @property
    def pauses(self) -> List[Dict]:
        """Host pauses so far, oldest first: ``name`` (``host.gc`` |
        ``host.stall``), ``t0``/``t1`` (``time.monotonic``), ``attrs``. At
        most :data:`MAX_PAUSES`; :attr:`pauses_dropped` counts the rest."""
        return list(self._pauses)

    def gc_ms_between(self, t0: float, t1: float) -> float:
        """Milliseconds of kept collector pauses inside ``[t0, t1]``: how
        much of a stall the collector explains."""
        total = 0.0
        for p in reversed(self._pauses):
            if p["t1"] <= t0:
                break
            if p["name"] == "host.gc":
                total += max(0.0, min(p["t1"], t1) - max(p["t0"], t0))
        return total * 1e3

    def record_span(self, name: str, parent, t0: float, t1: float,
                    cat: Optional[str] = None, attrs: Optional[Dict] = None,
                    tid: Optional[str] = None) -> None:
        """Retroactive span between two ``time.monotonic`` readings (e.g.
        queue wait, measured arrival→admit)."""
        if not self.enabled or parent is None:
            return
        trace_id, parent_id = _parent_of(parent)
        self._commit(name, cat or getattr(parent, "cat", CAT_SERVING),
                     trace_id, self._new_id(), parent_id, self.ts_us(t0),
                     max((t1 - t0) * 1e6, 0.0), attrs or {},
                     tid or threading.current_thread().name)

    def instant(self, name: str, parent, cat: Optional[str] = None,
                attrs: Optional[Dict] = None) -> None:
        if not self.enabled or parent is None:
            return
        now = time.monotonic()
        self.record_span(name, parent, now, now, cat=cat, attrs=attrs)

    def _commit(self, name, cat, trace_id, span_id, parent_id, ts, dur,
                attrs, tid) -> None:
        span = {"name": name, "cat": cat, "trace_id": trace_id,
                "span_id": span_id, "parent_id": parent_id, "ts": ts,
                "dur": dur, "pid": self._pid_label, "tid": tid,
                "attrs": attrs}
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)
        if self._stream is not None:
            self._stream.write(json.dumps(span) + "\n")
        if self._sinks:
            for fn in self._sinks:
                fn(span)

    # ----------------------------------------------------------- cross-process
    def ingest(self, spans: List[Dict], pid_label: Optional[str] = None
               ) -> None:
        """Merge spans exported by another process (its ``drain()`` output).
        Works even while this tracer is disabled — the parent may collect a
        child's spans without tracing itself."""
        ingested = []
        with self._lock:
            for s in spans:
                s = dict(s)
                if pid_label:
                    s["pid"] = pid_label
                if len(self._spans) == self._spans.maxlen:
                    self.dropped += 1
                self._spans.append(s)
                ingested.append(s)
        if self._sinks:
            for s in ingested:
                for fn in self._sinks:
                    fn(s)

    def drain(self) -> List[Dict]:
        """Remove and return every finished span (the subprocess streaming
        path: the child drains after each scheduler step and ships the batch
        over its stdout pipe)."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    @property
    def spans(self) -> List[Dict]:
        with self._lock:
            return list(self._spans)

    # ---------------------------------------------------------------- exports
    def chrome_events(self) -> List[Dict]:
        """Chrome trace events ('X' completes + 'M' lane metadata)."""
        return chrome_events_from(self.spans)

    def export_chrome(self, path: str) -> int:
        """Write Perfetto-loadable Chrome-trace JSON; returns the span count."""
        events = self.chrome_events()
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "otherData": {"dropped_spans": self.dropped}}, f)
        return sum(1 for e in events if e["ph"] == "X")


def chrome_events_from(spans: List[Dict]) -> List[Dict]:
    """Chrome trace events ('X' completes + 'M' lane metadata) from finished
    span dicts. Shared by :meth:`Tracer.chrome_events` and the flight
    recorder's dump bundle (which exports RETAINED trees, not the whole ring),
    so both artifacts stay Perfetto-loadable through one builder."""
    pids: Dict[str, int] = {}
    tids: Dict[tuple, int] = {}
    events: List[Dict] = []
    for s in spans:
        pid = pids.setdefault(s["pid"], len(pids) + 1)
        tkey = (s["pid"], s["tid"])
        tid = tids.setdefault(tkey, len(tids) + 1)
        args = {"trace_id": s["trace_id"], "span_id": s["span_id"]}
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        args.update(s.get("attrs") or {})
        events.append({"name": s["name"], "cat": s["cat"], "ph": "X",
                       "ts": s["ts"], "dur": max(s["dur"], 1.0),
                       "pid": pid, "tid": tid, "args": args})
    for label, pid in pids.items():
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": label}})
    for (plabel, tlabel), tid in tids.items():
        events.append({"name": "thread_name", "ph": "M",
                       "pid": pids[plabel], "tid": tid,
                       "args": {"name": tlabel}})
    return events


_tracer = Tracer()

# ------------------------------------------------------------- the collector
#: the collection under way, ``(time.monotonic at its start, its annotation
#: or None)``: the collector runs one at a time, start and stop on one thread
_gc_open = None
#: None until the process's tracer is first asked for, then whether the hook
#: is in ``gc.callbacks`` (False once removed by hand: nothing puts it back)
_gc_hooked = None


def _gc_hook(phase: str, info: Dict) -> None:
    """The ONE ``gc.callbacks`` entry: every collection's pause goes into the
    registry (``host/gc_pause_ms``, ``host/gc_collections_total``), a full one
    is a ``host.gc`` annotation for an armed profiler, and a full one or a
    pause of :data:`GC_KEEP_MS` and more is kept in ``tracer.pauses``.
    Generations 0 and 1 open no annotation: a TraceMe a young collection
    would flood a trace. It runs inside the collector, on whatever thread
    allocated: instruments are touched directly (no monitor fan-out) and the
    ring not at all."""
    global _gc_open
    if phase == "start":
        ann = None
        t0 = time.monotonic()
        if info["generation"] == 2:
            ann = TraceAnnotation("host.gc", generation=2)
            ann.__enter__()
        _gc_open = (t0, ann)
        return
    t1 = time.monotonic()
    if _gc_open is None:        # hooked while a collection was under way
        return
    (t0, ann), _gc_open = _gc_open, None
    if ann is not None:
        ann.set_metadata(collected=info["collected"])
        ann.__exit__(None, None, None)
    ms = (t1 - t0) * 1e3
    registry = metrics.get_registry()
    registry.histogram("host/gc_pause_ms").observe(ms)
    registry.counter("host/gc_collections_total").inc()
    if info["generation"] == 2 or ms >= GC_KEEP_MS:
        tracer = _tracer
        if hasattr(tracer, "record_pause"):      # a test's stand-in may not
            tracer.record_pause("host.gc", t0, t1, ring=False,
                                generation=info["generation"],
                                collected=info["collected"])


def install_gc_hook() -> None:
    """Put :func:`_gc_hook` into ``gc.callbacks`` (once, however often it is
    called)."""
    global _gc_hooked
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
    _gc_hooked = True


def uninstall_gc_hook() -> None:
    """Take the hook out again (tests); :func:`get_tracer` does not put it
    back, :func:`install_gc_hook` does."""
    global _gc_hooked, _gc_open
    if _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)
    _gc_hooked, _gc_open = False, None


def scope(name: str):
    """Name the device ops traced under it ``ds.<name>``: a ``with`` block
    (or a decorator) inside a function jax compiles. ``name`` is declared in
    ``schema.SCOPES``; an undeclared one raises while jax traces."""
    if name not in schema.SCOPES:
        raise KeyError(
            f"device scope {name!r} is not declared in observability.schema."
            "SCOPES — declare it (layer, what it holds, what reads it) "
            "before opening it")
    return jax.named_scope(schema.SCOPE_PREFIX + name)


def get_tracer() -> Tracer:
    """The process's tracer; the first call hooks the collector's pauses
    into it (:func:`install_gc_hook`), in a training process as in a server."""
    if _gc_hooked is None:
        install_gc_hook()
    return _tracer
