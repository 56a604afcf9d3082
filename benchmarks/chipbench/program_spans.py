"""The program's own spans, read from a profiler trace WITH their attributes.

``deepspeed_tpu/observability/trace.py`` opens every instrumented region as a
``jax.profiler.TraceAnnotation(name, **attrs)``: in the ``.xplane.pb`` the
span is an event on the line of the thread that opened it, named ``name``,
with the attributes as ``stats``, on the clock the device's events share
(seconds from the start of the profile, as in ``trace_reduce``). The names and
what each is for are declared in ``deepspeed_tpu/observability/schema.py:
SPANS``. ``trace_reduce.reduce_trace`` keeps names and times only; the readers
of the ``program_span`` metrics need the attributes, so they read the file
through here.

A program without these spans (a commit before they existed) gives empty
lists, and every reader built on this returns ``None``.
"""

import functools
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence

from benchmarks.chipbench import trace_reduce as tr

PREFIXES = ("serving.", "train.", "train_step", "setup.")


class Span(NamedTuple):
    name: str
    start: float            # seconds on the trace's clock
    end: float
    stats: Dict[str, object]
    thread: str


@functools.lru_cache(maxsize=2)
def load(path: str) -> List[Span]:
    """Every program span of the trace's host plane, by start time."""
    out = []
    for plane in tr.load(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = ev.start_ns * 1e-9
                    out.append(Span(ev.name, s, s + ev.duration_ns * 1e-9,
                                    dict(ev.stats), line.name))
    out.sort(key=lambda sp: (sp.start, -sp.end))
    return out


def in_window(ctx) -> List[Span]:
    """The program spans that lie wholly inside the traced window, or ``[]``
    where the run was not traced."""
    if not ctx.trace_path or not ctx.trace_reduced:
        return []
    lo, hi = ctx.trace_reduced["window"]
    return [sp for sp in load(ctx.trace_path) if sp.start >= lo and sp.end <= hi]


def named(spans: Sequence[Span], name: str) -> List[Span]:
    return [sp for sp in spans if sp.name == name]


def inside(spans: Sequence[Span], outer: Span,
           name: Optional[str] = None) -> List[Span]:
    """Spans of the outer span's thread that lie inside it (not itself)."""
    return [sp for sp in spans if sp is not outer and sp.thread == outer.thread
            and sp.start >= outer.start and sp.end <= outer.end
            and (name is None or sp.name == name)]


def host_s(red: dict, span: Span) -> float:
    """Seconds of the span in which no op ran on chip 0: what the host did
    while the device waited."""
    return (span.end - span.start) - tr.busy_inside(red, [(span.start, span.end)])[0]


def say_idle_by_span(ctx) -> None:
    """Print chip 0's idle seconds inside the window, in gaps of 20 us and
    more, by the innermost PROGRAM span over the middle of each gap, and the
    share of them that such a span names. (``trace_reduce.breakdown`` names a
    gap by the innermost host event of any name; jax's own events nest inside
    the program's spans and win there.)"""
    from benchmarks.chipbench.harness import say
    red = ctx.trace_reduced
    lo, hi = red["window"]
    spans = load(ctx.trace_path)
    merged = tr.busy(red["devices"][0], lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    by_name: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < tr.SMALL_GAP_S:
            continue
        mid = (a + b) / 2
        over = [sp for sp in spans if sp.start <= mid <= sp.end]
        key = min(over, key=lambda sp: sp.end - sp.start).name if over \
            else "no_program_span"
        by_name[key] = by_name.get(key, 0.0) + (b - a)
    all_s = sum(by_name.values())
    if not all_s:
        return
    named = all_s - by_name.get("no_program_span", 0.0)
    say(f"idle gaps of 20 us and more, by the innermost program span: "
        + ", ".join(f"{k} {v:.4f} s" for k, v in
                    sorted(by_name.items(), key=lambda kv: -kv[1]))
        + f"; a program span names {100.0 * named / all_s:.1f} % of {all_s:.4f} s")


def total(spans: Sequence[Span], key: str) -> float:
    """Sum of an integer attribute over the spans that carry it."""
    return sum(float(sp.stats[key]) for sp in spans if key in sp.stats)


def registry_totals() -> dict:
    """The four decode and delivery counters of the program's registry (cumulative over the
    process: warm-up and window), or ``{}`` where it has none of them."""
    from deepspeed_tpu.observability.metrics import get_registry
    snap = get_registry().snapshot()
    out = {}
    for short in ("decode_slot_steps_total", "decode_tokens_kept_total",
                  "deliveries_total", "deliveries_stalled_total"):
        entry = snap.get("serving/" + short)
        if entry is not None:
            out[short] = float(entry["value"] if isinstance(entry, dict) else entry)
    return out


def median_ms(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) * 1e3 if values else None


def fmt(ms: Optional[float]) -> str:
    return "none" if ms is None else f"{ms:.3f}"
