"""The decode chunks of a traced serving window, read ONE CLOCK AT A TIME.

A profiler trace has two clocks that the file presents as one: the host
plane's (the program's spans) and the device plane's (``XLA Modules``). They
sit about a millisecond apart, by an offset that differs from trace to trace,
and a reader that subtracts across them (device-idle time inside a host span;
"the execution that starts inside the span") has that offset in its value.
Nothing here does: a gap between two executions is device time alone, a
turnaround is the program's own ``time.monotonic`` difference carried on the
span as an attribute, and dispatches meet executions BY ORDER (the executor
numbers its dispatches, ``serving.dispatch``'s ``seq``; the device runs them
in that order), anchored once where a dispatch and an execution lie within a
few milliseconds of each other: chunks are 90-260 ms apart, the planes ~1 ms.

The pairs then BOUND the offset, which is all the two planes can say about
each other: an execution cannot start before its dispatch does, nor end after
the fetch that waited for it returns. ``say_bounds`` prints both bounds, their
width, and whether zero lies between them.

A program without ``seq`` on its dispatch spans (a commit before it) gives no
pairs, and every reader built on this returns ``None``.
"""

from typing import List, NamedTuple, Optional, Sequence, Tuple

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import stats
from benchmarks.chipbench import trace_reduce as tr

PROGRAM = "decode_chunk"
ANCHOR_S = 5e-3          # a dispatch and its execution lie closer than this


class Cycle(NamedTuple):
    dispatch: ps.Span                   # host plane
    fetch: Optional[ps.Span]            # host plane; None where the trace ends first
    run: Tuple[float, float]            # device plane: the execution's start, end


def dispatches(spans: Sequence[ps.Span]) -> List[ps.Span]:
    """The decode chunks' ``serving.dispatch`` spans that carry ``seq``, in
    ``seq`` order."""
    mine = [sp for sp in spans if sp.name == "serving.dispatch"
            and sp.stats.get("program") == PROGRAM and "seq" in sp.stats]
    return sorted(mine, key=lambda sp: int(sp.stats["seq"]))


def executions(red: dict) -> List[Tuple[float, float]]:
    """Chip 0's executions of the decode chunk, clipped ones too, by start."""
    if not red or not red["devices"]:
        return []
    return sorted(tr.programs(red, PROGRAM, whole_only=False))


def pair(spans: Sequence[ps.Span], runs: Sequence[Tuple[float, float]]) -> List[Cycle]:
    """Dispatches and executions paired by order from the first execution
    that starts within ``ANCHOR_S`` of a dispatch span (a trace that opens
    between a dispatch and its execution holds an execution with no span: it
    is passed over). A pair that breaks causality by more than ``ANCHOR_S``
    (an execution that starts before its dispatch, or ends after the fetch
    that waited for it returned) ends the pairing: the orders have come apart,
    and nothing after it can be trusted."""
    disp = dispatches(spans)
    fetches = [sp for sp in spans if sp.name == "serving.fetch"
               and sp.stats.get("program") == PROGRAM]
    anchor = next(((i, j) for j, (s, _) in enumerate(runs)
                   for i, d in enumerate(disp)
                   if d.start - ANCHOR_S <= s <= d.end + ANCHOR_S), None)
    if anchor is None:
        return []
    out = []
    for d, run, nxt in zip(disp[anchor[0]:], runs[anchor[1]:],
                           disp[anchor[0] + 1:] + [None]):
        until = nxt.start if nxt is not None else float("inf")
        fetch = next((f for f in fetches if f.thread == d.thread
                      and d.end <= f.start < until), None)
        if run[0] < d.start - ANCHOR_S or \
                (fetch is not None and run[1] > fetch.end + ANCHOR_S):
            break
        out.append(Cycle(d, fetch, run))
    return out


def cycles(ctx) -> List[Cycle]:
    if not ctx.trace_path or not ctx.trace_reduced:
        return []
    return pair(ps.load(ctx.trace_path), executions(ctx.trace_reduced))


def offset_bounds(found: Sequence[Cycle]) -> Optional[Tuple[float, float]]:
    """``(lower, upper)`` in seconds for the host plane's clock minus the
    device plane's: lower = the latest a dispatch STARTS after its execution
    does, upper = the earliest a fetch RETURNS after its execution ends."""
    lower = [c.dispatch.start - c.run[0] for c in found]
    upper = [c.fetch.end - c.run[1] for c in found if c.fetch is not None]
    if not lower or not upper:
        return None
    return max(lower), min(upper)


def device_gaps(red: dict) -> List[float]:
    """Seconds on the DEVICE's clock from the end of one decode-chunk
    execution to the start of the next, for the pairs that lie wholly inside
    the window with no other program's execution between them (an admission's
    prefill, a scatter, a zero fill)."""
    if not red or not red["devices"]:
        return []
    lo, hi = red["window"]
    runs = sorted((s, e, n) for n, s, e in red["devices"][0]["programs"])
    return [b[0] - a[1] for a, b in zip(runs, runs[1:])
            if a[2] == b[2] == PROGRAM and a[0] >= lo and b[1] <= hi]


def chunk_spans(ctx) -> List[ps.Span]:
    """The window's decode-chunk spans (a speculative round is one)."""
    return [sp for sp in ps.in_window(ctx)
            if sp.name in ("serving.decode_chunk", "serving.spec_verify")]


def turnarounds(ctx) -> List[float]:
    """``turnaround_ms`` of the window's chunks that had no admission before
    them (``admit_ms`` 0); ``[]`` for a program without the attribute."""
    return [float(sp.stats["turnaround_ms"]) for sp in chunk_spans(ctx)
            if "turnaround_ms" in sp.stats and not float(sp.stats.get("admit_ms", 0))]


def quartiles(values: Sequence[float]) -> str:
    return " ".join(f"{q:.3f}" for q in stats.quartiles(values)) or "none"


def say_bounds(ctx, found: Sequence[Cycle]) -> None:
    """Print the pairing ``found`` (:func:`cycles`) and what it says of the
    two planes' clocks."""
    from benchmarks.chipbench.harness import say
    runs = executions(ctx.trace_reduced)
    disp = dispatches(ps.load(ctx.trace_path))
    say(f"chunk cycles: {len(found)} of {len(disp)} serving.dispatch(program="
        f"{PROGRAM}) spans paired BY ORDER (seq) with {len(runs)} executions on "
        "chip 0" + (f", from seq {found[0].dispatch.stats['seq']}" if found else ""))
    bounds = offset_bounds(found)
    if bounds is None:
        return
    lower, upper = (b * 1e3 for b in bounds)
    inside = "lies" if lower <= 0.0 <= upper else "does NOT lie"
    say(f"host plane minus device plane, causal bounds over {len(found)} chunks: "
        f"at least {lower:+.3f} ms (an execution starts no earlier than its "
        f"dispatch), at most {upper:+.3f} ms (a fetch returns no earlier than its "
        f"execution ends); width {upper - lower:.3f} ms; zero {inside} between them")
