"""The share of the WHOLE measured window's wall time in which the slots'
decode streams stood behind a prefill (the scheduler runs no decode chunk
while it admits, and the device runs one program at a time): the number a
chunked prefill would move.

The program's ``serving.prefill`` spans (a cache-miss prefill's dispatch, its
device time and the first token's fetch) reach the trace only while it is
armed, a few seconds that catch a handful of prefills, so their share of the
traced window swings with how many it catches. What covers the whole window
is the benchmark's own ``chipbench.step`` span around every ``step()``: a
step is its admissions and then one decode chunk, and a step without an
admission takes the chunk's time. So the traced window CALIBRATES (the
median length of the steps that hold no ``serving.prefill`` span, and the
check that in the steps that hold one the time over that median is the spans'
own time) and the whole window is READ: the sum, over its steps that ran
longer than a chunk by half a prefill or more, of the time over the chunk's,
over the time inside the window's steps (what the harness does BETWEEN steps
is not the program's: in a traced run the profiler's stop alone holds the
window for ~15 s). Earlier lines: the traced window's prefills and chunks, its
share by spans and by steps side by side, and the whole window's count.
``None`` without the spans (a program before them, an untraced run)."""

import statistics

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "prefill_stall_pct"
UNIT = "%"
LAYER = "serve scheduler"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def traced_steps(ctx):
    """The benchmark's ``chipbench.step`` spans inside the traced window, on
    the trace's clock (``program_spans`` keeps the program's names alone)."""
    lo, hi = ctx.trace_reduced["window"]
    out = []
    for plane in tr.load(ctx.trace_path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if ev.name == "chipbench.step" and s >= lo \
                            and s + ev.duration_ns * 1e-9 <= hi:
                        out.append((s, s + ev.duration_ns * 1e-9))
    return sorted(out)


def over_a_chunk(steps, chunk_s: float, least_s: float):
    """``[seconds over chunk_s]`` of the steps that ran at least ``least_s``
    over it."""
    return [e - s - chunk_s for s, e in steps if e - s - chunk_s >= least_s]


def read(ctx):
    prefills = ps.named(ps.in_window(ctx), "serving.prefill")
    if not prefills or ctx.result is None:
        return None
    steps = traced_steps(ctx)
    bare = [e - s for s, e in steps
            if not any(s <= p.start and p.end <= e for p in prefills)]
    if not bare:
        return None
    chunk_s = statistics.median(bare)
    prefill_s = statistics.median(p.end - p.start for p in prefills)
    lo, hi = ctx.trace_reduced["window"]
    by_spans = sum(p.end - p.start for p in prefills)
    traced = over_a_chunk(steps, chunk_s, prefill_s / 2)
    say(f"traced window ({hi - lo:.3f} s): {len(prefills)} prefills of "
        f"{prefill_s * 1e3:.3f} ms median = {by_spans:.3f} s by their spans "
        f"({100.0 * by_spans / (hi - lo):.2f} %); {len(steps)} steps, {len(bare)} "
        f"without a prefill of {chunk_s * 1e3:.3f} ms median; the {len(traced)} steps "
        f"at least {prefill_s * 5e2:.1f} ms over it ran {sum(traced):.3f} s over it")
    w0, w1 = ctx.result.window
    inside = [(s, e) for n, s, e in ctx.spans
              if n == "chipbench.step" and s >= w0 and e <= w1]
    whole = over_a_chunk(inside, chunk_s, prefill_s / 2)
    stepping = sum(e - s for s, e in inside)
    say(f"whole window ({w1 - w0:.3f} s, {stepping:.3f} s of it inside its "
        f"{len(inside)} steps): {len(whole)} steps held prefills, {sum(whole):.3f} s "
        f"over a chunk's {chunk_s * 1e3:.3f} ms (~{sum(whole) / prefill_s:.1f} prefills)")
    return 100.0 * sum(whole) / stepping
