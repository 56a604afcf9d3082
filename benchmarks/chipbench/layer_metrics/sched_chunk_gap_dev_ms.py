"""Milliseconds on the DEVICE's clock between the end of one decode-chunk
execution and the start of the next, with no other program's execution
between them (an admission's prefill or scatter, a zero fill): the median
over the traced window. No host time enters, so the offset between a trace's
host and device planes cannot move it. Earlier lines: how the dispatches and
the executions were paired (by order), and the causal bounds the pairs put on
that offset (``chunk_cycles.say_bounds``)."""

from benchmarks.chipbench import chunk_cycles as cc
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "sched_chunk_gap_dev_ms"
UNIT = "ms"
LAYER = "serve scheduler"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    found = cc.cycles(ctx) if red and red["devices"] else []
    if not found:
        return None         # no device plane, or a program without ``seq``
    cc.say_bounds(ctx, found)
    gaps = cc.device_gaps(red)
    if not gaps:
        return None
    say(f"device gap between consecutive decode chunks: {len(gaps)} gaps, "
        f"quartiles {cc.quartiles([g * 1e3 for g in gaps])} ms, "
        f"longest {max(gaps) * 1e3:.3f} ms")
    return ps.median_ms(gaps)
