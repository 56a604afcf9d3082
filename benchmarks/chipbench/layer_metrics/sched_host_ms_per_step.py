"""Host milliseconds of one scheduler step: the benchmark's span around
``sched.step()`` minus the device-busy time inside it, both on the trace's
clock; the median over the traced steps."""

import statistics

from benchmarks.chipbench import trace_reduce as tr

NAME = "sched_host_ms_per_step"
UNIT = "ms"
LAYER = "serve scheduler"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return None
    lo, hi = red["window"]
    steps = [(s, e) for n, s, e in red["host"]
             if n == "chipbench.step" and s >= lo and e <= hi]
    if not steps:
        return None
    busy = tr.busy_inside(red, steps)
    return statistics.median((e - s) - b for (s, e), b in zip(steps, busy)) * 1e3
