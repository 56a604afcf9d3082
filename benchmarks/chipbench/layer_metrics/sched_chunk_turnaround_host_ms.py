"""Milliseconds on the HOST's clock from a decode chunk's fetch return to the
next chunk's dispatch return, as the program stamped them itself
(``turnaround_ms`` on the ``serving.decode_chunk`` span: a difference of two
``time.monotonic`` readings its spans took): the median over the traced
chunks that had no admission before them. No device time enters. Earlier
lines: the fetch wait's median, the two phases' quartiles, and the device's
gap less this turnaround: what the runtime adds between the host's return and
the device's start (completion and launch latency), a difference of two
one-clock numbers; and the median duration of ``serving.dispatch`` by program
(what a dispatch costs the host, a prefill's beside a chunk's)."""

import statistics

from benchmarks.chipbench import chunk_cycles as cc
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "sched_chunk_turnaround_host_ms"
UNIT = "ms"
LAYER = "serve scheduler"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return None
    turn = cc.turnarounds(ctx)
    if not turn:
        return None         # a program whose chunk spans lack the attribute
    waits = [float(sp.stats["fetch_wait_ms"]) for sp in cc.chunk_spans(ctx)
             if "fetch_wait_ms" in sp.stats]
    value = statistics.median(turn)
    say(f"chunk cycle on the host's clock: fetch wait median "
        f"{statistics.median(waits):.3f} ms over {len(waits)} "
        f"chunks, quartiles {cc.quartiles(waits)}; turnaround over {len(turn)} "
        f"chunks with no admission before them, quartiles {cc.quartiles(turn)} ms")
    by_program = {}
    for sp in ps.named(ps.in_window(ctx), "serving.dispatch"):
        by_program.setdefault(str(sp.stats.get("program")), []).append(sp.end - sp.start)
    say("serving.dispatch on the host's clock, median ms by program: "
        + ", ".join(f"{prog} {ps.median_ms(d):.3f} over {len(d)}"
                    for prog, d in sorted(by_program.items())))
    gap = ps.median_ms(cc.device_gaps(red))
    if gap is not None:
        say(f"device gap {gap:.3f} ms less host turnaround {value:.3f} ms = "
            f"{gap - value:+.3f} ms that the runtime adds (completion and launch)")
    return value
