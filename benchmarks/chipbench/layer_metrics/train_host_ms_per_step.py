"""Host milliseconds of one ``engine.train_batch`` call: the duration of the
program's ``train_step`` host span (it covers what the host did and waits for
nothing on the device); the median over the traced calls. Earlier lines give
its three children: ``train.host_batch`` (reshape and placement of the batch),
``train.dispatch`` (the call of the compiled step) and ``train.bookkeeping``
(schedulers, timers, monitor events)."""

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "train_host_ms_per_step"
UNIT = "ms"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)

PARTS = ("train.host_batch", "train.dispatch", "train.bookkeeping")


def read(ctx):
    spans = ps.in_window(ctx)
    steps = ps.named(spans, "train_step")
    if not steps:
        return None
    for part in PARTS:
        vals = [p.end - p.start for s in steps for p in ps.inside(spans, s, part)]
        say(f"under train_step: {part} {ps.fmt(ps.median_ms(vals))} ms median "
            f"over {len(vals)}")
    if ctx.trace_reduced["devices"]:
        ps.say_idle_by_span(ctx)
    ids = [s.stats.get("step") for s in steps]
    say(f"{len(steps)} train_step host spans in the traced window, steps "
        f"{ids[0]}..{ids[-1]}")
    return ps.median_ms([s.end - s.start for s in steps])
