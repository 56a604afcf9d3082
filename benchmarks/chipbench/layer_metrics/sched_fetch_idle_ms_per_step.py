"""Device-idle milliseconds of one scheduler step spent under the decode
chunk's ``serving.fetch`` (the chunk-boundary harvest: the chip waits while
the host pulls the chunk's arrays); the median over the traced steps that ran
a chunk. Earlier lines give the same for the chunk's ``serving.place_inputs``
and ``serving.dispatch`` and the step's ``serving.harvest`` and
``serving.telemetry``: together the idle share of ``sched_host_ms_per_step``;
and the window's idle gaps by the innermost program span over each."""

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "sched_fetch_idle_ms_per_step"
UNIT = "ms"
LAYER = "serve scheduler"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)

IN_CHUNK = ("serving.place_inputs", "serving.dispatch", "serving.fetch")
IN_STEP = ("serving.harvest", "serving.telemetry")


def read(ctx):
    if not ctx.trace_reduced or not ctx.trace_reduced["devices"]:
        return None            # no device plane: nothing to take busy time from
    spans = ps.in_window(ctx)
    red = ctx.trace_reduced
    idle = {name: [] for name in IN_CHUNK + IN_STEP}
    for step in ps.named(spans, "serving.step"):
        chunks = ps.inside(spans, step, "serving.decode_chunk")
        if not chunks:
            continue
        for name in IN_CHUNK:
            idle[name].append(sum(ps.host_s(red, sp)
                                  for sp in ps.inside(spans, chunks[0], name)))
        for name in IN_STEP:
            idle[name].append(sum(ps.host_s(red, sp)
                                  for sp in ps.inside(spans, step, name)))
    if not idle["serving.fetch"]:
        return None
    ps.say_idle_by_span(ctx)
    for name in IN_CHUNK + IN_STEP:
        say(f"device idle under {name}: {ps.fmt(ps.median_ms(idle[name]))} ms "
            f"a step, median over {len(idle[name])} steps")
    return ps.median_ms(idle["serving.fetch"])
