"""Device milliseconds a decode chunk spends once, outside its loop of steps:
the scopes ``kv.gather`` (pages into the dense view), ``kv.copy_back`` (the
chunk's new rows back into the pages) and ``chunk.pack`` (the packed operand
and result), a chunk. Earlier lines: each, and beside them ALL the device time
of a chunk outside its loop, whatever its name: the compiler's own copies of
the dense view into the loop's carry are once-a-chunk work too and carry no
scope (the ``while``'s metadata, or a parameter's name)."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench.harness import say

NAME = "decode_per_chunk_dev_ms"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPES = ("kv.gather", "kv.copy_back", "chunk.pack")


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None:
        return None
    runs = ds.whole_runs(ctx.trace_reduced, "decode_chunk", ds.ops(ctx.trace_path))
    say("once a chunk, ms: " + ", ".join(
        f"{scope} {t.seconds(scope) / t.runs * 1e3:.3f}" for scope in SCOPES)
        + f"; all ops outside the chunk's loop "
          f"{ds.outside_the_loop(ctx.trace_path, runs) / t.runs * 1e3:.3f}")
    return t.seconds(*SCOPES) / t.runs * 1e3
