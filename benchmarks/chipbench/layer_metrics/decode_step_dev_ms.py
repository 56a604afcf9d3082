"""Device milliseconds of one decode step: the device-busy time inside a
``decode_chunk`` program over the steps of a chunk; the median over the
traced chunks."""

from benchmarks.chipbench import trace_reduce as tr

NAME = "decode_step_dev_ms"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return None
    busy = tr.median_program_busy_s(red, "decode_chunk")
    return None if busy is None else busy / ctx.result.counters["chunk_size"] * 1e3
