"""Device milliseconds of one prefill program (``prefill`` or
``suffix_prefill``); the median over those in the traced window."""

from benchmarks.chipbench import trace_reduce as tr

NAME = "prefill_dev_ms"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "ttft_p50_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return None
    busy = tr.median_program_busy_s(red, "prefill", "suffix_prefill")
    return None if busy is None else busy * 1e3
