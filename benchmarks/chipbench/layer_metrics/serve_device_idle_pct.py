"""Share of the traced serving window in which no op ran on the chip."""

from benchmarks.chipbench import trace_reduce as tr

NAME = "serve_device_idle_pct"
UNIT = "%"
LAYER = "device"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    return tr.idle_pct(ctx.trace_reduced)
