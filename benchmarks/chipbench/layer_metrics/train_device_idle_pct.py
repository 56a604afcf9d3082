"""Share of the traced window in which no op ran on a chip (1 - union of
device-op intervals / window), averaged over the chips."""

from benchmarks.chipbench import trace_reduce as tr

NAME = "train_device_idle_pct"
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    return tr.idle_pct(ctx.trace_reduced)
