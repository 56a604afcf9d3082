"""Device-busy milliseconds inside one ``train_step`` program: the median
over the traced steps, on chip 0."""

from benchmarks.chipbench import trace_reduce as tr

NAME = "train_step_dev_ms"
UNIT = "ms"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return None
    busy = tr.median_program_busy_s(red, "train_step")
    return None if busy is None else busy * 1e3
