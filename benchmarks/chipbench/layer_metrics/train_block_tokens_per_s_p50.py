"""The median of the window's block readings (tokens per second per chip of
8 steps at a time, host clock): what the pipeline does while nothing stalls.
The end-to-end rate is all tokens over the whole window; where it falls short
of this, the window held a stall."""

NAME = "train_block_tokens_per_s_p50"
UNIT = "tokens/s/chip"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    if ctx.rehearse:
        return None
    return ctx.result.counters.get("block_median_tokens_per_s_per_chip")
