"""Share of the traced window in which a collective ran on a chip and no
compute op did, averaged over the chips. A trace with no collective in it
(one chip) gives nothing."""

from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "exposed_collective_pct"
UNIT = "%"
LAYER = "zero and collectives"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return None
    total, exposed = tr.exposed_collective_s(red)
    if total <= 0.0:
        return None
    say(f"collectives: {total:.4f} s of the {tr.window_s(red):.4f} s traced "
        f"window on a chip, {exposed:.4f} s of them with no compute op running")
    return 100.0 * exposed / tr.window_s(red)
