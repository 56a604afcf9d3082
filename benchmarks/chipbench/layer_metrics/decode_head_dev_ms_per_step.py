"""Device milliseconds a decode step (a forward, for a model that generates
by blocks) spends from the last layer's output to the chosen tokens: the
scopes ``head`` (final norm, the rows read, the vocabulary matmul and the
logits' copies) and ``sample`` (argmax or sampling, a block's unmasking).
Earlier lines: each."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench.harness import say

NAME = "decode_head_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPES = ("head", "sample")


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None:
        return None
    say("head, ms a step: " + ", ".join(
        f"{scope} {ds.ms_per_step(t, scope):.3f}" for scope in SCOPES))
    return ds.ms_per_step(t, *SCOPES)
