"""Device milliseconds a decode step (a forward, for a model that generates
by blocks) spends on attention: the scopes ``attn.core`` (the kernel or the
XLA products and softmax), ``attn.heads`` (the layout changes around it,
rotary, q/k norm) and ``kv.append``, over all layers. Earlier lines: each."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench.harness import say

NAME = "decode_attn_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPES = ("attn.core", "attn.heads", "kv.append")


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None:
        return None
    say("attention, ms a step: " + ", ".join(
        f"{scope} {ds.ms_per_step(t, scope):.3f}" for scope in SCOPES))
    return ds.ms_per_step(t, *SCOPES)
