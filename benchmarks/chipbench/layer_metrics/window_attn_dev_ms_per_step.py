"""Device milliseconds a decode step spends in the scope ``attn.window``, over
all layers: a windowed layer's ring (the token's keys and values written at row
``t mod window``, the attention over the ring's rows). ``None`` for a program
that opens no such scope."""

from benchmarks.chipbench import device_scopes as ds

NAME = "window_attn_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "attn.window"


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    return ds.ms_per_step(t, SCOPE)
