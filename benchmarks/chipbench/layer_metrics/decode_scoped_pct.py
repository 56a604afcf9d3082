"""Share of ``decode_chunk``'s device time whose op resolves to a scope the
program declared (``observability/schema.py: SCOPES``). Prints the run's table
of device time by scope, a step and a chunk, and what reading it cost."""

from benchmarks.chipbench import device_scopes as ds

NAME = "decode_scoped_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    return ds.scoped_pct(ctx, "decode_chunk")
