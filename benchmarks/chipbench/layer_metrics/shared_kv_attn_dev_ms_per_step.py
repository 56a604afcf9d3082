"""Device milliseconds a decode step spends in the scope ``attn.shared``, over
all layers: differential attention over the ONE cache that the full layer
keeps and every cross layer after it re-reads (eight reads of the same live
rows a step at the published depth). The row's append is ``kv.append``, the
combination of a pair's two maps ``attn.diff``. ``None`` for a program that
opens no such scope."""

from benchmarks.chipbench import device_scopes as ds

NAME = "shared_kv_attn_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "attn.shared"


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    return ds.ms_per_step(t, SCOPE)
