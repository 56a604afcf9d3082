"""A block forward's share of its memory roofline: the bytes one forward has
to move (``sdar_shapes.forward_bytes``: every parameter beside the experts,
each touched expert once, the live keys and values, the block's own rows)
over the chip's peak bytes/s, over the device time of a forward (device-busy
time inside a ``decode_chunk`` execution over the forwards its span counted,
median). The touched experts are the traced chunks' own count. ``None`` for a
program whose chunk spans carry no ``forwards``."""

import statistics

from benchmarks.chipbench import block_trace as bt
from benchmarks.chipbench import sdar_shapes as ss
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "block_forward_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    if not ctx.on_tpu or not red or not red["devices"]:
        return None
    pairs = [(sp, run) for sp, run in bt.decode_chunks(ctx)
             if "moe_experts_touched" in sp.stats]
    if not pairs:
        return None
    model = ctx.config["model"]
    slots = int(ctx.config["serve"]["slots"])
    live = ctx.result.counters["live_tokens_mean"]
    shares = []
    for sp, (lo, hi) in pairs:
        forwards = float(sp.stats["forwards"])
        touched = float(sp.stats["moe_experts_touched"]) / forwards
        need = ss.forward_bytes(model, slots, touched, live)
        spent = tr.busy_inside(red, [(lo, hi)])[0] / forwards
        if spent:
            shares.append((need, spent))
    if not shares:
        return None
    need = statistics.median(n for n, _ in shares)
    spent = statistics.median(s for _, s in shares)
    say(f"a forward has to move {need / 1e9:.3f} GB "
        f"({ss.params_beside_experts(model) * 2 / 1e9:.3f} beside the experts, "
        f"{live * ss.kv_bytes_per_token(model) / 1e9:.3f} of live keys and values, "
        f"the rest touched experts) and takes {spent * 1e3:.3f} ms of device time "
        f"(median of {len(shares)} chunks)")
    return 100.0 * statistics.median(
        n / ctx.peaks()["hbm_bytes_per_s"] / s for n, s in shares)

