"""The grouped expert kernel's share of its roofline in its GATED form (three
matrices an expert), in the block forwards of decode: per traced chunk the
least time the chip could take for what its ``serving.decode_chunk`` span
counted (the larger of touched experts x an expert's bytes over peak bytes/s
and assignments x an expert's operations over peak FLOP/s,
``sdar_shapes.py``), over the trace time of ``moe_grouped_ffn`` inside that
chunk's execution. ``None`` for a program whose chunk spans carry no
``forwards`` (no generation by blocks) or no expert counts."""

from benchmarks.chipbench import block_trace as bt
from benchmarks.chipbench import hybrid_trace as ht
from benchmarks.chipbench import sdar_shapes as ss
from benchmarks.chipbench.harness import say

NAME = "moe_gated_ffn_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
KERNEL = "moe_grouped_ffn"


def read(ctx):
    if not ctx.on_tpu:
        return None
    model, peaks = ctx.config["model"], None
    spent = least = 0.0
    bound = set()
    for sp, (lo, hi) in bt.decode_chunks(ctx):
        if "moe_experts_touched" not in sp.stats:
            continue
        t = ht.kernel_seconds(ctx.trace_reduced, KERNEL, lo, hi)
        if not t:
            continue
        peaks = peaks or ctx.peaks()
        by_bytes = ss.moe_ffn_bytes(float(sp.stats["moe_experts_touched"]), model) \
            / peaks["hbm_bytes_per_s"]
        by_flops = ss.moe_ffn_flops(float(sp.stats["moe_assignments"]), model) \
            / peaks["bf16_flops_per_s"]
        bound.add("memory" if by_bytes >= by_flops else "compute")
        least += max(by_bytes, by_flops)
        spent += t
    if not spent:
        return None
    say(f"{KERNEL} (gated) in block forwards: {spent:.4f} s in the traced chunks on "
        f"chip 0, least {least:.4f} s; bound by {sorted(bound)}")
    return 100.0 * least / spent
