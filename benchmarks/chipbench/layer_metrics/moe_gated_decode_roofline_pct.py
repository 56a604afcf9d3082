"""The grouped expert kernel's share of its roofline in its GATED form (three
matrices an expert) in one-token decode steps: per traced chunk the least time
the chip could take for what its ``serving.decode_chunk`` span counted (the
larger of touched experts x an expert's bytes over peak bytes/s and
assignments x an expert's operations over peak FLOP/s, ``lfm2_shapes.py``),
over the trace time of ``moe_grouped_ffn`` inside that chunk's execution.
``None`` for a program whose chunk spans carry no expert counts, whose chunks
hold no such kernel, or whose configuration has no gated experts of its own
width (``moe_intermediate_size`` beside ``layer_types``)."""

from benchmarks.chipbench import hybrid_trace as ht
from benchmarks.chipbench import lfm2_shapes as ls
from benchmarks.chipbench.harness import say

NAME = "moe_gated_decode_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
KERNEL = "moe_grouped_ffn"


def read(ctx):
    model = ctx.config["model"]
    if not ctx.on_tpu or "layer_types" not in model or "moe_intermediate_size" not in model:
        return None
    peaks = None
    spent = least = 0.0
    bound = set()
    for sp, (lo, hi) in ht.decode_chunks(ctx):
        if "moe_experts_touched" not in sp.stats:
            continue
        t = ht.kernel_seconds(ctx.trace_reduced, KERNEL, lo, hi)
        if not t:
            continue
        peaks = peaks or ctx.peaks()
        by_bytes = ls.moe_ffn_bytes(float(sp.stats["moe_experts_touched"]), model) \
            / peaks["hbm_bytes_per_s"]
        by_flops = ls.moe_ffn_flops(float(sp.stats["moe_assignments"]), model) \
            / peaks["bf16_flops_per_s"]
        bound.add("memory" if by_bytes >= by_flops else "compute")
        least += max(by_bytes, by_flops)
        spent += t
    if not spent:
        return None
    say(f"{KERNEL} (gated, experts of {ls.expert_params(model) * 2 / 1e6:.2f} MB) in "
        f"decode: {spent:.4f} s in the traced chunks on chip 0, least {least:.4f} s; "
        f"bound by {sorted(bound)}")
    return 100.0 * least / spent
