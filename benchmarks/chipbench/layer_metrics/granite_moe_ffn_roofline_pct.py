"""The grouped expert kernel's share of its roofline in one-token decode
steps, its bytes and operations taken from the functions the CONFIGURATION
names (``shapes.moe_ffn_bytes(touched, assignments, model)`` and
``shapes.moe_ffn_flops(assignments, model)``, resolved as ``model_builder``
is): per traced chunk the least time the chip could take for what its
``serving.decode_chunk`` span counted (the larger of bytes over peak bytes/s
and operations over peak FLOP/s), over the trace time of ``moe_grouped_ffn``
inside that chunk's execution. ``None`` for a configuration that names no
such functions, a program whose chunk spans carry no expert counts, or whose
chunks hold no such kernel."""

from benchmarks.chipbench import hybrid_trace as ht
from benchmarks.chipbench import registry
from benchmarks.chipbench.harness import say

NAME = "granite_moe_ffn_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
KERNEL = "moe_grouped_ffn"


def read(ctx):
    names = ctx.config.get("shapes") or {}
    if not ctx.on_tpu or "moe_ffn_bytes" not in names or "moe_ffn_flops" not in names:
        return None
    model = ctx.config["model"]
    ffn_bytes = registry.resolve(names["moe_ffn_bytes"])
    ffn_flops = registry.resolve(names["moe_ffn_flops"])
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
        assignments = float(sp.stats["moe_assignments"])
        by_bytes = ffn_bytes(float(sp.stats["moe_experts_touched"]), assignments,
                             model) / peaks["hbm_bytes_per_s"]
        by_flops = ffn_flops(assignments, model) / peaks["bf16_flops_per_s"]
        bound.add("memory" if by_bytes >= by_flops else "compute")
        least += max(by_bytes, by_flops)
        spent += t
    if not spent:
        return None
    say(f"{KERNEL} ({names['moe_ffn_bytes']}) in decode: {spent:.4f} s in the traced "
        f"chunks on chip 0, least {least:.4f} s; bound by {sorted(bound)}")
    return 100.0 * least / spent
