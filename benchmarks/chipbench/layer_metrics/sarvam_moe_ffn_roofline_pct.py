"""The grouped expert kernel's share of its roofline in one-token decode
steps where an expert is CUT OVER ITS WIDTH (``ops/moe/grouped_ffn.py:
width_blocks``: sarvam's gated 4096 x 2048 experts run as two kernel calls a
layer, a half of every matrix each): the reading of
``granite_moe_ffn_roofline_pct`` (the least time for the touched experts'
bytes and the assignments' operations, from the functions the configuration
names under ``shapes``, over ALL the ``moe_grouped_ffn`` time inside the
chunk's execution: both halves) under the name this family's cell lists.
``None`` wherever that reader gives ``None``: a configuration that names no
``moe_ffn_bytes`` / ``moe_ffn_flops``, chunk spans without expert counts, no
such kernel, no chip. An earlier line gives the held experts touched and the
assignments a step (``moe_experts_touched_per_step``'s numbers, whose list an
accepted test holds to its cells)."""

from benchmarks.chipbench import hybrid_trace as ht
from benchmarks.chipbench import registry
from benchmarks.chipbench.harness import say

NAME = "sarvam_moe_ffn_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    chunks = [sp for sp, _ in ht.decode_chunks(ctx) if "moe_experts_touched" in sp.stats]
    steps = len(chunks) * ctx.result.counters.get("chunk_size", 0) if chunks else 0
    if steps:
        # ``moe_experts_touched_per_step``'s line: its list holds its cells
        say(f"expert layers, {len(chunks)} traced chunks: "
            f"{sum(float(sp.stats['moe_experts_touched']) for sp in chunks) / steps:.1f} "
            f"held experts touched a step, "
            f"{sum(float(sp.stats['moe_assignments']) for sp in chunks) / steps:.1f} "
            "assignments on them")
    dirs = ctx.dirs or registry.search_dirs(registry.load_benchmark())
    return registry.load_module("layer_metrics", "granite_moe_ffn_roofline_pct",
                                dirs).read(ctx)
