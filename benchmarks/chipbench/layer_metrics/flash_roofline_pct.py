"""The flash kernels' share of their roofline: the least time the chip could
take for the calls of ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` in
the traced window (the larger of operations over peak FLOP/s and bytes over
peak bytes/s, per call, from ``shapes.py``) over the time the trace gives
them. A recomputed forward is a call like any other."""

from benchmarks.chipbench import shapes, trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "flash_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"] or not ctx.on_tpu:
        return None
    m, c = ctx.config["model"], ctx.result.counters
    batch_heads = c["micro_batch_per_chip"] * m["n_head"]
    seq, d_head = c["sequence_length"], m["n_embd"] // m["n_head"]
    peaks = ctx.peaks()
    lo, hi = red["window"]
    spent = least = 0.0
    bound = set()
    for name, s, e in red["devices"][0]["ops"]:
        kernel = tr.base_name(name)
        if kernel not in shapes.FLASH_MATMULS or s < lo or e > hi:
            continue
        by_flops = shapes.flash_call_flops(kernel, batch_heads, seq, d_head) \
            / peaks["bf16_flops_per_s"]
        by_bytes = shapes.flash_call_bytes(kernel, batch_heads, seq, d_head) \
            / peaks["hbm_bytes_per_s"]
        bound.add("compute" if by_flops >= by_bytes else "memory")
        least += max(by_flops, by_bytes)
        spent += e - s
    if spent <= 0.0:
        return None
    say(f"flash kernels: {spent:.4f} s in the traced window on chip 0, least "
        f"{least:.4f} s; bound by {sorted(bound)}")
    return 100.0 * least / spent
