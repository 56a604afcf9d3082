"""Device milliseconds a decode step spends in the grouped expert kernel
(``moe_grouped_ffn``, all expert layers): its time inside the traced
``decode_chunk`` executions over their steps."""

from benchmarks.chipbench import hybrid_trace as ht

NAME = "moe_decode_dev_ms_per_step"
UNIT = "ms"
LAYER = "kernels"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
KERNEL = "moe_grouped_ffn"


def read(ctx):
    chunks = ht.decode_chunks(ctx)
    spent = sum(ht.kernel_seconds(ctx.trace_reduced, KERNEL, lo, hi)
                for _, (lo, hi) in chunks)
    if not spent:
        return None
    return spent / (len(chunks) * ctx.result.counters["chunk_size"]) * 1e3
