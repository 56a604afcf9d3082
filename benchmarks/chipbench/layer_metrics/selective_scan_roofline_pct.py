"""The selective-scan kernel's share of its roofline in a prefill: per traced
``prefill`` execution the bytes and operations of the prompt bucket's scans,
all Mamba-1 layers (``shapes.scan_bytes(tokens, model)`` and
``shapes.scan_flops(tokens, model)``, the functions the configuration names:
``x`` and ``dt`` read and ``y`` written, ``B`` and ``C`` read, float32; the
bucket's positions, which the kernel all walks), the larger of bytes over peak
bytes/s and operations over peak FLOP/s, over the trace time of
``selective_scan`` inside that execution. The recurrence is elementwise
(no matmul) and sequential in time, so the MXU's peak is a bound it cannot
reach; the line says which of the two bounds. ``None`` for a configuration
that names no ``scan_bytes``, a window without a whole prefill, a prefill
without the kernel, no chip."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import hybrid_trace as ht
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import registry
from benchmarks.chipbench.harness import say

NAME = "selective_scan_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p50_ms"
KINDS = ("serve_closed",)
KERNEL = "selective_scan"
SKEW_S = 2e-3


def read(ctx):
    names = ctx.config.get("shapes") or {}
    red = ctx.trace_reduced
    if not ctx.on_tpu or "scan_bytes" not in names or "scan_flops" not in names \
            or not ctx.trace_path or not red or not red["devices"]:
        return None
    nbytes = registry.resolve(names["scan_bytes"])
    flops = registry.resolve(names["scan_flops"])
    peaks = ctx.peaks()
    runs = ds.whole_runs(red, "prefill", ds.ops(ctx.trace_path))
    spent = by_bytes = by_flops = 0.0
    paired = 0
    for sp in ps.named(ps.in_window(ctx), "serving.prefill"):
        # host and device planes may lie a millisecond apart (PERF.md section
        # 6, PR 55); a prefill starts within half a millisecond of its span
        mine = [r for r in runs if sp.start - SKEW_S <= r[0] <= sp.end]
        t = ht.kernel_seconds(red, KERNEL, *mine[0]) if len(mine) == 1 else 0.0
        if t:
            paired += 1
            spent += t
            bucket = int(sp.stats["bucket"])
            by_bytes += nbytes(bucket, ctx.config["model"]) / peaks["hbm_bytes_per_s"]
            by_flops += flops(bucket, ctx.config["model"]) / peaks["bf16_flops_per_s"]
    if not spent:
        return None
    say(f"{KERNEL} ({names['scan_bytes']}) in {paired} of {len(runs)} whole prefills: "
        f"{spent:.4f} s on chip 0, least {by_bytes:.4f} s by bytes, {by_flops:.4f} s "
        "by operations")
    return 100.0 * max(by_bytes, by_flops) / spent
