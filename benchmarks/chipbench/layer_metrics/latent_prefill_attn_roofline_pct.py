"""The flash forward kernel's share of the MXU's peak in a latent-attention
model's EXPANDED prefill: per traced ``prefill`` execution the operations of
the prompt's causal attention AS PUBLISHED (``shapes.prefill_attn_flops(tokens,
model)``, the function the configuration names: queries and keys of 192 lanes,
values of 128, over the prompt's own ``tokens``; the program pads queries and
keys to 256 lanes and the prompt to its bucket, which is work it adds and no
operation of the model) over peak FLOP/s, over the trace time of ``flash_fwd``
inside that execution. Earlier lines: ONE prefill's device time by the
program's declared scopes (``ms/step`` there is milliseconds a prefill), which
no other reader prints. ``None`` for a configuration that names no
``prefill_attn_flops``, a window without a whole prefill, a prefill without
the kernel, no chip."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import hybrid_trace as ht
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import registry
from benchmarks.chipbench.harness import say

NAME = "latent_prefill_attn_roofline_pct"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p50_ms"
KINDS = ("serve_closed",)
KERNEL = "flash_fwd"
SKEW_S = 2e-3


def read(ctx):
    names = ctx.config.get("shapes") or {}
    red = ctx.trace_reduced
    if not ctx.on_tpu or "prefill_attn_flops" not in names or not ctx.trace_path \
            or not red or not red["devices"]:
        return None
    flops = registry.resolve(names["prefill_attn_flops"])
    runs = ds.whole_runs(red, "prefill", ds.ops(ctx.trace_path))
    spent = least = 0.0
    paired = 0
    for sp in ps.named(ps.in_window(ctx), "serving.prefill"):
        # a machine's first profiled process has its host plane up to a
        # millisecond ahead of its device plane (PERF.md section 6, PR 55), and
        # a prefill starts within half a millisecond of its span
        mine = [r for r in runs if sp.start - SKEW_S <= r[0] <= sp.end]
        t = ht.kernel_seconds(red, KERNEL, *mine[0]) if len(mine) == 1 else 0.0
        if t:
            paired += 1
            spent += t
            least += flops(int(sp.stats["tokens"]), ctx.config["model"]) \
                / ctx.peaks()["bf16_flops_per_s"]
    if not spent:
        return None
    t = ds._table(ctx.trace_path, "prefill", tuple(runs), float(len(runs)))
    if t.declared() > 0:
        ds.say_table(t)
    say(f"{KERNEL} ({names['prefill_attn_flops']}) in {paired} of {len(runs)} whole prefills: "
        f"{spent:.4f} s on chip 0, least {least:.4f} s by operations as published")
    return 100.0 * least / spent
