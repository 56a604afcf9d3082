"""A decode step's share of its memory roofline, the step's bytes taken from
the function the CONFIGURATION names (``shapes.decode_step_bytes(model, slots,
live tokens, experts touched a step)``, resolved as ``model_builder`` is; for
a latent-attention mixture: every parameter beside the routed experts, each
touched expert once, every live latent row as stored) over the chip's peak
bytes/s, over the device time of a decode step (device-busy time inside a
``decode_chunk`` execution over its steps, median): the share of the WHOLE
step. The touched experts are the traced chunks' own count. ``None`` for a
configuration that names no such function, a program without the scope
``attn.latent`` or one whose chunk spans carry no expert counts."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import registry
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "latent_decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "attn.latent"


def read(ctx):
    red, names = ctx.trace_reduced, ctx.config.get("shapes") or {}
    if not ctx.on_tpu or not red or not red["devices"] \
            or "decode_step_bytes" not in names:
        return None
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    chunks = [sp for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk")
              if "moe_experts_touched" in sp.stats]
    chunk_s = tr.median_program_busy_s(red, "decode_chunk")
    if not chunks or not chunk_s:
        return None
    c = ctx.result.counters
    touched = ps.total(chunks, "moe_experts_touched") / (len(chunks) * c["chunk_size"])
    slots = int(ctx.config["serve"]["slots"])
    step_bytes = registry.resolve(names["decode_step_bytes"])
    need = step_bytes(ctx.config["model"], slots, c["live_tokens_mean"], touched)
    no_experts = step_bytes(ctx.config["model"], slots, c["live_tokens_mean"], 0.0)
    no_rows = step_bytes(ctx.config["model"], slots, 0.0, touched)
    say(f"a decode step has to move {need / 1e9:.3f} GB ({names['decode_step_bytes']}): "
        f"{(need - no_experts) / 1e9:.3f} in {touched:.1f} touched experts, "
        f"{(need - no_rows) / 1e9:.3f} in {c['live_tokens_mean']:.0f} live latent rows "
        f"a layer, {(no_experts + no_rows - need) / 1e9:.3f} beside them")
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (chunk_s / c["chunk_size"])
