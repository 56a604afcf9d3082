"""A decode step's share of its memory roofline, the step's bytes taken from
the function the CONFIGURATION names (``shapes.decode_step_bytes(model, slots,
live tokens, experts touched a step)``, resolved as ``model_builder`` is; for
a Granite hybrid with experts: every parameter beside the routed experts, each
touched expert once, the recurrent state and the windows of every slot read
and written, the live keys and values) over the chip's peak bytes/s, over the
device time of a decode step (device-busy time inside a ``decode_chunk``
execution over its steps, median): the share of the WHOLE step. The touched
experts are the traced chunks' own count. ``None`` for a configuration that
names no such function or a program whose chunk spans carry no expert counts."""

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import registry
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "granite_moe_decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red, names = ctx.trace_reduced, ctx.config.get("shapes") or {}
    if not ctx.on_tpu or not red or not red["devices"] \
            or "decode_step_bytes" not in names:
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
    none = step_bytes(ctx.config["model"], slots, c["live_tokens_mean"], 0.0)
    say(f"a decode step has to move {need / 1e9:.3f} GB ({names['decode_step_bytes']}): "
        f"{(need - none) / 1e9:.3f} in {touched:.1f} touched experts, "
        f"{none / 1e9:.3f} beside them")
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (chunk_s / c["chunk_size"])
