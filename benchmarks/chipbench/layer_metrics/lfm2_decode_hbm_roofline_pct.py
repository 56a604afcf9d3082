"""A decode step's share of its memory roofline for a model of gated short
convolutions, attention and gated experts: the bytes a step has to move
(``lfm2_shapes.decode_step_bytes``: every parameter beside the routed experts,
each touched expert once, the convolutions' windows of every slot read and
written, the live keys and values) over the chip's peak bytes/s, over the
device time of a decode step (device-busy time inside a ``decode_chunk``
execution over its steps, median): the share of the WHOLE step. The touched
experts are the traced chunks' own count. ``None`` for a configuration
without ``layer_types`` or a program whose chunk spans carry no expert
counts."""

from benchmarks.chipbench import lfm2_shapes as ls
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "lfm2_decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red, model = ctx.trace_reduced, ctx.config["model"]
    if not ctx.on_tpu or not red or not red["devices"] or "layer_types" not in model:
        return None
    chunks = [sp for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk")
              if "moe_experts_touched" in sp.stats]
    chunk_s = tr.median_program_busy_s(red, "decode_chunk")
    if not chunks or not chunk_s:
        return None
    c = ctx.result.counters
    touched = ps.total(chunks, "moe_experts_touched") / (len(chunks) * c["chunk_size"])
    slots = int(ctx.config["serve"]["slots"])
    need = ls.decode_step_bytes(model, slots, touched, c["live_tokens_mean"])
    say(f"a decode step has to move {need / 1e9:.3f} GB: "
        f"{ls.params_beside_experts(model) * 2 / 1e9:.3f} beside the experts, "
        f"{ls.moe_ffn_bytes(touched, model) / 1e9:.3f} in {touched:.1f} touched experts, "
        f"{2 * slots * ls.conv_state_bytes_per_slot(model) / 1e9:.4f} of convolution "
        f"windows, {c['live_tokens_mean'] * ls.kv_bytes_per_token(model) / 1e9:.4f} of "
        "keys and values")
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (chunk_s / c["chunk_size"])
