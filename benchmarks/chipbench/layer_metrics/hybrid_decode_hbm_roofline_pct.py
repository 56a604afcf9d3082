"""A hybrid decode step's share of its memory roofline: the bytes a step has
to move (``hybrid_shapes.decode_step_bytes``: every parameter beside the
routed experts, each touched expert once, the recurrent state of every slot
read and written, the live keys and values) over the chip's peak bytes/s, over
the device time of a decode step (device-busy time inside a ``decode_chunk``
execution over its steps, median). The touched experts are the traced chunks'
own count."""

from benchmarks.chipbench import hybrid_shapes as hs
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "hybrid_decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    if not ctx.on_tpu or not red or not red["devices"]:
        return None
    chunks = [sp for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk")
              if "moe_experts_touched" in sp.stats]
    chunk_s = tr.median_program_busy_s(red, "decode_chunk")
    if not chunks or not chunk_s:
        return None
    c, model = ctx.result.counters, ctx.config["model"]
    touched = ps.total(chunks, "moe_experts_touched") / (len(chunks) * c["chunk_size"])
    slots = int(ctx.config["serve"]["slots"])
    need = hs.decode_step_bytes(model, slots, touched, c["live_tokens_mean"])
    say(f"a decode step has to move {need / 1e9:.3f} GB: "
        f"{hs.params_beside_experts(model) * 2 / 1e9:.3f} beside the experts, "
        f"{hs.moe_ffn_bytes(touched, model) / 1e9:.3f} in {touched:.1f} touched experts, "
        f"{hs.ssm_step_bytes(slots, model) / 1e9:.3f} of recurrent state, "
        f"{c['live_tokens_mean'] * hs.kv_bytes_per_token(model) / 1e9:.4f} of keys and values")
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (chunk_s / c["chunk_size"])
