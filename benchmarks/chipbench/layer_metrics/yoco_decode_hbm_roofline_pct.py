"""A decode step's share of its memory roofline, the step's bytes taken from
the function the CONFIGURATION names (``shapes.decode_step_bytes(model, slots,
live tokens)``, resolved as ``model_builder`` is; for a model whose layers
share ONE cache: every parameter once, the live rows of that cache once a
reading layer, every slot's rings read, its recurrent state read and written)
over the chip's peak bytes/s, over the device time of a decode step
(device-busy time inside a ``decode_chunk`` execution over its steps, median):
the share of the WHOLE step. ``None`` for a configuration that names no such
function or a program without the scope ``attn.shared``."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import registry
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "yoco_decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "attn.shared"


def read(ctx):
    red, names = ctx.trace_reduced, ctx.config.get("shapes") or {}
    if not ctx.on_tpu or not red or not red["devices"] \
            or "decode_step_bytes" not in names or "shared_attn_bytes" not in names:
        return None
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    chunk_s = tr.median_program_busy_s(red, "decode_chunk")
    if not chunk_s:
        return None
    c = ctx.result.counters
    slots, live = int(ctx.config["serve"]["slots"]), float(c["live_tokens_mean"])
    step_bytes = registry.resolve(names["decode_step_bytes"])
    need = step_bytes(ctx.config["model"], slots, live)
    no_rows = step_bytes(ctx.config["model"], slots, 0.0)
    say(f"a decode step has to move {need / 1e9:.3f} GB ({names['decode_step_bytes']}): "
        f"{(need - no_rows) / 1e9:.3f} in {live:.0f} live rows of the one cache read "
        f"by every layer that attends it, {no_rows / 1e9:.3f} beside them (weights, "
        "rings, recurrent state)")
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (chunk_s / c["chunk_size"])
