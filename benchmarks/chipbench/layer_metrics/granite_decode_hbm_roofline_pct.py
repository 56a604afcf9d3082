"""A decode step's share of its memory roofline for a Granite hybrid: the
bytes a step has to move (``granite_shapes.decode_step_bytes``: every
parameter once, the recurrent state and the convolutions' windows of every
slot read and written, the live keys and values) over the chip's peak bytes/s,
over the device time of a decode step (device-busy time inside a
``decode_chunk`` execution over its steps, median): the share of the WHOLE
step, as ``lfm2_decode_hbm_roofline_pct`` is. ``None`` for a configuration
without ``mamba_n_heads`` and for a run without a device trace."""

from benchmarks.chipbench import granite_shapes as gs
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "granite_decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red, model = ctx.trace_reduced, ctx.config["model"]
    if not ctx.on_tpu or not red or not red["devices"] or "mamba_n_heads" not in model:
        return None
    chunk_s = tr.median_program_busy_s(red, "decode_chunk")
    c = ctx.result.counters
    if not chunk_s or "chunk_size" not in c:
        return None
    slots = int(ctx.config["serve"]["slots"])
    need = gs.decode_step_bytes(model, slots, c["live_tokens_mean"])
    say(f"a decode step has to move {need / 1e9:.3f} GB: "
        f"{gs.params(model) * 2 / 1e9:.3f} of parameters, "
        f"{gs.ssm_update_bytes(slots, model) / 1e9:.3f} of recurrent state, "
        f"{2 * slots * gs.conv_state_bytes_per_slot(model) / 1e9:.4f} of convolution "
        f"windows, {c['live_tokens_mean'] * gs.kv_bytes_per_token(model) / 1e9:.4f} of "
        "keys and values")
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (chunk_s / c["chunk_size"])
