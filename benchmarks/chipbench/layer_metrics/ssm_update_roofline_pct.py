"""The one-token state update's share of its memory roofline, for a Granite
hybrid: the bytes the update has to move a step
(``granite_shapes.ssm_update_bytes``: every slot's float32 recurrent state read
once and written once, all Mamba layers) over the chip's peak bytes/s, over the
device time a decode step spends in the scope ``ssm.update`` (``device_scopes``'
table of the traced ``decode_chunk`` executions). The update is an XLA fusion,
no Mosaic kernel: its ops are found by the scope the program declares around
it. Earlier lines: the scope's ms a step and a layer, and the bytes.
``None`` for a configuration without ``mamba_n_heads`` and for a program that
opens no such scope."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import granite_shapes as gs
from benchmarks.chipbench.harness import say

NAME = "ssm_update_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "ssm.update"


def read(ctx):
    model = ctx.config["model"]
    if not ctx.on_tpu or "mamba_n_heads" not in model:
        return None
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    slots = int(ctx.config["serve"]["slots"])
    need = gs.ssm_update_bytes(slots, model)
    ms = ds.ms_per_step(t, SCOPE)
    layers = gs.mixers(model).count("mamba")
    say(f"{SCOPE}: {ms:.3f} ms a step, {ms / layers:.4f} a layer ({layers} layers) "
        f"for {need / 1e9:.3f} GB: {slots} slots x "
        f"{gs.ssm_state_bytes_per_slot(model)} B of state read and written")
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (ms * 1e-3)
