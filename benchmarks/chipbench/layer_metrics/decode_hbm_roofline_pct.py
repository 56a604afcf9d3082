"""A decode step's share of its memory roofline: the bytes a step has to read
(every weight once and the live keys and values, ``shapes.py``) over the
chip's peak bytes/s, over the device time of a decode step (the device-busy
time inside a ``decode_chunk`` program over the steps of a chunk, median)."""

from benchmarks.chipbench import shapes, trace_reduce as tr

NAME = "decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    red = ctx.trace_reduced
    if not ctx.on_tpu or not red or not red["devices"]:
        return None
    chunk_s = tr.median_program_busy_s(red, "decode_chunk")
    if not chunk_s:
        return None
    m, c = ctx.config["model"], ctx.result.counters
    need = shapes.decode_step_bytes(m["n_layer"], m["n_embd"], m["n_head"],
                                    m["vocab_size"], c["live_tokens_mean"])
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / (chunk_s / c["chunk_size"])
