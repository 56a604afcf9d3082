"""Model FLOP/s utilisation of the traced steps: tokens per second per chip,
taken on the device's clock from the start of the first traced ``train_step``
to the start of the last (every gap between them counts), times the
operations a token needs (6 N + 12 L d s, copied in ``shapes.py``;
recomputation not counted) over the chip's bf16 peak."""

from benchmarks.chipbench import shapes, trace_reduce as tr

NAME = "train_mfu_pct"
UNIT = "%"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    red = ctx.trace_reduced
    if not red or not red["devices"] or not ctx.on_tpu:
        return None
    lo, hi = red["window"]
    starts = sorted(s for s, _ in tr.programs(red, "train_step", whole_only=False)
                    if lo <= s <= hi)
    if len(starts) < 2:
        return None
    c, m = ctx.result.counters, ctx.config["model"]
    rate = (len(starts) - 1) * c["tokens_per_step"] / (starts[-1] - starts[0]) / ctx.chips
    flops = shapes.gpt2_train_flops_per_token(
        m["n_layer"], m["n_embd"], m["vocab_size"],
        max(c["sequence_length"], m["n_positions"]), c["sequence_length"])
    return 100.0 * rate * flops / ctx.peaks()["bf16_flops_per_s"]
