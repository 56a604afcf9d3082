"""The training step's matrix products against the chip's bf16 peak: the
least time ``6 x tokens x (12 L d^2 + d V)`` operations take
(``scope_shapes.py``: what ``train_mfu_pct`` counts, less attention) over the
device time a step spends in the scopes that hold them, ``attn.qkv``,
``attn.out``, ``mlp.up``, ``mlp.down`` and ``head``, all phases (their bias
adds and the copies XLA fuses with them included). Earlier lines: each scope's
own share, forward and backward, and the trace's own ``model_flops`` in these
scopes beside the count from shapes."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import scope_shapes as ss
from benchmarks.chipbench.harness import say

NAME = "train_matmul_roofline_pct"
UNIT = "%"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    t = ds.table(ctx, "train_step")
    if t is None or not ctx.on_tpu:
        return None
    spent = t.seconds(*ss.MATMUL_SCOPES)
    if not spent:
        return None
    m = ctx.config["model"]
    tokens = ctx.result.counters["tokens_per_step"] / ctx.chips * t.steps
    peak = ctx.peaks()["bf16_flops_per_s"]
    forward = ss.gpt2_forward_matmul_flops_per_token(m["n_layer"], m["n_embd"],
                                                     m["vocab_size"])
    for scope in ss.MATMUL_SCOPES:
        for phase, times in (("forward", 1.0), ("backward", 2.0)):
            s = t.seconds(scope, phase=phase)
            if s:
                say(f"{scope} {phase}: {s / t.steps * 1e3:.3f} ms a step, "
                    f"{100.0 * times * forward[scope] * tokens / peak / s:.1f} % of the peak")
    counted = sum(v[3] for (sc, _), v in t.rows.items() if sc in ss.MATMUL_SCOPES)
    need = ss.gpt2_train_matmul_flops_per_token(m["n_layer"], m["n_embd"],
                                                m["vocab_size"]) * tokens
    say(f"matrix products: {need / t.steps / 1e12:.3f} TFLOP a step from shapes "
        f"(the trace's own model_flops in these scopes: {counted / t.steps / 1e12:.3f}), "
        f"{need / peak / t.steps * 1e3:.2f} ms at the peak, "
        f"{spent / t.steps * 1e3:.2f} ms spent")
    return 100.0 * need / peak / spent
