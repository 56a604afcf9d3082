"""Device milliseconds a decode step spends on the recurrent state of the
state-space layers. The update stays in XLA (fusions with no name of their
own), so its ops are found by what they touch: every op inside the traced
``decode_chunk`` executions whose HLO text names the state's type,
``f32[slots, heads, head_dim, state]``, as its result or an operand (the
update, the read-out against C, and any copy of the state the loop makes).
No roofline share is given for it: a fusion's bytes are the compiler's."""

from benchmarks.chipbench import hybrid_trace as ht
from benchmarks.chipbench.harness import say

NAME = "ssm_decode_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    model = ctx.config["model"]
    if "mamba_num_heads" not in model or not ctx.trace_path:
        return None
    state = "f32[{},{},{},{}]".format(
        int(ctx.config["serve"]["slots"]), int(model["mamba_num_heads"]),
        int(model["mamba_head_dim"]), int(model["ssm_state_size"]))
    chunks = ht.decode_chunks(ctx)
    spent = sum(ht.seconds_of_ops_mentioning(ctx.trace_path, state, lo, hi)
                for _, (lo, hi) in chunks)
    if not spent:
        return None
    steps = len(chunks) * ctx.result.counters["chunk_size"]
    n_layers = model["hybrid_override_pattern"].count("M")
    say(f"ops on {state} inside {len(chunks)} decode chunks: {spent:.4f} s, "
        f"{spent / steps / max(1, n_layers) * 1e3:.4f} ms a layer a step")
    return spent / steps * 1e3
