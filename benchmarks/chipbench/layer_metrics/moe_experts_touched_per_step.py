"""Distinct held experts a decode step reads, summed over the expert layers:
sum ``moe_experts_touched`` over the traced ``serving.decode_chunk`` spans
over their steps. Times an expert's bytes it is what the grouped expert kernel
must read a step. An earlier line gives the assignments a step and the
program's registry counters over the whole process."""

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "moe_experts_touched_per_step"
UNIT = "experts"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    chunks = [sp for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk")
              if "moe_experts_touched" in sp.stats]
    if not chunks:
        return None
    steps = len(chunks) * ctx.result.counters["chunk_size"]
    touched = ps.total(chunks, "moe_experts_touched")
    say(f"expert layers, traced window: {ps.total(chunks, 'moe_assignments') / steps:.1f} "
        f"assignments on held experts a step, {touched / steps:.1f} experts touched "
        f"a step, over {len(chunks)} chunks")
    from deepspeed_tpu.observability.metrics import get_registry
    snap = get_registry().snapshot()
    vals = {k: snap[k] for k in ("serving/moe_assignments_total",
                                 "serving/moe_experts_touched_total",
                                 "serving/ssm_state_bytes") if k in snap}
    say("registry, whole process: " + ", ".join(
        f"{k} {float(v['value'] if isinstance(v, dict) else v):.0f}"
        for k, v in vals.items()))
    return touched / steps
