"""Decode steps that computed a token nobody asked for, as a share of all
decode steps run: 100 x (sum ``slot_steps_run`` - sum ``tokens_kept``) / sum
``slot_steps_run`` over the traced window's ``serving.decode_chunk`` spans
(a chunk runs all its steps for every active slot; a stream's last chunk
keeps only what was asked). An earlier line gives the same share from the
program's registry counters over the whole process, and what the traffic's
lengths alone would give."""

import math

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say
from benchmarks.chipbench.lengths import fixed_requests

NAME = "decode_wasted_step_pct"
UNIT = "%"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def read(ctx):
    chunks = ps.named(ps.in_window(ctx), "serving.decode_chunk")
    run = ps.total(chunks, "slot_steps_run")
    if not run:
        return None
    kept = ps.total(chunks, "tokens_kept")
    reg = ps.registry_totals()
    if reg.get("decode_slot_steps_total"):
        say(f"registry, whole process: {reg['decode_tokens_kept_total']:.0f} "
            f"tokens kept of {reg['decode_slot_steps_total']:.0f} slot-steps: "
            f"{100.0 * (1 - reg['decode_tokens_kept_total'] / reg['decode_slot_steps_total']):.2f} % wasted")
    k = int(ctx.config["serve"]["chunk_size"])
    outs = [int(o) for _, o in fixed_requests(ctx.traffic,
                                              int(ctx.config["serve"]["max_seq_len"]))]
    asked = sum(o - 1 for o in outs)
    steps = sum(k * math.ceil((o - 1) / k) for o in outs)
    say(f"spans, traced window: {kept:.0f} tokens kept of {run:.0f} slot-steps "
        f"in {len(chunks)} chunks; one cycle of the traffic's lengths asks "
        f"{asked} decode tokens in {steps} slot-steps: "
        f"{100.0 * (1 - asked / steps):.2f} % wasted")
    return 100.0 * (run - kept) / run
