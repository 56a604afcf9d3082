"""Device milliseconds a training step spends recomputing in the backward
pass what the forward pass had computed (ops under jax's
``rematted_computation``): what the remat policy still runs twice, which
``train_mfu_pct`` never counts. Earlier lines: by scope."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench.harness import say

NAME = "train_recompute_dev_ms"
UNIT = "ms"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    t = ds.table(ctx, "train_step")
    if t is None:
        return None
    rows = sorted(((v[0], sc) for (sc, ph), v in t.rows.items() if ph == "recomputed"),
                  reverse=True)
    if rows:
        say("recomputed, ms a step: " + ", ".join(
            f"{sc} {s / t.steps * 1e3:.3f}" for s, sc in rows))
    return sum(s for s, _ in rows) / t.steps * 1e3
