"""Share of ``train_step``'s device time whose op resolves to a region with a
name: a scope the program declared (``observability/schema.py: SCOPES``) or
the scan's stack of saved activations (``device_scopes.py``). Prints the run's
table of device time by scope and phase, and what reading it cost."""

from benchmarks.chipbench import device_scopes as ds

NAME = "train_scoped_pct"
UNIT = "%"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)


def read(ctx):
    return ds.scoped_pct(ctx, "train_step")
