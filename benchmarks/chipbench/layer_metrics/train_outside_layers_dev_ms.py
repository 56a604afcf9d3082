"""Device milliseconds a training step spends outside its layers: the scopes
``embed``, ``head``, ``loss``, ``grad.norm_clip`` and ``optimizer.update``,
with ``param.cast`` and ``grad.accum`` (the compute-type copy of the
parameters before the scan and the gradients' accumulation after it). Earlier
lines: each scope's own."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench.harness import say

NAME = "train_outside_layers_dev_ms"
UNIT = "ms"
LAYER = "train engine"
MOVES = "train_tokens_per_s_per_chip"
KINDS = ("train",)
OUTSIDE = ("embed", "head", "loss", "param.cast", "grad.accum", "grad.norm_clip",
           "optimizer.update")


def read(ctx):
    t = ds.table(ctx, "train_step")
    if t is None:
        return None
    say("outside the layers, ms a step: " + ", ".join(
        f"{scope} {ds.ms_per_step(t, scope):.3f}" for scope in OUTSIDE))
    return ds.ms_per_step(t, *OUTSIDE)
