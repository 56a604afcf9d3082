"""Device milliseconds a decode step spends in the gated short convolutions,
over all their layers: the scopes ``sconv.in`` (the input projection and the
product ``B * u``), ``sconv.conv`` (the taps over the window and its roll)
and ``sconv.out`` (the gate and the output projection). Earlier lines: each.
``None`` for a program that opens no such scope."""

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench.harness import say

NAME = "shortconv_decode_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPES = ("sconv.in", "sconv.conv", "sconv.out")


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(*SCOPES):
        return None
    say("gated short convolutions, ms a step: " + ", ".join(
        f"{scope} {ds.ms_per_step(t, scope):.3f}" for scope in SCOPES))
    return ds.ms_per_step(t, *SCOPES)
