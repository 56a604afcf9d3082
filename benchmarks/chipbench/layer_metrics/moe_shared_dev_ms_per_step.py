"""Device milliseconds a decode step spends in the scope ``moe.shared``, over
all expert layers: the gated shared expert beside gated experts (three
matmuls on the layer's own normed input), or in the latent mixture the
projections to and from the latent space and its shared expert. ``None`` for
a program that opens no such scope.

It counts the ops UNDER the scope, which are the matmuls alone. Where the
compiler fetches their weights ahead by asynchronous copies of the decode
loop, those copies carry no scope and their time is not here: on the chip
granite-4.0-h-small's shared expert read 0.081 ms a step (PERF.md section 6,
PR 53) where its 377 MB of weights take at least 0.46 ms at 819 GB/s. So
this is NOT the layer's cost and would not move with it; it is not proposed
for ``BENCHMARK.json`` as it stands (PERF.md section 7 (m)): a reader of the
shared expert has to count the copies of its three matrices too."""

from benchmarks.chipbench import device_scopes as ds

NAME = "moe_shared_dev_ms_per_step"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)
SCOPE = "moe.shared"


def read(ctx):
    t = ds.table(ctx, "decode_chunk")
    if t is None or not t.seconds(SCOPE):
        return None
    return ds.ms_per_step(t, SCOPE)
