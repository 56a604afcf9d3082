"""Deliveries of a chunk's tokens to a stream that a prefill of ANOTHER
request held up (one ran since the stream's previous delivery or its first
token), as a share of all deliveries: 100 x sum ``stalled_deliveries`` / sum
``deliveries`` over the traced window's ``serving.decode_chunk`` spans. It is
the mode the delivery gap's 90th percentile sits in. An earlier line gives the
same share from the program's registry counters over the whole process."""

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "delivery_stalled_pct"
UNIT = "%"
LAYER = "serve scheduler"
MOVES = "delivery_gap_p90_ms"
KINDS = ("serve_closed",)


def read(ctx):
    chunks = ps.named(ps.in_window(ctx), "serving.decode_chunk")
    deliveries = ps.total(chunks, "deliveries")
    if not deliveries:
        return None
    stalled = ps.total(chunks, "stalled_deliveries")
    reg = ps.registry_totals()
    if reg.get("deliveries_total"):
        say(f"registry, whole process: {reg['deliveries_stalled_total']:.0f} of "
            f"{reg['deliveries_total']:.0f} deliveries stalled: "
            f"{100.0 * reg['deliveries_stalled_total'] / reg['deliveries_total']:.2f} %")
    say(f"spans, traced window: {stalled:.0f} of {deliveries:.0f} deliveries in "
        f"{len(chunks)} chunks waited on another request's prefill")
    return 100.0 * stalled / deliveries
