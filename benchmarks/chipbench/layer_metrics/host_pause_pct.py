"""Share of the WHOLE measured window (51 s, not the traced 3) in which the
host stood still, as the program recorded it itself in
``get_tracer().pauses`` (``time.monotonic``, the window's own clock): 100 x
(the union of the ``host.gc`` intervals inside the window + what each
``host.stall`` ran OVER its typical time, less the collector's part of it)
over the window. A stall's time is its last ``ms - typical_ms``: a stalled
fetch is mostly the device's chunk, which is no pause. It is counted where it
lies inside the benchmark's ``step()`` spans: a turnaround runs from one
``step()`` into the next, and what the harness does between them (the
profiler's start and stop, most of all) is not the program's. Earlier lines:
every pause with its kind, generation or phase, milliseconds, seconds into
the window and what it fell in; collections by generation; stalls with the
collector's part of each."""

from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "host_pause_pct"
UNIT = "%"
LAYER = "serve scheduler"
MOVES = "tpot_mean_ms"
KINDS = ("serve_closed",)


def _meet(a, b):
    """The parts of the merged intervals ``a`` that lie inside ``b``."""
    return tr.subtract(a, tr.subtract(a, b))


def _where(ctx, stalls, a: float, b: float) -> str:
    mid = (a + b) / 2
    over = [n for n, s, e in ctx.spans if s <= mid <= e]
    place = over[-1] if over else "between steps"
    stall = next((p for p in stalls if p["t0"] <= mid <= p["t1"]), None)
    return place + (f", in a stalled {stall['attrs']['phase']}" if stall else "")


def read(ctx):
    from deepspeed_tpu.observability.trace import get_tracer
    tracer = get_tracer()
    pauses = getattr(tracer, "pauses", None)
    if pauses is None or ctx.result is None:
        return None         # a program that keeps no such list
    lo, hi = ctx.result.window
    inside = [p for p in pauses if p["t1"] > lo and p["t0"] < hi]
    gcs = [p for p in inside if p["name"] == "host.gc"]
    stalls = [p for p in inside if p["name"] == "host.stall"]
    steps = tr.union([(s, e) for n, s, e in ctx.spans if n == "chipbench.step"], lo, hi)
    paused = tr.length(tr.union([(p["t0"], p["t1"]) for p in gcs], lo, hi))
    for p in gcs:
        say(f"pause host.gc generation {p['attrs']['generation']}: "
            f"{(p['t1'] - p['t0']) * 1e3:.3f} ms, {p['t0'] - lo:.3f} s into the "
            f"window, collected {p['attrs']['collected']} "
            f"({_where(ctx, stalls, p['t0'], p['t1'])})")
    for p in stalls:
        at = p["attrs"]
        over_s = max(0.0, at["ms"] - at["typical_ms"]) * 1e-3
        mine = tr.union([(max(p["t0"], p["t1"] - over_s), p["t1"])], lo, hi)
        mine = _meet(mine, steps) if steps else mine
        own_s = max(0.0, tr.length(mine) - at["gc_ms"] * 1e-3)
        paused += own_s
        say(f"pause host.stall phase {at['phase']}: {at['ms']:.3f} ms where "
            f"{at['typical_ms']:.3f} is typical, {p['t0'] - lo:.3f} s into the "
            f"window, {at['gc_ms']:.3f} ms of it the collector's; of the "
            f"{over_s * 1e3:.3f} ms over, {tr.length(mine) * 1e3:.3f} lie inside "
            f"step() and {own_s * 1e3:.3f} are counted beside the collector's")
    by_gen = {}
    for p in gcs:
        g = by_gen.setdefault(p["attrs"]["generation"], [0, 0.0])
        g[0] += 1
        g[1] += (min(p["t1"], hi) - max(p["t0"], lo)) * 1e3
    window = hi - lo
    say(f"host pauses kept inside the {window:.3f} s window: collections "
        + (", ".join(f"generation {g}: {n} in {ms:.3f} ms"
                     for g, (n, ms) in sorted(by_gen.items())) or "none")
        + f" (a young one is kept from 1 ms on); stalls {len(stalls)} in "
        f"{sum(p['attrs']['ms'] for p in stalls):.3f} ms; "
        f"{getattr(tracer, 'pauses_dropped', 0)} pauses dropped by the process")
    if not ctx.trace_reduced or not ctx.trace_reduced["devices"]:
        return None         # a rehearsal: the lines above, no number
    return 100.0 * paused / window
