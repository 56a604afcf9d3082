"""The traced decode chunks of a model that generates by diffusion over
blocks, as pairs of the program's ``serving.decode_chunk`` span and the device
execution of ``decode_chunk`` it dispatched. A chunk of ten forwards runs for
a tenth of a second and a span covers its dispatch and its fetch, so the pair
of a span is the execution that OVERLAPS it most (and by more than half of its
own length): a trace whose host and device clocks are a millisecond apart
still pairs every chunk, where a pairing by "starts inside the span" loses
those whose execution seems to start before its dispatch. A program without
such spans or executions gives ``[]`` and every reader built on this ``None``.
"""

from typing import List, Tuple

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import trace_reduce as tr


def decode_chunks(ctx) -> List[Tuple[ps.Span, Tuple[float, float]]]:
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return []
    runs = tr.programs(red, "decode_chunk")
    out = []
    for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk"):
        if "forwards" not in sp.stats:
            continue
        over = [(min(e, sp.end) - max(s, sp.start), (s, e)) for s, e in runs]
        over = [(o, r) for o, r in over if o > 0.5 * (r[1] - r[0])]
        if over:
            out.append((sp, max(over)[1]))
    return out
