"""What the hybrid model's readers share: the traced decode chunks as pairs of
the program's ``serving.decode_chunk`` span (with its counts) and the device
execution of ``decode_chunk`` that started under it, and the device time of a
named kernel inside an execution. A program without such spans, counts or
kernels (a commit before they existed) gives empty lists, and every reader
built on this returns ``None``."""

import functools
from typing import List, Tuple

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import trace_reduce as tr


def decode_chunks(ctx) -> List[Tuple[ps.Span, Tuple[float, float]]]:
    """``[(host span, (start, end) of its device execution)]`` for every
    ``serving.decode_chunk`` span of the traced window whose program ran
    wholly inside the window."""
    red = ctx.trace_reduced
    if not red or not red["devices"]:
        return []
    runs = tr.programs(red, "decode_chunk")
    out = []
    for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk"):
        mine = [r for r in runs if sp.start <= r[0] <= sp.end]
        if len(mine) == 1:
            out.append((sp, mine[0]))
    return out


def kernel_seconds(red: dict, kernel: str, lo: float, hi: float) -> float:
    """Device seconds of the ops named ``kernel`` (``kernel.<n>`` too) that
    ran wholly inside ``[lo, hi]`` on chip 0."""
    return sum(e - s for name, s, e in red["devices"][0]["ops"]
               if tr.base_name(name) == kernel and s >= lo and e <= hi)


@functools.lru_cache(maxsize=2)
def ops_with_text(path: str) -> List[Tuple[str, float, float]]:
    """Chip 0's ``XLA Ops`` events with their WHOLE name, the op's HLO text
    (``%fusion.3 = f32[32,128,64,128]{...} fusion(...)``: result and operand
    types are in it), as ``[(text, start, end)]`` on the trace's clock.
    ``trace_reduce`` keeps the op's name alone."""
    out = []
    for plane in tr.load(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != 0:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    if not tr.CONTAINER.match(tr.op_name(ev.name)):
                        s = ev.start_ns * 1e-9
                        out.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def seconds_of_ops_mentioning(path: str, what: str, lo: float, hi: float) -> float:
    """Device seconds of the ops wholly inside ``[lo, hi]`` whose HLO text
    mentions ``what`` (a type such as ``f32[32,128,64,128]``), as a result or
    as an operand."""
    return sum(e - s for text, s, e in ops_with_text(path)
               if what in text and s >= lo and e <= hi)
