"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

What a TPU trace holds (looked at by hand on a v5e, jax 0.9.0): one plane per
chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<name>(<fingerprint>)``), ``XLA Ops`` (one event
per executed HLO op, named by its HLO text ``%<op> = ...``; a ``while`` or
``conditional`` covers the ops of its body, so those containers are left out
of every sum) and ``Async XLA Ops`` (the span from an asynchronous op's start
to its done: copies, and collectives across chips). The host plane
``/host:CPU`` has a line per thread; ``jax.profiler.TraceAnnotation`` spans
land on the line of the thread that opened them. All of them share one clock,
nanoseconds from the start of the profile.

Times here are seconds on that clock. Nothing in this file reads the host's
clock or the program.
"""

import gzip
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # (start, end), seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)")
WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
SMALL_GAP_S = 20e-6


def op_name(event_name: str) -> str:
    """``%flash_fwd.13 = (bf16[...`` -> ``flash_fwd.13``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def base_name(name: str) -> str:
    """``flash_fwd.13`` -> ``flash_fwd``."""
    return re.sub(r"\.\d+$", "", name)


def program_name(module_event: str) -> str:
    """``jit_train_step(1479...)`` -> ``train_step``."""
    return module_event.split("(", 1)[0].removeprefix("jit_")


def load(path: str):
    """The trace as ``jax.profiler.ProfileData``; ``.gz`` is read in memory."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def union(intervals: Sequence[Interval], lo: Optional[float] = None,
          hi: Optional[float] = None) -> List[Interval]:
    """Merged, sorted intervals, clipped to ``[lo, hi]``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def subtract(merged_a: Sequence[Interval],
             merged_b: Sequence[Interval]) -> List[Interval]:
    """Parts of ``merged_a`` that no interval of ``merged_b`` covers."""
    out, j = [], 0
    for a, b in merged_a:
        cur = a
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < b:
            if merged_b[k][0] > cur:
                out.append((cur, merged_b[k][0]))
            cur = max(cur, merged_b[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def reduce_trace(path: str) -> dict:
    """Everything the metric readers need, as plain lists:

    ``window``: ``(start, end)`` of the ``chipbench.window`` span, or the
    extent of the device events where the trace has no such span;
    ``devices``: per chip ``ops`` ``[(name, start, end)]`` without containers,
    ``asyncs`` (the same for ``Async XLA Ops``) and ``programs``;
    ``host``: the benchmark's and the program's host spans
    ``[(name, start, end)]`` of every thread that opened a ``chipbench.`` span.
    """
    data = load(path)
    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": [], "asyncs": [], "programs": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name = op_name(ev.name)
                        if not CONTAINER.match(name):
                            s = ev.start_ns * 1e-9
                            dev["ops"].append((name, s, s + ev.duration_ns * 1e-9))
                elif line.name == "Async XLA Ops":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev["asyncs"].append((op_name(ev.name), s,
                                              s + ev.duration_ns * 1e-9))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev["programs"].append((program_name(ev.name), s,
                                                s + ev.duration_ns * 1e-9))
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans = [(ev.name, ev.start_ns * 1e-9,
                          (ev.start_ns + ev.duration_ns) * 1e-9)
                         for ev in line.events if not ev.name.startswith("$")]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in spans):
                    host.extend(spans)
    devices.sort(key=lambda d: d["id"])
    host.sort(key=lambda s: (s[1], -s[2]))
    window = next(((a, b) for n, a, b in host if n == WINDOW_SPAN), None)
    if window is None:
        edges = [(s, e) for d in devices for _, s, e in d["ops"]]
        window = (min(s for s, _ in edges), max(e for _, e in edges)) \
            if edges else (0.0, 0.0)
    return {"window": window, "devices": devices, "host": host}


def busy(dev: dict, lo: float, hi: float) -> List[Interval]:
    """Merged intervals in which an op ran on this chip inside ``[lo, hi]``."""
    return union([(s, e) for _, s, e in dev["ops"]], lo, hi)


def device_busy_s(red: dict) -> float:
    """Seconds in which an op ran, averaged over the chips of the trace."""
    lo, hi = red["window"]
    per = [length(busy(d, lo, hi)) for d in red["devices"]]
    return sum(per) / len(per) if per else 0.0


def window_s(red: dict) -> float:
    return red["window"][1] - red["window"][0]


def op_seconds(red: dict, group=base_name) -> Dict[str, float]:
    """Seconds per op name inside the window, averaged over the chips."""
    lo, hi = red["window"]
    out: Dict[str, float] = {}
    for d in red["devices"]:
        for name, s, e in d["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = group(name)
                out[key] = out.get(key, 0.0) + (e - s)
    n = max(1, len(red["devices"]))
    return {k: v / n for k, v in out.items()}


def programs(red: dict, name: str, device: int = 0,
             whole_only: bool = True) -> List[Interval]:
    """Executions of the program ``name`` on one chip; with ``whole_only``
    those that lie wholly inside the window."""
    lo, hi = red["window"]
    dev = red["devices"][device]
    return [(s, e) for n, s, e in dev["programs"]
            if n == name and (not whole_only or (s >= lo and e <= hi))]


def busy_inside(red: dict, spans: Sequence[Interval],
                device: int = 0) -> List[float]:
    """Device-busy seconds inside each of ``spans`` on one chip."""
    dev = red["devices"][device]
    return [length(busy(dev, s, e)) for s, e in spans]


def median_program_busy_s(red: dict, *names: str) -> Optional[float]:
    """Median device-busy seconds inside one execution of the programs
    ``names`` on chip 0; ``None`` where the window holds none."""
    import statistics
    runs = [iv for n in names for iv in programs(red, n)]
    return statistics.median(busy_inside(red, runs)) if runs else None


def idle_pct(red: Optional[dict]) -> Optional[float]:
    """Share of the traced window in which no op ran, averaged over the chips."""
    if not red or not red["devices"] or window_s(red) <= 0:
        return None
    return 100.0 * (1.0 - device_busy_s(red) / window_s(red))


def exposed_collective_s(red: dict) -> Tuple[float, float]:
    """``(seconds in collectives, seconds of them in which no compute op ran)``
    inside the window, averaged over the chips. A collective is an op or an
    asynchronous span whose name begins like one; compute is every other op."""
    lo, hi = red["window"]
    total = exposed = 0.0
    for d in red["devices"]:
        coll = union([(s, e) for n, s, e in d["ops"] + d["asyncs"]
                      if COLLECTIVE.match(n)], lo, hi)
        compute = union([(s, e) for n, s, e in d["ops"]
                         if not COLLECTIVE.match(n)], lo, hi)
        total += length(coll)
        exposed += length(subtract(coll, compute))
    n = max(1, len(red["devices"]))
    return total / n, exposed / n


def _covering(host: Sequence[Tuple[str, float, float]], a: float, b: float) -> str:
    """Name a gap by the host spans over its middle: the outermost
    ``chipbench.`` span, then the innermost span of any name under it."""
    mid = (a + b) / 2
    over = [(n, s, e) for n, s, e in host if s <= mid <= e]
    outer = next((n for n, _, _ in over if n.startswith(SPAN_PREFIX)
                  and n != WINDOW_SPAN), None)
    if outer is None:
        return "no_benchmark_span"
    inner = min(over, key=lambda x: x[2] - x[1])[0]
    inner = re.sub(r"[^A-Za-z0-9_.()-]", "_", inner)
    return outer if inner == outer else f"{outer}/{inner}"


def idle_gaps(red: dict, device: int = 0) -> Dict[str, float]:
    """Idle seconds of one chip inside the window, by what the host was doing:
    each gap between ops is named by the host span over its middle; gaps
    under 20 us are summed under one name."""
    lo, hi = red["window"]
    if not red["devices"]:
        return {}
    merged = busy(red["devices"][device], lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    out: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        key = "between_ops_under_20us" if b - a < SMALL_GAP_S \
            else _covering(red["host"], a, b)
        out[key] = out.get(key, 0.0) + (b - a)
    return out


def breakdown(red: dict, top: int = 10) -> dict:
    """The ten ops with most device time and the longest idle gaps by name."""
    ops = sorted(op_seconds(red, group=lambda n: n).items(),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(red).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
