"""Device time by the program's declared scopes, from the op metadata a
profiler trace already holds.

``deepspeed_tpu/observability`` names regions INSIDE its compiled programs with
``observability.scope(name)``, a ``jax.named_scope("ds." + name)``: the name
lands on jax's name stack and so in every op's ``op_name``. In the raw
``.xplane.pb`` each device op has one **event metadata** record on its
``/device:TPU:<n>`` plane (``XPlane.event_metadata``, keyed by id, named by the
op's HLO text, the name ``trace_reduce`` already splits) with the stats
``tf_op`` (that name stack: ``jit(train_step)/while/body/closed_call/
transpose(jvp(GPT2))/.../ds.mlp.up/c_fc/dot_general:``), ``hlo_category``,
``flops``, ``model_flops`` and ``bytes_accessed``. ``jax.profiler.ProfileData``
shows an event's times and name but not these, so the two maps of each device
plane are read here from the file's wire format (the lines, which are the bulk
of the file, are skipped by their length prefix; no new dependency), once a
trace, and joined to ``ProfileData``'s events by name within the plane.

An op belongs to the INNERMOST declared scope of its ``tf_op``; a fusion
carries the metadata XLA gave it (its root's). ``while/body/dynamic_slice`` and
``dynamic_update_slice`` with no scope are a scan's stack of saved activations,
which jax slices outside any function of the program: ``act.stack``. An op
with no declared scope is ``unscoped:<last two segments of its tf_op>``,
``unscoped:(argument)`` where its ``tf_op`` is a program argument's name (the
compiler's copy of it), or ``unscoped:(<hlo category>)`` where it has no
``tf_op`` at all. The phase is
jax's: ``rematted_computation`` recomputed, else ``transpose(`` backward, else
forward. Containers are left out as ``trace_reduce.CONTAINER`` leaves them out,
so a table's sum is the program's device-busy time.

A program without scopes (a commit before they existed) resolves nothing and
every reader built on this returns ``None``.
"""

import bisect
import functools
import gzip
import re
import statistics
import struct
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import trace_reduce as tr

SCOPE = re.compile(r"(?:^|[/(])ds\.([a-z0-9_]+(?:\.[a-z0-9_]+)*)")
ACT_STACK = re.compile(r"while/body/dynamic_(update_)?slice:?$")
ARGUMENT = re.compile(r"^[\w.]+(\[[^/]*\])+$")      # ``caches[7]['k']``: no name stack
DERIVED = "act.stack"
UNSCOPED = "unscoped:"


class OpMeta(NamedTuple):
    tf_op: str
    category: str
    flops: float
    model_flops: float
    bytes_accessed: float


class Op(NamedTuple):
    start: float            # seconds on the trace's clock
    end: float
    scope: str
    phase: str
    flops: float            # the compiler's count for one execution of the op
    model_flops: float
    bytes_accessed: float
    name: str               # the HLO op's name, ``fusion.408``
    prim: str               # the last segment of its ``tf_op``, ``dot_general``


# ------------------------------------------------------------ the wire format
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: a varint's value,
    or the bytes of a length-delimited or fixed field (as a memoryview)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    """``(key, value bytes)`` of one entry of a protobuf map."""
    key = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _stat(buf, stat_names: Dict[int, str]):
    """An ``XStat`` as ``(its name, its value)``; a ``ref_value`` is the name
    of the stat metadata it points at."""
    name = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v)
        elif field == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif field in (3, 4):
            value = v
        elif field == 5:
            value = _text(v)
        elif field == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane_metadata(buf) -> Tuple[str, Dict[str, OpMeta]]:
    """A plane's name and its event metadata by the events' name (``XPlane``:
    name 2, lines 3, event_metadata 4, stat_metadata 5; ``XEventMetadata``:
    name 2, stats 5; ``XStatMetadata``: name 2)."""
    name, events, stat_names = "", [], {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 4:
            events.append(_map_entry(v)[1])
        elif field == 5:
            key, value = _map_entry(v)
            stat_names[key] = next((_text(x) for f, _, x in _fields(value) if f == 2), "")
    if not tr.DEVICE_PLANE.match(name):
        return name, {}
    out = {}
    for ev in events:
        ev_name, stats = "", {}
        for field, _, v in _fields(ev):
            if field == 2:
                ev_name = _text(v)
            elif field == 5:
                key, value = _stat(v, stat_names)
                stats[key] = value
        out[ev_name] = OpMeta(
            str(stats.get("tf_op") or ""), str(stats.get("hlo_category") or ""),
            *(float(stats.get(k) or 0.0) for k in ("flops", "model_flops", "bytes_accessed")))
    return name, out


@functools.lru_cache(maxsize=2)
def metadata(path: str) -> Dict[int, Dict[str, OpMeta]]:
    """``{chip: {event name: OpMeta}}`` of a trace's device planes."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            raw = f.read()
    else:
        with open(path, "rb") as f:
            raw = f.read()
    out = {}
    for field, wire, v in _fields(memoryview(raw)):
        if field == 1 and wire == 2:                   # XSpace.planes
            name, meta = _plane_metadata(v)
            m = tr.DEVICE_PLANE.match(name)
            if m:
                out[int(m.group(1))] = meta
    return out


# ------------------------------------------------------------------ resolving
def resolve(meta: Optional[OpMeta]) -> Tuple[str, str]:
    """``(scope, phase)`` of an op by its metadata."""
    tf_op = meta.tf_op if meta else ""
    phase = ("recomputed" if "rematted_computation" in tf_op else
             "backward" if "transpose(" in tf_op else "forward")
    found = SCOPE.findall(tf_op)
    if found:
        return found[-1], phase
    if ACT_STACK.search(tf_op):
        return DERIVED, phase
    if ARGUMENT.match(tf_op):          # the compiler's copy of a program argument
        return UNSCOPED + "(argument)", phase
    if tf_op:
        return UNSCOPED + "/".join(tf_op.rstrip(":").split("/")[-2:]), phase
    return f"{UNSCOPED}({meta.category if meta and meta.category else 'no metadata'})", phase


@functools.lru_cache(maxsize=2)
def _events(path: str, chip: int) -> Tuple[List[Op], List[tr.Interval]]:
    meta = metadata(path).get(chip, {})
    resolved: Dict[str, tuple] = {}
    nothing = OpMeta("", "", 0.0, 0.0, 0.0)
    out, whiles = [], []
    for plane in tr.load(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != chip:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                text = ev.name
                hit = resolved.get(text)
                if hit is None:
                    name = tr.op_name(text)
                    if tr.CONTAINER.match(name):
                        hit = ()
                    else:
                        mt = meta.get(text) or nothing
                        prim = mt.tf_op.rstrip(":").rsplit("/", 1)[-1] or f"({mt.category})"
                        hit = (name,) + resolve(mt) + (mt, prim)
                    resolved[text] = hit
                s = ev.start_ns * 1e-9
                if hit:
                    name, scope, phase, mt, prim = hit
                    out.append(Op(s, s + ev.duration_ns * 1e-9, scope, phase, mt.flops,
                                  mt.model_flops, mt.bytes_accessed, name, prim))
                elif text.startswith("%while"):
                    whiles.append((s, s + ev.duration_ns * 1e-9))
    out.sort(key=lambda op: op.start)
    return out, sorted(whiles)


def ops(path: str, chip: int = 0) -> List[Op]:
    """One chip's ``XLA Ops`` events without containers (the events of
    ``trace_reduce.reduce_trace``'s ``ops`` list, by start time), each with its
    scope, phase and the compiler's counts."""
    return _events(path, chip)[0]


def outside_the_loop(path: str, runs: Sequence[tr.Interval]) -> float:
    """Device seconds of the ops inside ``runs`` (executions of a program
    that is one loop with a head and a tail, as a decode chunk is) that lie
    outside the execution's longest ``while``: what the program does once an
    execution, whatever name the compiler gave it."""
    all_ops, whiles = _events(path, 0)
    runs = sorted(runs)
    loops = []
    for lo, hi in runs:
        mine = [w for w in whiles if w[0] >= lo and w[1] <= hi]
        loops.append(max(mine, key=lambda w: w[1] - w[0]) if mine else (hi, hi))
    starts = [lo for lo, _ in runs]
    total = 0.0
    for op in inside(all_ops, runs):
        a, b = loops[bisect.bisect_right(starts, op.start) - 1]
        if op.end <= a or op.start >= b:
            total += op.end - op.start
    return total


# ---------------------------------------------------------------- aggregation
class Table(NamedTuple):
    program: str
    runs: int                                   # whole executions in the window
    steps: float                                # steps those executions ran
    rows: Dict[Tuple[str, str], List[float]]    # (scope, phase) -> [s, calls, bytes, model flops]
    tails: Dict[Tuple[str, str, str], float]    # (scope, phase, primitive or op) -> s

    def seconds(self, *scopes: str, phase: Optional[str] = None) -> float:
        return sum(v[0] for (sc, ph), v in self.rows.items()
                   if sc in scopes and (phase is None or ph == phase))

    def total(self) -> float:
        return sum(v[0] for v in self.rows.values())

    def scoped(self) -> float:
        """Seconds in a named region: a declared scope, or ``act.stack``."""
        return sum(v[0] for (sc, _), v in self.rows.items()
                   if not sc.startswith(UNSCOPED))

    def declared(self) -> float:
        """Seconds in a scope the PROGRAM declared (0 for a program from
        before the scopes, whose ``act.stack`` the reader still names)."""
        return sum(v[0] for (sc, _), v in self.rows.items()
                   if not sc.startswith(UNSCOPED) and sc != DERIVED)


def whole_runs(red: dict, program: str, all_ops: Sequence[Op]) -> Tuple[tr.Interval, ...]:
    """The executions of ``program`` that lie wholly inside the window AND
    whose ops the trace holds. A trace that opens while a step runs holds that
    step's event, from where it opens or whole, with only the ops after that
    or none (``tr.programs`` takes it for a whole execution when the window
    opened before it): so an execution is kept only if it is as long as the
    others and holds as many op events (each within 2 % of the median)."""
    runs = sorted(tr.programs(red, program))
    if len(runs) < 3:
        return tuple(runs)
    starts = [s for s, _ in runs]
    held = [0] * len(runs)
    for op in inside(all_ops, runs):
        held[bisect.bisect_right(starts, op.start) - 1] += 1
    long, many = (statistics.median(e - s for s, e in runs), statistics.median(held))
    return tuple(run for run, n in zip(runs, held)
                 if run[1] - run[0] >= 0.98 * long and n >= 0.98 * many)


def inside(all_ops: Sequence[Op], runs: Sequence[tr.Interval]) -> List[Op]:
    """The ops that lie wholly inside one of the (disjoint) intervals."""
    runs = sorted(runs)
    starts = [s for s, _ in runs]
    out = []
    for op in all_ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= runs[i][1]:
            out.append(op)
    return out


def span_steps(ctx, runs: int) -> Optional[float]:
    """Steps the window's decode chunks ran, as the program's
    ``serving.decode_chunk`` spans say: ``forwards`` where a span has it (a
    model that generates by blocks), else ``slot_steps_run / active_slots``
    (``chunk`` is the chunk's index). Sum over sum; where the window holds
    another number of spans than of whole executions (one of the two cut by
    its edge), the spans' mean times the executions."""
    per = []
    for sp in ps.named(ps.in_window(ctx), "serving.decode_chunk"):
        if "forwards" in sp.stats:
            per.append(float(sp.stats["forwards"]))
        elif float(sp.stats.get("active_slots") or 0) > 0:
            per.append(float(sp.stats["slot_steps_run"]) / float(sp.stats["active_slots"]))
    if not per or not runs:
        return None
    return sum(per) if len(per) == runs else runs * sum(per) / len(per)


@functools.lru_cache(maxsize=4)
def _table(path: str, program: str, runs: Tuple[tr.Interval, ...],
           steps: float) -> Table:
    rows: Dict[Tuple[str, str], List[float]] = {}
    tails: Dict[Tuple[str, str], float] = {}
    for op in inside(ops(path), runs):
        row = rows.setdefault((op.scope, op.phase), [0.0, 0, 0.0, 0.0])
        row[0] += op.end - op.start
        row[1] += 1
        row[2] += op.bytes_accessed
        row[3] += op.model_flops
        # a declared row says which primitive holds most of it, an unscoped
        # one (whose name ends in its primitive) which HLO op
        key = (op.scope, op.phase, op.name if op.scope.startswith(UNSCOPED) else op.prim)
        tails[key] = tails.get(key, 0.0) + op.end - op.start
    return Table(program, len(runs), steps, rows, tails)


def table(ctx, program: str) -> Optional[Table]:
    """Device time of the whole executions of ``program`` inside the traced
    window on chip 0, by scope and phase; ``None`` where the run was not
    traced, the window holds no whole execution, or no op of them has a
    declared scope (a program from before the scopes)."""
    red = ctx.trace_reduced
    if not ctx.trace_path or not red or not red["devices"]:
        return None
    runs = whole_runs(red, program, ops(ctx.trace_path))
    steps = float(len(runs)) if program == "train_step" else span_steps(ctx, len(runs))
    if not runs or not steps:
        return None
    t = _table(ctx.trace_path, program, runs, steps)
    return t if t.declared() > 0 else None


def ms_per_step(t: Table, *scopes: str, phase: Optional[str] = None) -> float:
    return t.seconds(*scopes, phase=phase) / t.steps * 1e3


def say_table(t: Table) -> None:
    """Print the table: rows scope x phase by time, ms a step (and ms a chunk
    for a serving program), calls a step, the trace's own ``bytes_accessed``
    and ``model_flops`` over the time, and the ten largest ``unscoped:`` rows
    with the op that holds most of each."""
    from benchmarks.chipbench.harness import say
    serve = t.program != "train_step"
    total = t.total()
    say(f"device time of {t.runs} whole {t.program} executions ({t.steps:g} steps) by "
        f"declared scope: {total:.4f} s = {total / t.steps * 1e3:.3f} ms a step, "
        f"{100.0 * t.scoped() / total:.2f} % of it in a declared scope or {DERIVED}")
    say(f"  {'scope':<44}{'phase':<11}{'ms/step':>9}" + (f"{'ms/chunk':>10}" if serve else "")
        + f"{'calls/step':>11}{'GB/s':>8}{'TFLOP/s':>9}{'share %':>8}  most of it in")

    def line(scope, phase, row):
        s, calls, nbytes, flops = row
        return (f"  {scope[:43]:<44}{phase:<11}{s / t.steps * 1e3:>9.3f}"
                + (f"{s / t.runs * 1e3:>10.3f}" if serve else "")
                + f"{calls / t.steps:>11.1f}{nbytes / s / 1e9 if s else 0.0:>8.0f}"
                  f"{flops / s / 1e12 if s else 0.0:>9.1f}{100.0 * s / total:>8.2f}")

    def most(scope, phase):
        s, name = max((s, n) for (sc, ph, n), s in t.tails.items()
                      if (sc, ph) == (scope, phase))
        return f"  {name} {100.0 * s / t.rows[(scope, phase)][0]:.0f} %"

    by_time = sorted(t.rows.items(), key=lambda kv: -kv[1][0])
    for (scope, phase), row in by_time:
        if not scope.startswith(UNSCOPED):
            say(line(scope, phase, row) + most(scope, phase))
    loose = [kv for kv in by_time if kv[0][0].startswith(UNSCOPED)]
    for (scope, phase), row in loose[:10]:
        say(line(scope, phase, row) + most(scope, phase))
    if loose[10:]:
        rest = sum(v[0] for _, v in loose[10:])
        say(f"  {len(loose) - 10} more unscoped rows: {rest / t.steps * 1e3:.3f} ms a step")


def scoped_pct(ctx, program: str) -> Optional[float]:
    """The ``*_scoped_pct`` readers: print the run's table and what reading
    it cost (the metadata parse and the join happen here, once a trace), and
    return the share of the program's device time in a declared scope."""
    from benchmarks.chipbench.harness import say
    t0 = time.monotonic()
    t = table(ctx, program)
    if t is None:
        return None
    say_table(t)
    say(f"device scopes: read and printed in {time.monotonic() - t0:.2f} s after the "
        f"window ({len(metadata(ctx.trace_path).get(0, {}))} metadata records, "
        f"{len(ops(ctx.trace_path))} op events)")
    return 100.0 * t.scoped() / t.total()
