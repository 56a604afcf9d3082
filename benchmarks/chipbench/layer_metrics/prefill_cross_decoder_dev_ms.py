"""Device milliseconds of ONE prefill under the cross-decoder: the layers
after the one full-attention layer (gated memory units, cross-attention, their
feed-forwards), which a prefill that stops early runs at ONE position a
sequence (the span's ``positions_cross``), found by the layer names jax's name
stack carries (``layers_<i>`` from the configuration's ``shapes.cross_decoder_from``
on). A few milliseconds (their weights read once) while the stop holds; a
large share of the prefill if a change loses it. Earlier lines: ONE prefill's
device time by the program's declared scopes. ``None`` for a configuration
that names no ``cross_decoder_from``, a program whose ``serving.prefill`` spans
carry no ``positions_cross``, a window without a whole prefill, no chip."""

import re

from benchmarks.chipbench import device_scopes as ds
from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench import registry
from benchmarks.chipbench import trace_reduce as tr
from benchmarks.chipbench.harness import say

NAME = "prefill_cross_decoder_dev_ms"
UNIT = "ms"
LAYER = "compiled steps"
MOVES = "ttft_p50_ms"
KINDS = ("serve_closed",)
LAYER_NAME = re.compile(r"/layers_(\d+)/")


def layer_seconds(path: str, runs, first: int) -> float:
    """Device seconds on chip 0, inside ``runs``, of the ops whose name stack
    says ``layers_<i>`` with ``i >= first``."""
    meta = ds.metadata(path).get(0, {})
    runs = sorted(runs)
    total = 0.0
    for plane in tr.load(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != 0:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if tr.CONTAINER.match(tr.op_name(ev.name)):
                    continue
                found = LAYER_NAME.findall(getattr(meta.get(ev.name), "tf_op", ""))
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if found and int(found[-1]) >= first \
                        and any(lo <= s and e <= hi for lo, hi in runs):
                    total += e - s
    return total


def read(ctx):
    names = ctx.config.get("shapes") or {}
    red = ctx.trace_reduced
    if not ctx.on_tpu or "cross_decoder_from" not in names or not ctx.trace_path \
            or not red or not red["devices"]:
        return None
    spans = [sp for sp in ps.named(ps.in_window(ctx), "serving.prefill")
             if "positions_cross" in sp.stats]
    runs = ds.whole_runs(red, "prefill", ds.ops(ctx.trace_path))
    if not spans or not runs:
        return None
    first = int(registry.resolve(names["cross_decoder_from"])(ctx.config["model"]))
    t = ds._table(ctx.trace_path, "prefill", tuple(runs), float(len(runs)))
    if t.declared() > 0:
        ds.say_table(t)
    spent = layer_seconds(ctx.trace_path, runs, first)
    if not spent:
        return None
    say(f"the cross-decoder (layers_{first} on) in {len(runs)} whole prefills: "
        f"{spent / len(runs) * 1e3:.3f} ms a prefill of {t.total() / len(runs) * 1e3:.3f} "
        f"on chip 0, at {int(spans[0].stats['positions_cross'])} position a sequence "
        f"where the layers before it ran at {int(spans[0].stats['positions_self'])}")
    return spent / len(runs) * 1e3
