"""Compile seconds and persistent-cache traffic from jax's own monitoring
events, and the StableHLO modules it lowered (copied from ``chip_smoke.py:
Probe``; the benchmark keeps its own)."""

import glob
import os
import re
import time

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_KERNEL = re.compile(r'@tpu_custom_call\b.*?kernel_name\s*=\s*"([^"]*)"')
_MAIN_INT_ARG = re.compile(r"tensor<((?:\d+x){2,})i32>")


def union_seconds(spans, lo=None, hi=None) -> float:
    """Length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]``: an inner jit traced while an outer one traces reports both."""
    total, edge = 0.0, lo if lo is not None else float("-inf")
    for a, b in sorted(spans):
        if hi is not None:
            b = min(b, hi)
        a = max(a, edge)
        if b > a:
            total += b - a
            edge = b
    return total


class Probe:
    def __init__(self, dump_dir=None):
        import jax
        self.dump_dir = dump_dir
        self.spans = []             # (start, end) on time.monotonic()
        self.backend = []           # ends of backend compiles (cache misses too)
        self.hits = self.misses = 0
        self._seen = set()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:          # reported at its end
            now = time.monotonic()
            self.spans.append((now - secs, now))
            if event == BACKEND_COMPILE:
                self.backend.append(now)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def compile_seconds(self, lo, hi) -> float:
        return union_seconds([s for s in self.spans if s[1] > lo and s[0] < hi],
                             lo, hi)

    def compiles_between(self, lo, hi) -> int:
        """Programs compiled (or loaded from the cache) inside ``[lo, hi]``."""
        return sum(1 for t in self.backend if lo <= t <= hi)

    def lowered_between(self, lo, hi):
        """Names of the modules lowered inside ``[lo, hi]`` (``time.monotonic``),
        by the time their dump was written."""
        if not self.dump_dir:
            return []
        shift = time.time() - time.monotonic()
        out = []
        for path in sorted(glob.glob(os.path.join(self.dump_dir, "*.mlir"))):
            if lo <= os.path.getmtime(path) - shift <= hi:
                out.append(os.path.basename(path).split("_", 2)[2]
                           .rsplit("_compile", 1)[0])
        return out

    def new_modules(self):
        """``[(module name, StableHLO text)]`` lowered since the last call."""
        out = []
        if not self.dump_dir:
            return out
        for path in sorted(glob.glob(os.path.join(self.dump_dir, "*.mlir"))):
            if path in self._seen:
                continue
            self._seen.add(path)
            name = os.path.basename(path).split("_", 2)[2]
            name = name.rsplit("_compile", 1)[0].removeprefix("jit_")
            with open(path) as f:
                out.append((name, f.read()))
        return out


def kernel_names(text: str) -> dict:
    """``{Mosaic kernel name: call sites}`` of a lowered StableHLO module: a
    compiled Pallas kernel is a ``tpu_custom_call`` carrying ``kernel_name``
    (the rule of ``deepspeed_tpu/analysis/lowered.py``, kept here as well)."""
    counts = {}
    for name in _KERNEL.findall(text):
        counts[name] = counts.get(name, 0) + 1
    return counts


def first_int_arg_shape(text: str) -> str:
    """Shape of ``@main``'s first int32 argument of rank >= 2 (a serving
    prefill's padded prompt bucket, ``"1x128"``), or ``""``."""
    m = re.search(r"func\.func\s+public\s+@main\((.*?)\)\s*->", text, re.S)
    found = _MAIN_INT_ARG.findall(re.sub(r'"[^"]*"', '""', m.group(1))) if m else []
    return found[0].rstrip("x") if found else ""
