"""What every traffic kind's driver shares: the run's context, the benchmark's
own host spans, the profiler's switch, and the shape of a result."""

import contextlib
import glob
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


class Refused(RuntimeError):
    """The machine does not hold what the cell asks for: exit 2, no result."""


def say(msg: str) -> None:
    """An earlier line of the output (the result is the LAST line)."""
    print(msg, flush=True)


def seed31(seed: int, salt: int = 0) -> int:
    """A seed that a signed 32-bit PRNG key takes, from any whole number."""
    return (int(seed) * 1_000_003 + salt) % (2 ** 31 - 1)


@dataclass
class Result:
    """What a driver hands back. ``window`` is ``(start, end)`` of the measured
    window on ``time.monotonic()``; ``end_to_end`` maps a metric's name to its
    value; ``counters`` and ``spans`` are what the per-layer readers may read;
    ``counts_only`` names the metrics that are pure counts (a rehearsal may
    print those); ``reasons`` is empty exactly when the outputs are correct."""
    window: Tuple[float, float]
    attempted: int
    failed: int
    end_to_end: Dict[str, Optional[float]]
    counters: Dict[str, float] = field(default_factory=dict)
    reasons: List[str] = field(default_factory=list)
    counts_only: Tuple[str, ...] = ()


@dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    kind_name: str
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list
    probe: object
    t0: float
    trace_dir: str
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    trace_path: Optional[str] = None
    trace_reduced: Optional[dict] = None
    result: Optional[Result] = None
    tracing: bool = False
    dirs: List[str] = field(default_factory=list)   # where files are found by name
    memory_at_close: Optional[List[dict]] = None    # per chip, when the window closed

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def on_tpu(self) -> bool:
        return self.devices[0].platform == "tpu"

    def peaks(self) -> dict:
        from benchmarks.chipbench.peaks import peaks_for
        return peaks_for(self.devices[0].device_kind)

    def note_memory(self) -> None:
        """Keep each chip's memory statistics as the window closes: what the
        checks after it allocate (the float32 reference) is not the system's."""
        self.memory_at_close = [d.memory_stats() or {} for d in self.devices]

    def reference(self):
        """The plain reference the configuration names (``reference/<module>.py``
        under a directory of ``paths``) with its settings, or ``(None, {})``."""
        from benchmarks.chipbench import registry
        spec = dict(self.config.get("reference") or {})
        if not spec:
            return None, {}
        dirs = self.dirs or registry.search_dirs(registry.load_benchmark())
        return registry.load_module("reference", spec.pop("module"), dirs), spec

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the benchmark's own: kept on ``time.monotonic()``
        and, while the profiler runs, written into its trace too."""
        import jax
        t = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, t, time.monotonic()))

    # ------------------------------------------------------------ profiler
    def start_trace(self) -> None:
        """Arm ``jax.profiler`` (python's own tracer off: it would slow the
        host it is watching) and open the ``chipbench.window`` span."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("chipbench.window")
        self._window.__enter__()
        self.tracing = True

    def stop_trace(self) -> None:
        import jax
        if not self.tracing:
            return
        self.tracing = False
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.trace_path = found[0] if found else None

