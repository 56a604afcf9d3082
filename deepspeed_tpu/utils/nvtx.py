"""Profiler range annotation.

Behavioural equivalent of reference ``deepspeed/utils/nvtx.py`` (``instrument_w_nvtx``)
and the accelerator ``range_push/range_pop`` surface: on TPU the profiler is XLA's —
ranges become ``jax.profiler.TraceAnnotation`` named scopes, visible in TensorBoard's
trace viewer / Perfetto exactly where NVTX ranges land in Nsight.

Neither the program's host spans nor its device regions are made here:
``observability.trace.Tracer.span`` opens the ``TraceAnnotation`` of a host
region itself, with the span's attributes, and ``observability.scope`` names
the ops inside a compiled program, each under a name declared once in
``observability/schema.py``. What stays here is the reference's decorator and
accelerator surface, :func:`instrument_w_nvtx` and :func:`range_push` /
:func:`range_pop`: no-ops cheap enough for hot paths when no profiler is
capturing (``TraceMe`` checks an atomic).
"""

import functools
import threading
from typing import Callable

import jax


def instrument_w_nvtx(func: Callable) -> Callable:
    """Decorate ``func`` so its execution appears as a named range in profiler traces
    (name kept for reference source compatibility)."""

    @functools.wraps(func)
    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(func.__qualname__):
            return func(*args, **kwargs)

    return wrapped


class _RangeStack(threading.local):
    """Thread-local: TraceAnnotation scopes are thread-bound, and the reference's
    range_push/range_pop contract is per-thread."""

    def __init__(self):
        self._stack = []

    def push(self, name: str):
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._stack.append(ann)

    def pop(self):
        if self._stack:
            self._stack.pop().__exit__(None, None, None)


_ranges = _RangeStack()


def range_push(name: str):
    """Accelerator ``range_push`` (reference ``abstract_accelerator.py:161``)."""
    _ranges.push(name)


def range_pop():
    _ranges.pop()
