"""On-demand XLA device-profiler capture.

Host spans (``observability.trace``) say where wall time went; only the XLA
profiler says what the device executed during a decode chunk or train step.
This module makes that capture operational instead of a notebook trick:

- **programmatic**: :class:`ProfilerCapture` wraps
  ``jax.profiler.start_trace`` / ``stop_trace`` so a capture covers exactly N
  *ticks* (train steps or decode chunks — the instrumented hot paths call
  :func:`tick` once per unit of work);
- **on-demand**: arm at construction (``capture_on_start``) or at runtime via
  ``SIGUSR2`` (:meth:`install_sigusr2`) — send the signal to a live
  ``deepspeed-serve``/trainer and the *next* N ticks are captured to the
  logdir, then the profiler stops. No restart, no steady-state overhead;
- **aligned**: the program's host spans (``tracer.span``) and the declared
  scopes of its compiled programs (``observability.scope``: each device op's
  ``tf_op``) land inside the capture, so the device timeline lines up with
  the host spans and every op says which region of the model it is.

The module-level :func:`tick` costs one global load + ``is None`` check when
no capture is configured — hot-path safe.
"""

import os
import signal
import threading
from typing import Optional

from ..utils.logging import logger


class ProfilerCapture:
    """Capture the next ``num_ticks`` units of work when armed."""

    def __init__(self, logdir: str, num_ticks: int = 4,
                 capture_on_start: bool = False):
        if num_ticks < 1:
            raise ValueError(f"num_ticks must be >= 1, got {num_ticks}")
        self.logdir = str(logdir)
        self.num_ticks = int(num_ticks)
        self._armed = bool(capture_on_start)
        self._remaining = 0
        self._active = False
        self._lock = threading.Lock()
        self.captures = 0            # completed captures this process

    # ------------------------------------------------------------------ state
    @property
    def armed(self) -> bool:
        return self._armed

    @property
    def active(self) -> bool:
        return self._active

    def arm(self, num_ticks: Optional[int] = None) -> None:
        """Signal-handler safe: flag only; the next tick starts the trace."""
        if num_ticks is not None:
            self.num_ticks = int(num_ticks)
        self._armed = True

    def install_sigusr2(self):
        """Route ``SIGUSR2`` to :meth:`arm`; returns the previous handler."""
        def _handler(signum, frame):
            self.arm()
        return signal.signal(signal.SIGUSR2, _handler)

    # ------------------------------------------------------------------- ticks
    def tick(self, kind: str = "step") -> None:
        """One unit of work completed (train step / decode chunk). Starts the
        device trace when armed, stops it after ``num_ticks``."""
        if not self._armed and not self._active:
            return
        with self._lock:
            if self._armed and not self._active:
                self._armed = False
                os.makedirs(self.logdir, exist_ok=True)
                import jax
                try:
                    jax.profiler.start_trace(self.logdir)
                except Exception as e:            # a capture must never kill
                    logger.warning(f"profiler capture failed to start: {e}")
                    return
                self._active = True
                self._remaining = self.num_ticks
                logger.info(f"[obs] XLA profiler capture started "
                            f"({self.num_ticks} {kind}(s) -> {self.logdir})")
                return
            if self._active:
                self._remaining -= 1
                if self._remaining <= 0:
                    self._finish()

    def _finish(self) -> None:
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception as e:                    # pragma: no cover
            logger.warning(f"profiler capture failed to stop: {e}")
        self._active = False
        self.captures += 1
        logger.info(f"[obs] XLA profiler capture written to {self.logdir}")

    def close(self) -> None:
        """Stop a capture left running (e.g. the loop ended mid-capture)."""
        with self._lock:
            if self._active:
                self._finish()


_capture: Optional[ProfilerCapture] = None


def configure_capture(logdir: Optional[str], num_ticks: int = 4,
                      capture_on_start: bool = False,
                      sigusr2: bool = True) -> Optional[ProfilerCapture]:
    """Install the process-wide capture (``logdir=None`` uninstalls)."""
    global _capture
    if _capture is not None:
        _capture.close()
    if logdir is None:
        _capture = None
        return None
    _capture = ProfilerCapture(logdir, num_ticks=num_ticks,
                               capture_on_start=capture_on_start)
    if sigusr2:
        try:
            _capture.install_sigusr2()
        except ValueError:        # not the main thread: arm() still works
            logger.warning("SIGUSR2 trigger unavailable off the main thread; "
                           "use ProfilerCapture.arm()")
    return _capture


def get_capture() -> Optional[ProfilerCapture]:
    return _capture


def tick(kind: str = "step") -> None:
    """Hot-path hook: one global load + None check when no capture exists."""
    c = _capture
    if c is not None:
        c.tick(kind)
