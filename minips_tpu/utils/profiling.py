"""Profiling hooks — jax.profiler made first-class (SURVEY.md §5.1).

The reference has only glog-timestamped iteration timers; on TPU the real
tool is the XLA profiler: ``jax.profiler.trace`` captures a TensorBoard-
readable trace (HLO timelines, per-op HBM/MXU utilization). Because the
[T1] primary metric is samples/sec/chip, profiling is not an afterthought:
``profile_steps`` wraps a window of training steps, and ``TrainLoop``
exposes it via ``profile_dir``/``profile_range``.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator, Optional


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace into ``log_dir`` (view with
    TensorBoard's profile plugin, or read the ``.xplane.pb`` with
    ``jax.profiler.ProfileData``). Whoever calls this asked for a trace: a
    profiler that cannot start or stop raises."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield


class StepWindowProfiler:
    """Trace exactly the steps in [start, stop) — skipping compile-bearing
    early steps, the standard TPU profiling hygiene (first call traces +
    compiles and would drown the steady-state timeline)."""

    def __init__(self, log_dir: str, start: int, stop: int):
        if stop <= start:
            raise ValueError("profile window must be non-empty")
        self.log_dir = log_dir
        self.start = start
        self.stop = stop
        self._ctx: Optional[contextlib.AbstractContextManager] = None

    def on_step(self, step: int) -> None:
        """Call once per step with the 0-based step index (before work)."""
        if step == self.start and self._ctx is None:
            self._ctx = profile_trace(self.log_dir)
            self._ctx.__enter__()
        elif step == self.stop and self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None

    def close(self) -> None:
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None


class Annotation:
    """Named host-side span that also shows up in device traces via
    jax.profiler.TraceAnnotation; accumulates wall time per name so hot
    host phases (data loading, checkpoint snapshot) are quantified even
    without a device trace."""

    totals: dict[str, float] = {}

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        import jax

        self._t0 = time.monotonic()
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:  # pragma: no cover
            self._ann = None
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        Annotation.totals[self.name] = (
            Annotation.totals.get(self.name, 0.0)
            + time.monotonic() - self._t0)
        return False
