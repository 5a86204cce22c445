"""Host-side step timing for throughput accounting (SURVEY.md §5.1).

The [T1] primary metric is samples/sec/chip (BASELINE.json:2), so timing is a
first-class utility, not an afterthought. ``StepTimer`` excludes the first
``warmup_steps`` (compile-bearing) steps from steady-state rate computation —
under XLA the first invocation traces + compiles (~20-40s cold on TPU) and
would poison a naive average. ``warmup_steps=0`` counts everything from
construction time.
"""

from __future__ import annotations

import time


class StepTimer:
    def __init__(self, warmup_steps: int = 2):
        self.warmup_steps = max(int(warmup_steps), 0)
        self._steps = 0
        self._samples = 0
        self._t_start: float | None = (
            time.monotonic() if self.warmup_steps == 0 else None)
        self._t_last: float | None = None

    def step(self, n_samples: int) -> None:
        now = time.monotonic()
        self._steps += 1
        if self._steps == self.warmup_steps:
            # last warmup step just finished: steady state begins now
            self._t_start = now
            self._samples = 0
        elif self._steps > self.warmup_steps:
            self._samples += n_samples
        self._t_last = now

    @property
    def steady_seconds(self) -> float:
        if self._t_start is None or self._t_last is None:
            return 0.0
        return max(self._t_last - self._t_start, 0.0)

    @property
    def samples_per_sec(self) -> float:
        s = self.steady_seconds
        return self._samples / s if s > 0 else 0.0
