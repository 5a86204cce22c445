"""Profiling hooks (SURVEY.md §5.1): trace window produces an artifact;
spans accumulate host time by name (tests/test_spans.py has the rest)."""

from __future__ import annotations

import os
import time

import jax.numpy as jnp

from minips_tpu.utils import profiling
from minips_tpu.utils.profiling import StepWindowProfiler, span


def test_step_window_profiler_writes_trace(tmp_path):
    d = str(tmp_path / "trace")
    p = StepWindowProfiler(d, start=2, stop=4)
    for i in range(6):
        p.on_step(i)
        jnp.sum(jnp.ones(16)).block_until_ready()
    p.close()
    # jax writes plugins/profile/<run>/ under the log dir
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found, "no trace artifacts written"


def test_window_closed_even_if_run_ends_early(tmp_path):
    p = StepWindowProfiler(str(tmp_path / "t2"), start=0, stop=100)
    p.on_step(0)
    p.close()  # must not raise / leak an open trace
    p.close()  # idempotent


def test_span_accumulates_count_and_total_by_name():
    profiling.clear()
    with span("phase_x"):
        time.sleep(0.01)
    with span("phase_x"):
        time.sleep(0.01)
    count, total_ns = profiling.snapshot()[1]["phase_x"]
    assert count == 2
    assert total_ns >= 0.02e9
