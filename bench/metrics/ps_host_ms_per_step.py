"""The host time the program itself spends to enqueue one step: the median
duration of the ``ps.step`` spans in the program's own ring
(``minips_tpu.utils.profiling.snapshot()``: everything between the
caller's batch and the returned loss future). The reader runs in the
process that ran the program; a program without the ring reports
nothing."""

import statistics
import sys


def read(run):
    try:
        from minips_tpu.utils.profiling import STEP, snapshot
    except ImportError:
        return None
    took = [s.end_ns - s.start_ns for s in snapshot()[0] if s.name == STEP]
    if not took:
        return None
    print(f"ps_host_ms_per_step: median of {len(took)} ps.step spans",
          file=sys.stderr)
    return 1e-6 * statistics.median(took)
