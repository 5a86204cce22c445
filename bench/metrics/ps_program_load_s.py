"""Seconds the program's own executables took to be built, or read back
from the persistent cache, and loaded: the sum of the ``ps.compile``
records in the program's ring whose parent is a ``ps.*`` span (a table's
initialisers under ``ps.table.init``, the step under ``ps.step``). The
benchmark's own jits and the reference's have no such parent. A program
without the ring reports nothing."""


def read(run):
    try:
        from minips_tpu.utils.profiling import COMPILE, snapshot
    except ImportError:
        return None
    took = [s.end_ns - s.start_ns for s in snapshot()[0]
            if s.name == COMPILE and (s.parent_name or "").startswith("ps.")]
    if not took:
        return None
    return 1e-9 * sum(took)
