"""Seconds jax took to trace the program's own functions and lower them
to modules, before ``ps_program_load_s``'s compilations: the sum of the
``ps.trace`` and ``ps.lower`` records in the program's ring whose parent
is a ``ps.*`` span (the step under ``ps.step``, a table's initialisers
under ``ps.table.init``). The program records a trace only where it is
the outermost (a function traced inside another's trace is part of the
outer's seconds). A program without these records reports nothing."""


def read(run):
    try:
        from minips_tpu.utils import profiling as prof
        names = (prof.TRACE, prof.LOWER)
    except (ImportError, AttributeError):
        return None
    took = [s.end_ns - s.start_ns for s in prof.snapshot()[0]
            if s.name in names and (s.parent_name or "").startswith("ps.")]
    if not took:
        return None
    return 1e-9 * sum(took)
