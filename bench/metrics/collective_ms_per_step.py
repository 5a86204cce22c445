"""Device time a step of the collectives the compiler built for the fused
PS step (the pull's all-gather, the push's reduce-scatter or all-reduce,
the loss's mean): the union of the intervals in which an operation that
``opkinds.is_collective`` finds ran, over the traced steps, on the BUSIEST
chip (a collective ends when its last chip arrives, so the mean over chips
would hide who waited). An asynchronous collective shows as its ``-start``
and ``-done`` ops: what is counted is the time the chip spent IN them, the
part compute did not hide. On one chip, or in a program whose step has no
collective, there is nothing to read."""

from benchlib import opkinds
from benchlib import trace as tracelib


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    t = run.trace_summary
    per_dev = [tracelib.seconds_matching(ops, t["lo"], t["hi"],
                                         opkinds.is_collective)
               for ops in run.trace.devices.values()]
    if not per_dev or not max(per_dev):
        return None
    return 1e3 * max(per_dev) / run.traced_steps
