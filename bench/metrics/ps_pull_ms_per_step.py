"""Device time a traced step in ``ps.pull``, the table's pull: the all-
gather of the shards where the table lies over chips, the cast to the
workers' dtype, the unravel: the union of the intervals in which an op ran
whose instruction the step's own account
(``minips_tpu.utils.profiling.programs()``) puts in that phase, by its
scope or by its neighbours, averaged over the chips
(``benchlib/phases.py``). A CPU run, a program without the account and a
step that keeps none report nothing."""

from benchlib import phases


def read(run):
    return phases.read(run, "ps.pull")
