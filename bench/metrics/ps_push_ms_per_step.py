"""Device time a traced step in ``ps.push``, the push: the gradients' cast
back and their ravel into the table's one vector, the padding keys' zeros,
the reduce over the workers and its scatter to the owners: the union of the
intervals in which an op ran whose instruction the step's own account
(``minips_tpu.utils.profiling.programs()``) puts in that phase, by its
scope or by its neighbours, averaged over the chips
(``benchlib/phases.py``). A CPU run, a program without the account and a
step that keeps none report nothing."""

from benchlib import phases


def read(run):
    return phases.read(run, "ps.push")
