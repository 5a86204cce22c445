"""Mean training loss over a fixed range of steps from the seed (the cell's
``loss_steps``, first and last, counted from the very first step of the
run). It is the same number however fast the window ran: a run that has
not reached the last step by the window's end steps on until it has. The
range is a cell's own: long enough that seeds read alike (PERF.md)."""


def read(run):
    first, last = run.loss_steps
    part = run.losses[first - 1: last]
    return sum(part) / len(part)
