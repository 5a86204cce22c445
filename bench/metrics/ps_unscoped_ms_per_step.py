"""Device busy time a traced step that none of the four phase metrics
covers (``ps_pull|grad|push|update_ms_per_step``): ops whose instruction
the step's own account puts in no PS phase, by scope or by neighbours, and
ops whose name the account does not know; what those four cannot see. The
mean over the chips (``benchlib/phases.py``). A CPU run, a program without
the account and a step that keeps none report nothing."""

from benchlib import phases


def read(run):
    return phases.read(run, phases.UNSCOPED)
