"""Median step of the window, host clock, feed to ``block_until_ready``."""


def read(run):
    return 1e3 * run.percentile(run.step_s, 50)
