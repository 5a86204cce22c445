"""All samples trained in the window over the whole window's seconds, per
chip. No medians of chunks: a stall inside the window moves it."""


def read(run):
    return run.per_s_chip(run.samples_per_step)
