"""All tokens trained in the window over the whole window's seconds, per
chip."""


def read(run):
    if not run.tokens_per_step:
        return None
    return run.per_s_chip(run.tokens_per_step)
