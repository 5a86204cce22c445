"""Device busy time per step: the union of the intervals in which an
operation ran, over the traced window's steps, averaged over the chips."""


def read(run):
    if (run.trace_summary is None or not run.traced_steps
            or not run.trace_summary["busy_s"]):
        return None
    return 1e3 * run.trace_summary["busy_s"] / run.traced_steps
