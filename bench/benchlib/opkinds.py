"""Which device operations belong to which layer, and their time per step.

Until the program names its phases (named scopes inside ``make_step``:
a later ``tracing`` PR), operations are matched by the category and the
shapes that the trace gives them.
"""

from __future__ import annotations

import re

from benchlib import trace as tracelib

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective")


def _dims(detail: str) -> set:
    return {int(d) for d in re.findall(r"[0-9]+", detail)}


def is_sparse_op(op, *sizes: int) -> bool:
    """The sparse table's work: gather, scatter and sort, and every
    operation that has one of ``sizes`` (the table's row count, the count
    of ids in a step's batch: the rows pulled and pushed) among its
    result's or its operands' dimensions. A fusion does not say what it
    computes, its shapes do: the tower's operations carry neither number."""
    if op.category in ("gather", "scatter", "sort"):
        return True
    return bool(_dims(op.detail) & {s for s in sizes if s})


def sparse_seconds_per_step(run):
    """Device seconds per step of the sparse table's work. Over several
    chips an operation carries the table's rows and the batch's ids either
    whole or as one chip's shard, so both mark it."""
    slots, ids = int(run.config["num_slots"]), run.info["rows_per_step"]
    sizes = {slots, slots // run.chips, ids, ids // run.chips}
    return seconds_per_step(run, lambda o: is_sparse_op(o, *sizes))


def is_kernel(op) -> bool:
    """A Pallas kernel: a ``tpu_custom_call``, opcode ``custom-call``."""
    return op.category == "custom-call"


def is_collective(op) -> bool:
    return any(op.category.startswith(k) or op.name.startswith(k)
               for k in COLLECTIVES)


def seconds_per_step(run, pred):
    """Union seconds per traced step of the ops ``pred`` accepts, averaged
    over the chips; None where there is no trace or nothing matched."""
    if run.trace is None or not run.traced_steps:
        return None
    t = run.trace_summary
    per_dev = [tracelib.seconds_matching(ops, t["lo"], t["hi"], pred)
               for ops in run.trace.devices.values()]
    if not per_dev or not sum(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / run.traced_steps
