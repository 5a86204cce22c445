"""A traced run's device time by the phase of the parameter server's step:
pull, grad, push, update.

``trace.py`` keeps an op's name, opcode and shapes and not its scope. What
is missing is one fact an instruction: which phase ``fusion.32`` belongs
to. The program says that itself: a fused step keeps an account of its own
compiled program (``minips_tpu.utils.profiling.programs()``), and
``minips_tpu.utils.trace_analysis.accounts()`` reads from its text the PS
phase of every instruction, under the name the trace's op events carry: by
its ``op_name`` or, where the compiler left it without one, by its
neighbours. The join is by that name. A phase's time is the UNION of
the intervals in which one of its ops ran (a ``while`` and its body count
once), averaged over the chips as ``device_ms_per_step`` is; what none of
the four unions covers (ops of no phase, ops the account does not know) is
``unscoped``. On one chip nothing overlaps and the five sum to the busy
time; across chips the four exceed their share by what a collective hides
under compute.

Where the program keeps no account (the parent of the PR that brought it,
a step that is not jitted) or the run has no device trace, there is
nothing to read and every function here says so with ``None`` or ``{}``.
"""

from __future__ import annotations

from benchlib import trace as tracelib

PS_PHASES = ("ps.pull", "ps.grad", "ps.push", "ps.update")
UNSCOPED = "unscoped"


def programs() -> dict:
    """``{program name: its account}`` as the program in this process
    hands them out; ``{}`` where it keeps none."""
    try:
        from minips_tpu.utils import profiling
    except ImportError:
        return {}
    return getattr(profiling, "programs", dict)()


def accounts() -> dict:
    """The same accounts in the form ``programs.json`` holds them (this
    reads and parses each program's compiled text); ``{}`` where the
    program keeps none."""
    try:
        from minips_tpu.utils import trace_analysis
    except ImportError:
        return {}
    return getattr(trace_analysis, "accounts", dict)()


def by_instruction(accs: dict) -> dict:
    """``{instruction name: PS phase or None}`` over the accounts (one
    program runs the steps of a cell)."""
    out = {}
    for acc in accs.values():
        at = acc["instruction_fields"].index("ps_phase")
        for name, row in acc["instructions"].items():
            out[name] = row[at]
    return out


def split(tr: tracelib.Trace, lo: float, hi: float, known: dict) -> dict:
    """Seconds of [lo, hi], the mean over the chips: each phase's union
    and ``unscoped``, the busy time outside the four (ops of no phase, ops
    whose name ``known`` does not hold)."""
    n = max(len(tr.devices), 1)
    out = dict.fromkeys(PS_PHASES + (UNSCOPED,), 0.0)
    for ops in tr.devices.values():
        groups: dict = {p: [] for p in PS_PHASES}
        for o in ops:
            if known.get(o.name) in groups:
                groups[known[o.name]].append(o)
        for p, members in groups.items():
            out[p] += tracelib.busy_seconds(members, lo, hi) / n
        covered = tracelib.busy_seconds(
            [o for members in groups.values() for o in members], lo, hi)
        out[UNSCOPED] += (tracelib.busy_seconds(ops, lo, hi) - covered) / n
    return out


def ms_per_step(run) -> dict | None:
    """``{phase or "unscoped": ms a traced step}`` of a run, read once a
    run; ``None`` without a device trace or an account."""
    if "ps_phase_ms" in run.__dict__:
        return run.ps_phase_ms
    run.ps_phase_ms = None
    if (run.trace is None or not run.traced_steps
            or not run.trace_summary["busy_s"]):
        return None
    known = by_instruction(accounts())
    if not known:
        return None
    t = run.trace_summary
    got = split(run.trace, t["lo"], t["hi"], known)
    run.ps_phase_ms = {k: v * 1e3 / run.traced_steps for k, v in got.items()}
    return run.ps_phase_ms


def read(run, phase: str):
    """One of the five, for a metric's reader."""
    per = ms_per_step(run)
    return None if per is None else per[phase]
