"""Which part of a slow step was slow: one run of a cell in this process,
then, for the three slowest steps of its window (``steps_ms.slowest`` of
the result line), the program's own spans of that step and the gaps
between them, from the program's ring
(``minips_tpu.utils.profiling.snapshot()``, which records with no
profiler running).

  python3 bench/tools/spans_of_run.py --workload <cell> --seed <n> \
      --seconds <s> [--trace 0|1] [--skip-reference]

A step of the window is: the harness takes a batch (``tool.next_batch``)
and puts it on the device (``tool.put``), both spans of this tool around
the adapter's calls; the program enqueues the step (``ps.step`` and its
children); the harness waits for the loss (no span: the gap from the end
of ``ps.step`` to the next ``tool.next_batch``). ``--skip-reference``
leaves the output check's reference out (``correct`` is then false): a
hunt for a stall does not need it.

Prints the result line, then one JSON line a slow step; needs the chips.
"""

from __future__ import annotations

import argparse
import bisect
import io
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    sys.path.insert(0, p)


class Spanned:
    """The system adapter with this tool's spans around its feed."""

    def __init__(self, system, skip_reference: bool):
        from minips_tpu.utils.profiling import span
        self._system, self._skip, self._span = system, skip_reference, span
        self._read: list = []

    def __getattr__(self, name):
        return getattr(self._system, name)

    def host_batch(self, i):
        with self._span("tool.next_batch"):
            return self._system.host_batch(i)

    def put(self, batch):
        with self._span("tool.put"):
            return self._system.put(batch)

    def to_host(self, obs):
        out = self._system.to_host(obs)
        self._read.append(out)
        return out

    def reference(self, **kw):
        if self._skip:
            # the program's own readings (the harness reads the gradient,
            # then the change) and no loss: the check runs and fails
            return {"loss": [], "grad": self._read[0],
                    "delta": self._read[1]}
        return self._system.reference(**kw)


def step_report(spans, ordinal: int) -> dict:
    """The spans of the step with ``ps.step`` ordinal ``ordinal``, from the
    end of the step before it to the start of the one after it, with
    offsets in ms from the first, and the gaps between top-level spans."""
    steps = {s.step: s for s in spans if s.name == "ps.step"}
    if ordinal not in steps:
        return {"ordinal": ordinal, "error": "not in the ring"}
    lo = steps[ordinal - 1].end_ns if ordinal - 1 in steps \
        else steps[ordinal].start_ns
    hi = steps[ordinal + 1].start_ns if ordinal + 1 in steps \
        else steps[ordinal].end_ns
    inside = sorted((s for s in spans if s.end_ns > lo and s.start_ns < hi),
                    key=lambda s: (s.start_ns, -s.end_ns))
    ms = lambda ns: round(1e-6 * ns, 3)                      # noqa: E731
    rows, gaps, at = [], [], lo
    for s in inside:
        rows.append({"name": s.name, "parent": s.parent_name,
                     "at_ms": ms(s.start_ns - lo),
                     "ms": ms(s.end_ns - s.start_ns)})
        if s.parent is None:
            if s.start_ns > at:
                gaps.append({"before": s.name, "ms": ms(s.start_ns - at)})
            at = max(at, s.end_ns)
    if hi > at:
        gaps.append({"before": "next ps.step", "ms": ms(hi - at)})
    return {"ordinal": ordinal, "from_prev_step_end_ms": ms(hi - lo),
            "spans": rows, "gaps": gaps}


def window_medians(spans, lo: int, hi: int) -> dict:
    """Medians over the window's steps (``ps.step`` ordinals lo..hi-1) of
    each part of a step, in ms: a run whose every step is slow shows here
    which part."""
    steps = {s.step: s for s in spans if s.name == "ps.step"}
    feeds = sorted((s for s in spans if s.name == "tool.next_batch"),
                   key=lambda s: s.start_ns)
    parts: dict = {"tool.next_batch": [], "tool.put": [], "ps.step": [],
                   "wait": []}
    for s in spans:
        if s.name in ("tool.next_batch", "tool.put"):
            parts[s.name].append(s.end_ns - s.start_ns)
    starts = [f.start_ns for f in feeds]
    for k in range(lo, hi):
        if k not in steps:
            continue
        parts["ps.step"].append(steps[k].end_ns - steps[k].start_ns)
        j = bisect.bisect_left(starts, steps[k].end_ns)
        if j < len(starts):     # the wait ends where the next feed starts
            parts["wait"].append(starts[j] - steps[k].end_ns)
    return {k: round(1e-6 * statistics.median(v), 4)
            for k, v in parts.items() if v}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--skip-reference", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--root", default=None,
                    help="another root than the repo's (a tiny copy)")
    args = ap.parse_args()
    from benchlib import harness, spec
    from minips_tpu.utils import profiling
    t_start = harness.process_start_time()
    root = args.root or spec.ROOT
    cell = spec.load_cell(args.workload, root)
    seen = []

    def wrap(system):
        seen.append(system)
        return Spanned(system, args.skip_reference)

    out = io.StringIO()
    rc = harness.run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start,
                          require_tpu=not args.allow_cpu, wrap_system=wrap,
                          root=root, out=out)
    line = out.getvalue().strip()
    print(line, flush=True)
    if rc != 0 or not line:
        return rc
    result = json.loads(line.splitlines()[-1])
    spans = profiling.snapshot()[0]
    # the window's step i is the (set-up's steps + i)-th ps.step of the run
    ordinals = sorted(s.step for s in spans if s.name == "ps.step")
    if not ordinals:
        print("spans_of_run: the program recorded no ps.step span",
              file=sys.stderr)
        return 1
    first = (ordinals[0] + seen[0].check_steps
             + int(cell.traffic["warmup_steps"]))
    for i, took_ms in result["steps_ms"]["slowest"]:
        print(json.dumps(dict(step_report(spans, first + i),
                              window_step=i, step_ms=took_ms)), flush=True)
    print(json.dumps({"window_medians_ms": window_medians(
        spans, first, first + result["attempted"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
