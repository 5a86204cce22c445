"""Prints what a kept profiler trace holds (planes, lines, a few events
with their stats): look at one trace by hand before trusting a reduction.
Usage: BENCH_KEEP_TRACE=1 python3 bench/run.py ... --trace 1; then
python3 bench/tools/dump_trace.py [dir]"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    from jax.profiler import ProfileData
    from benchlib import harness, trace
    path = trace.latest_xplane(sys.argv[1] if len(sys.argv) > 1
                               else harness.TRACE_DIR)
    if path is None:
        print("no trace kept")
        return 1
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            keep = [e for e in events if e.name.startswith("bench.")][:4] \
                or events[:6]
            for e in keep:
                print("     ", e.name, e.start_ns, e.duration_ns,
                      {k: str(v)[:160] for k, v in dict(e.stats).items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
