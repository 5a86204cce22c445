"""python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json; prints one JSON line last on
standard output. Fails, printing no result, without the chips the cell
asks for, or outside a checkout that holds the program.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "minips_tpu")):
        print("bench: no minips_tpu/ beside bench/: nothing to measure",
              file=sys.stderr)
        return 2
    for p in (ROOT, HERE):          # HERE first: benchlib is ours
        if p in sys.path:
            sys.path.remove(p)
        sys.path.insert(0, p)
    from benchlib import harness, spec
    t_start = harness.process_start_time()
    try:
        return harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=t_start)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
