"""Reads a cell's output check on many seeds in one process: the sound
program's readings (the lower reading of each limit), the control's (the
system adapter's ``control_readings``: the program's own lower-precision
path where it has one, else the reference one precision down) and the
planted faults'. Training's readings need no measured window: three steps
a seed.

  python3 bench/tools/check_seeds.py --workload <cell> --seeds 12 \
      [--control 3] [--faults 3] [--first-seed N] [--leaves FILE]

``--leaves`` also writes every reading leaf by leaf (the program's and the
reference's norms), one JSON line each, for a look at which leaf decides.

Prints one JSON line per reading and a summary; needs the cell's chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2200000000)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--root", default=None,
                    help="another root than the repo's (a tiny copy)")
    ap.add_argument("--leaves", default=None)
    args = ap.parse_args()
    from benchlib import check, harness, spec
    from minips_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload, args.root or spec.ROOT)
    leaves = open(args.leaves, "w") if args.leaves else None
    if harness.find_devices(cell.chips, not args.allow_cpu) is None:
        print("check_seeds: the cell's chips are not here", file=sys.stderr)
        return harness.NO_CHIP_RC
    mod = spec.load_system(cell.config["system"])
    phases = harness.Phases(harness.process_start_time())
    summary: dict = {}

    def note(kind, seed, prog, ref):
        nums = check.numbers(prog, ref)
        got = {k: v[0] for k, v in nums.items()}
        at = {k: v[1] for k, v in nums.items()}
        print(json.dumps({"kind": kind, "seed": seed, "numbers": got,
                          "leaf": at}), flush=True)
        for k, v in got.items():
            summary.setdefault(kind, {}).setdefault(k, []).append(v)
        if leaves:
            print(json.dumps({"kind": kind, "seed": seed, "prog": {
                k: v for k, v in prog.items() if k != "rows"}, "ref": {
                k: v for k, v in ref.items() if k != "rows"}}),
                  file=leaves, flush=True)

    for j in range(args.seeds):
        seed = args.first_seed + 7919 * j
        system = mod.build(cell, seed, phases)
        prog = harness.first_readings(system)
        system.free()
        ref = system.reference()
        note("sound", seed, prog, ref)
        if j < args.faults:
            note("fault_half_batch", seed, system.reference(keep=0.5), ref)
            if cell.chips > 1:
                note("fault_no_exchange", seed,
                     system.reference(keep=1.0 / cell.chips), ref)
            unchanged = dict(prog, delta={k: 0.0 for k in prog["delta"]})
            note("fault_state_unchanged", seed, unchanged, ref)
        if j < args.control:
            note("control", seed, mod.control_readings(system, phases), ref)
    print("# summary: kind number min max")
    for kind, nums in summary.items():
        for k, vals in nums.items():
            print(f"# {kind} {k} {min(vals):.6g} {max(vals):.6g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
