"""``check_seeds.py`` for a system whose adapter names its own planted
faults (``FAULTS``: name -> the reference's arguments): on each seed the
sound program's readings, the control's, each planted fault's and an
unchanged state's, against the reference; where the adapter counts them,
the tokens whose expert differs between program and reference.

  python3 bench/tools/check_faults.py --workload <cell> --seeds 8 \
      [--control 8] [--faults 8] [--first-seed N]

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
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--control", type=int, default=8)
    ap.add_argument("--faults", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=2800000000)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--root", default=None,
                    help="another root than the repo's (a tiny copy)")
    args = ap.parse_args()
    from benchlib import check, harness, spec
    from minips_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload, args.root or spec.ROOT)
    if harness.find_devices(cell.chips, not args.allow_cpu) is None:
        print("check_faults: the cell's chips are not here", file=sys.stderr)
        return harness.NO_CHIP_RC
    mod = spec.load_system(cell.config["system"])
    phases = harness.Phases(harness.process_start_time())
    summary: dict = {}

    def note(kind, seed, prog, ref, **more):
        nums = check.numbers(prog, ref)
        got = {k: v[0] for k, v in nums.items()}
        ok = check.decide(prog, ref, cell.workload["limits"])[0]
        print(json.dumps({"kind": kind, "seed": seed, "numbers": got,
                          "leaf": {k: v[1] for k, v in nums.items()},
                          "within_limits": ok, **more}), flush=True)
        for k, v in got.items():
            summary.setdefault(kind, {}).setdefault(k, []).append(v)

    for j in range(args.seeds):
        seed = args.first_seed + 7919 * j
        system = mod.build(cell, seed, phases)
        prog = harness.first_readings(system)
        system.free()
        ref = system.reference()
        note("sound", seed, prog, ref,
             flips=getattr(system, "flips", None))
        if j < args.faults:
            for name, kw in getattr(mod, "FAULTS", {}).items():
                note(name, seed, system.reference(**kw), ref)
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
