#!/usr/bin/env python3
"""Readings of a cell's control, beside the program's own, in one run.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py`` does, then puts the control in the program's
place: the configuration's plain reference computed in the next lower
precision than the configuration states (bfloat16 for the float32 CG,
float8 weights for the bfloat16 model), compared with the reference as
the program is.  Each compared number is printed as
``reading <name> program <value> control <value> limit <value>``; a limit
holds only where the control reads above it.  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import sys
import time

from run import HERE, ROOT, T_START


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness
    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           False, t_start=T_START, control=True)
    control = {c["name"]: c["value"] for c in out.control}
    for c in out.checks:
        print(f"reading {c['name']} program {c['value']!r} control "
              f"{control.get(c['name'])!r} limit {c['limit']!r}")
    print(f"seconds {time.perf_counter() - T_START:.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
