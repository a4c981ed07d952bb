#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: one run of the
cell per seed through ``bench/run.py``'s own ``run_cell``, all in one
process, printing the numbers compared.

    python3 bench/tools/limits.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--control]

Without ``--control`` the numbers are the program's own answers' (the
lower readings); with it, the control's answers to the same requests
(``bench/control.py``), which have to come out not correct (the upper
readings).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(run.ROOT / "src"))
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        try:
            out = run.run_cell(args.workload, seed, args.seconds, False,
                               control=args.control)
        except run.NoChip as e:
            print(f"limits: {e}", file=sys.stderr)
            return 1
        row = {"seed": seed, "control": args.control,
               "correct": out["correct"], "attempted": out["attempted"],
               "checks": out["checks"],
               "seconds": time.perf_counter() - t0}
        print("limits: " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
