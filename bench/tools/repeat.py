#!/usr/bin/env python3
"""Run one cell several times, each run a process of its own as the
benchmark's check runs it, and summarise the spread.

    python3 bench/tools/repeat.py --workload <cell> --seeds 11,12,13 \
        --seconds 30 [--trace 1] [--sets 2] [--out runs.jsonl]

With ``--sets 2`` the seeds run twice, set after set.  Prints every run's
result line and, per metric, each set's median and its spread (the
distance between the quartiles of ``statistics.quantiles(n=4)`` over the
median).
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(vals: list) -> float:
    if len(vals) < 2:
        return 0.0
    q1, med, q3 = statistics.quantiles(vals, n=4)
    return (q3 - q1) / med if med else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="30")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    seeds = args.seeds.split(",")
    runs = []
    for s in range(args.sets):
        for seed in seeds:
            t0 = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", args.workload,
                 "--seed", seed, "--seconds", args.seconds,
                 "--trace", args.trace],
                cwd=ROOT, capture_output=True, text=True, timeout=1500)
            wall = time.perf_counter() - t0
            lines = proc.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1])
            except (IndexError, json.JSONDecodeError):
                res = None
            rec = {"set": s, "seed": seed, "rc": proc.returncode,
                   "wall_s": wall, "result": res,
                   "stderr_tail": proc.stderr[-3000:]}
            runs.append(rec)
            print(f"repeat: set {s} seed {seed} rc={proc.returncode} "
                  f"wall={wall:.1f}s", flush=True)
            if res is None:
                print(proc.stderr[-3000:], flush=True)
            else:
                print(json.dumps(res), flush=True)
                print("\n".join(proc.stderr.strip().splitlines()[-8:]),
                      flush=True)
            if args.out:
                with open(ROOT / args.out, "a") as fh:
                    fh.write(json.dumps(rec) + "\n")
    names = sorted({m for r in runs if r["result"]
                    for m in r["result"]["metrics"]})
    for name in names:
        for s in range(args.sets):
            vals = [r["result"]["metrics"][name]["value"] for r in runs
                    if r["set"] == s and r["result"]
                    and name in r["result"]["metrics"]]
            if vals:
                print(f"summary: {name} set {s}: median "
                      f"{statistics.median(vals):.6g} spread "
                      f"{spread(vals):.4f} values "
                      f"{[round(v, 6) for v in vals]}", flush=True)
    bad = [r for r in runs if not (r["result"] and r["result"]["correct"])]
    print(f"summary: {len(runs)} runs, {len(bad)} not correct", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
