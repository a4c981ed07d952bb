#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one process, one set-up, then one
window at each offered rate.

    python3 bench/tools/knee.py --workload sift1m-l2.range-open --seed 7 \
        --rates 60,100,140,180 --seconds 20

For each rate it prints the rate completed, the latency quartiles, the
median latency of the window's last third over its first third (a growing
backlog reads well above 1), the mean batch size and the generator's
lateness.  The knee is the highest rate whose backlog does not grow.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402
from bench.cell import load_cell, request_params  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    import jax

    c = load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(run.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(run.ROOT / "src"))
    cfg, traffic, cell = c["config"], c["traffic"], c["cell"]
    corpus, pool = c["data"].make(cfg, args.seed)
    entry = c["entry"].Entry(cfg, corpus)
    entry.warm(pool, traffic, request_params(traffic, cell))
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = dict(cell, rate_per_s=rate)
        rec = c["loop"].run(entry, pool, traffic, cell, args.seed,
                            args.seconds)
        lat = c["loop"].latencies(rec)
        due = np.asarray([r["due"] for r in rec["requests"]]) - rec["t0"]
        first = lat[due < args.seconds / 3]
        last = lat[due >= 2 * args.seconds / 3]
        ok = [r for r in rec["requests"] if r["ok"]]
        span = max(r["done"] for r in ok) - rec["t0"]
        sizes = [r["res"].batch_size for r in ok]
        row = {
            "rate": rate, "completed_per_s": len(ok) / span,
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "growth": float(np.median(last) / np.median(first)),
            "mean_batch": float(np.mean(sizes)),
            "late_p95_ms": 1e3 * float(np.percentile(
                c["loop"].lateness(rec), 95)),
            "failed": len(rec["requests"]) - len(ok),
        }
        rows.append(row)
        print("knee: " + json.dumps(row), flush=True)
    entry.close()
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
