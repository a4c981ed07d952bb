"""Sharded BSS scaling sweep over a device mesh.

    PYTHONPATH=src python -m benchmarks.bss_sharded --devices 1 2 4 8

On an accelerator host the sweep runs in the calling process over the real
devices (a chip belongs to one process: a child could not get it).  On a
CPU-only host the devices are simulated; the forcing flag must precede jax
initialisation, so the entry point then re-executes itself in a subprocess
with ``XLA_FLAGS`` requesting ``max(devices)`` simulated host devices.
Either way it sweeps ONE built l2 index
through ``("data",)`` meshes of every requested width: the same range +
kNN workload per width, hits AND per-query distance counts asserted
against the numpy oracle and the single-device fused engine, wall-clock
recorded per width.  ``BENCH_bss_sharded.json`` (archived by the
sharded-matrix CI job) carries the curve plus the device stamp from
``paper_common.write_bench_json``.

On SIMULATED devices the curve measures sharding overhead, not speedup —
every shard shares the same host cores, so flat-ish microseconds/query
across widths is the healthy signal (the collective + dispatch overhead
is bounded); the speedup column becomes meaningful the day the same sweep
runs on a real multi-chip mesh.
"""

from __future__ import annotations

import json
import subprocess
import sys
from benchmarks.paper_common import now

from repro.launch.simdevices import simulated_device_env

DEFAULT_DEVICES = (1, 2, 4, 8)
_OUT = "BENCH_bss_sharded.json"


def _reexec_with_devices(devices, seed: int, out: str) -> int:
    """Run the sweep in a child process whose XLA_FLAGS force max(devices)
    simulated host devices (env assembly shared with the test shim — see
    ``repro.launch.simdevices``)."""
    env = simulated_device_env(max(devices))
    cmd = [
        sys.executable, "-m", "benchmarks.bss_sharded", "--inner",
        "--seed", str(seed), "--out", out,
        "--devices", *[str(d) for d in devices],
    ]
    return subprocess.run(cmd, env=env).returncode


def _sweep(devices, seed: int):
    """The actual measurement (runs in the re-exec'd child).  Returns
    (csv rows, results dict for the JSON record)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from benchmarks.paper_common import FULL, timed
    from repro.core import flat_index
    from repro.data import metricsets
    from repro.parallel.shard_index import (
        shard_bss, sharded_knn_batched, sharded_query_batched,
    )

    devs = jax.devices()
    usable = [c for c in devices if c <= len(devs)]
    skipped = [c for c in devices if c > len(devs)]

    n = 65_536 if FULL else 24_576  # 192 blocks of 128 at the CI size
    nq, k = 256, 10
    data = metricsets.colors_surrogate(n + nq, dim=96, seed=seed + 17)
    db, q = data[:n], data[n:]
    t = metricsets.calibrate_threshold("l2", db[:20_000], 1e-4, seed=seed)
    idx, dt_build = timed(
        flat_index.build_bss, "l2", db, n_pivots=16, n_pairs=24, block=128,
        seed=seed,
    )
    oracle_hits, oracle_stats = flat_index.bss_query(idx, q, t)

    rows = []
    results = {
        "corpus": int(n), "queries": int(nq), "k": int(k),
        "threshold": float(t), "build_s": round(dt_build, 2),
        "n_blocks": int(idx.n_blocks),
        "oracle_dists_per_query": round(oracle_stats["dists_per_query"], 2),
        "devices_available": len(devs),
        "devices_skipped": skipped,
        "widths": {},
    }

    # single-device fused engine: the baseline every width is held to
    flat_index.bss_query_batched(idx, q, t)  # jit warm-up
    single_hits, single_stats = flat_index.bss_query_batched(idx, q, t)
    dt_single = min(
        timed(flat_index.bss_query_batched, idx, q, t)[1] for _ in range(3)
    )
    flat_index.bss_knn_batched(idx, q, k)
    dt_single_knn = min(
        timed(flat_index.bss_knn_batched, idx, q, k)[1] for _ in range(3)
    )
    results["single_device"] = {
        "range_us_per_query": round(dt_single / nq * 1e6, 1),
        "knn_us_per_query": round(dt_single_knn / nq * 1e6, 1),
        "exact": bool(single_hits == oracle_hits),
        "dists_per_query": round(single_stats["dists_per_query"], 2),
    }
    rows.append(
        f"bss_sharded/baseline/1dev,{dt_single / nq * 1e6:.1f},"
        f"exact={single_hits == oracle_hits};"
        f"knn_us={dt_single_knn / nq * 1e6:.1f};corpus={n}"
    )

    base_range = None
    for c in usable:
        mesh = Mesh(np.array(devs[:c]), ("data",))
        sidx = shard_bss(idx, mesh)
        sharded_query_batched(sidx, q, t)  # warm-up (jit + layout)
        hits, st = sharded_query_batched(sidx, q, t)
        dt_range = min(
            timed(sharded_query_batched, sidx, q, t)[1] for _ in range(3)
        )
        sharded_knn_batched(sidx, q, k)
        ki, _, kst = sharded_knn_batched(sidx, q, k)
        dt_knn = min(
            timed(sharded_knn_batched, sidx, q, k)[1] for _ in range(3)
        )
        exact = bool(
            hits == oracle_hits
            and abs(st["dists_per_query"] - oracle_stats["dists_per_query"])
            < 1e-6
        )
        if base_range is None:
            base_range = dt_range
        results["widths"][str(c)] = {
            "range_us_per_query": round(dt_range / nq * 1e6, 1),
            "knn_us_per_query": round(dt_knn / nq * 1e6, 1),
            "knn_rounds": int(kst["rounds"]),
            "exact": exact,
            "dists_per_query": round(st["dists_per_query"], 2),
            "speedup_vs_1shard": round(base_range / max(dt_range, 1e-9), 2),
        }
        rows.append(
            f"bss_sharded/{c}dev/range,{dt_range / nq * 1e6:.1f},"
            f"exact={exact};dists_per_query={st['dists_per_query']:.0f};"
            f"knn_us={dt_knn / nq * 1e6:.1f};rounds={kst['rounds']};"
            f"speedup_vs_1shard="
            f"{base_range / max(dt_range, 1e-9):.2f}x"
        )
        if not exact:
            raise SystemExit(
                f"sharded/{c}dev diverged from the oracle — the sweep is "
                f"the exactness gate at benchmark scale"
            )
    return rows, results


def _real_devices() -> bool:
    """True when jax sees an accelerator: the sweep must then run in this
    process, which holds the devices."""
    import jax

    return jax.default_backend() != "cpu"


def run(devices=DEFAULT_DEVICES, seed: int = 0):
    """Harness entry point (benchmarks.run): in-process over real devices;
    on CPU, re-exec under the forcing flag, then lift the child's CSV rows
    back into this process."""
    if _real_devices():
        return _sweep(tuple(devices), seed)[0]
    out = _OUT
    code = _reexec_with_devices(tuple(devices), seed, out)
    if code != 0:
        raise RuntimeError(f"bss_sharded subprocess failed ({code})")
    with open(out) as fh:
        payload = json.load(fh)
    return payload.get("rows", [])


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, nargs="+",
                    default=list(DEFAULT_DEVICES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=_OUT)
    ap.add_argument("--inner", action="store_true",
                    help="(internal) already under the forcing flag")
    args = ap.parse_args()
    if not args.inner and not _real_devices():
        raise SystemExit(
            _reexec_with_devices(tuple(args.devices), args.seed, args.out)
        )
    from benchmarks.paper_common import FULL, write_bench_json

    print("name,us_per_call,derived")
    t0 = now()
    rows, results = _sweep(tuple(args.devices), args.seed)
    for r in rows:
        print(r, flush=True)
    write_bench_json(args.out, {
        "bench": "bss_sharded",
        "seed": args.seed,
        "wall_s": round(now() - t0, 1),
        "full": FULL,
        "rows": rows,
        "sweep": results,
    })


if __name__ == "__main__":
    main()
