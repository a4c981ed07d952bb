"""Benchmark harness: one module per paper table/figure + the beyond-paper
engines.  Prints ``name,us_per_call,derived`` CSV (harness contract).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run trees lrt  # subset
    REPRO_BENCH_FULL=1 ... for paper-size datasets (hours on 1 CPU core)

A suite that raises still prints its ``<suite>/ERROR`` row, and the harness
then exits non-zero.
"""

from __future__ import annotations

import sys
from benchmarks.paper_common import now
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    from benchmarks import (
        analysis_cache,
        bss_engine,
        bss_incremental,
        bss_sharded,
        paper_lrt,
        paper_scatter,
        paper_trees,
        paper_unbalance,
        retrieval_serving,
        roofline,
    )

    suites = {
        "scatter": paper_scatter.run,     # Fig. 4-7
        "trees": paper_trees.run,         # Fig. 12-13 (host numpy walk)
        "trees_forest": paper_trees.run_forest,  # same sweep, device forest
        "lrt": paper_lrt.run,             # Fig. 15-16 (§5)
        "lrt_forest": paper_lrt.run_forest,  # same sweep, device forest
        "unbalance": paper_unbalance.run,  # §6 future work, implemented
        "bss": bss_engine.run,            # beyond-paper TPU engine
        "bss_metrics": bss_engine.run_metrics,  # 4-supermetric sweep
        "bss_bf16": bss_engine.run_precision,  # fp32-vs-bf16 exact phase
        "bss_sharded": bss_sharded.run,   # multi-device mesh sweep
        "bss_incremental": bss_incremental.run,  # living-corpus maintenance
        "retrieval": retrieval_serving.run,  # serving integration
        "retrieval_async": retrieval_serving.run_async,  # async front, Poisson
        "roofline": roofline.run,         # dry-run derived terms
        "analysis_cache": analysis_cache.run,  # bounded-recompile replay
    }
    pick = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    failed = []
    for name in pick:
        t0 = now()
        try:
            for r in suites[name]():
                print(r, flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"# suite {name} finished in {now() - t0:.1f}s", flush=True)
    if failed:
        raise SystemExit(f"suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
