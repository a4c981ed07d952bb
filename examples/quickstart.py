"""Quickstart: supermetric search in 60 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds the paper's best tree (hpt_fft_log) and the TPU-native Blocked
Supermetric Scan over a clustered dataset, runs the same range queries with
Hyperbolic vs Hilbert exclusion, and prints the paper's figure of merit.
"""

import os

# Sharded-serving demo (step 8): simulate a 4-device host mesh when running
# on a single-CPU machine.  Must precede the first jax import; a real
# accelerator platform ignores the host-platform flag (and XLA_FLAGS set by
# the environment wins).
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)

import numpy as np  # noqa: E402

from repro.core import flat_index, tree  # noqa: E402
from repro.core.backends import EngineOpts  # noqa: E402
from repro.data import metricsets  # noqa: E402

# 1. a clustered "real-world-like" metric space (colors surrogate)
data = metricsets.colors_surrogate(10_000, dim=64, seed=0)
db, queries = metricsets.split_queries(data, frac=0.05, seed=1, max_queries=100)
t = metricsets.calibrate_threshold("l2", db, selectivity=2e-4)
print(f"corpus={len(db)}  queries={len(queries)}  threshold t={t:.4f}")

# 2. the paper's winning structure, both exclusion mechanisms
tr = tree.build_tree("hpt_fft_log", "l2", db, seed=2)
for mech in ("hyperbolic", "hilbert"):
    results, counter = tree.range_search(tr, queries, t, mech)
    print(f"hpt_fft_log + {mech:10s}: {counter.mean:8.1f} distances/query")

# 3. exactness against brute force
truth = tree.exhaustive_search("l2", db, queries, t)
assert all(sorted(a) == sorted(b) for a, b in zip(results, truth))
print("exactness: verified against exhaustive search")

# 4. the TPU-native engine (MXU-tile-aligned block pruning): fused batched
#    path (one jitted pass) checked against its numpy oracle
idx = flat_index.build_bss("l2", db, n_pivots=16, n_pairs=24, block=128)
hits, stats = flat_index.bss_query_batched(idx, queries, t)
oracle_hits, _ = flat_index.bss_query(idx, queries, t)
assert hits == oracle_hits
assert all(sorted(a) == sorted(b) for a, b in zip(hits, truth))
print(
    f"BSS engine (fused): {stats['dists_per_query']:.0f} distances/query, "
    f"{100 * stats['block_exclusion_rate']:.1f}% of 128-point blocks pruned "
    f"(exact results, == numpy oracle)"
)

# 5. batched exact kNN on the same index (radius-deepening rounds)
knn_idx, knn_dist, kstats = flat_index.bss_knn_batched(idx, queries, k=5)
print(
    f"BSS kNN: top-5 for {len(queries)} queries in {kstats['rounds']} "
    f"jitted rounds, {kstats['dists_per_query']:.0f} distances/query"
)

# 6. the same engine under the OTHER supermetrics (paper §2.2): the colors
#    surrogate rows are probability vectors, valid for JSD / Triangular —
#    and cosine rides the l2 kernels on the unit sphere.
from repro.core.npdist import pairwise_np  # noqa: E402

for metric in ("cosine", "jsd", "triangular"):
    t_m = metricsets.calibrate_threshold(metric, db, selectivity=2e-4)
    idx_m = flat_index.build_bss(metric, db, n_pivots=16, n_pairs=24, block=128)
    hits_m, stats_m = flat_index.bss_query_batched(idx_m, queries, t_m)
    oracle_m, _ = flat_index.bss_query(idx_m, queries, t_m)
    # the float32 engine and float64 oracle may only disagree on points
    # whose distance is within float rounding of the raw quantile threshold
    for a, b, qv in zip(hits_m, oracle_m, queries):
        for j in set(a) ^ set(b):
            dj = float(pairwise_np(metric, qv, db[j])[0, 0])
            assert abs(dj - t_m) <= 1e-5 * t_m, (metric, j, dj, t_m)
    print(
        f"BSS engine [{metric:10s}]: {stats_m['dists_per_query']:.0f} "
        f"distances/query (exact, == numpy oracle)"
    )

# 7. the device forest: array-encode the tree from step 2 and run the SAME
#    range search as a single jitted batched walk (frontier-per-level) —
#    identical result sets AND identical per-query distance counts.
from repro.forest import encode_tree, forest_range_search  # noqa: E402

enc = encode_tree(tr)
f_hits, f_stats = forest_range_search(enc, queries, t, "hilbert")
assert all(sorted(a) == sorted(b) for a, b in zip(f_hits, results))
assert (f_stats["per_query_dists"] == counter.per_query).all()
print(
    f"device forest (hpt_fft_log): {f_stats['dists_per_query']:8.1f} "
    f"distances/query over {f_stats['n_levels']} jitted levels "
    f"(results AND per-query counts == host walk)"
)

# 8. sharded serving: partition the BSS corpus blocks over a ("data",)
#    device mesh — build_bss(mesh=...) bears the device arrays with their
#    NamedSharding, and the SAME fused engine then runs one shard-local
#    pass per device under shard_map (range: hit bitmasks concatenated in
#    corpus order; kNN: per-shard top-k merged by all-gather + global
#    top-k under a global shrinking radius).  Hits AND distance counts are
#    identical to the single-device engine of steps 4-5.
import jax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

mesh = Mesh(np.array(jax.devices()), ("data",))
idx_sh = flat_index.build_bss(
    "l2", db, n_pivots=16, n_pairs=24, block=128, mesh=mesh
)
sh_hits, sh_stats = flat_index.bss_query_batched(idx_sh, queries, t)
assert sh_hits == hits  # identical to the single-device fused engine
sh_knn, sh_kd, sh_kstats = flat_index.bss_knn_batched(idx_sh, queries, k=5)
assert all(
    set(a.tolist()) == set(b.tolist()) for a, b in zip(sh_knn, knn_idx)
)
print(
    f"sharded BSS over {sh_stats['n_shards']} devices: "
    f"{sh_stats['dists_per_query']:.0f} distances/query — hits and counts "
    f"== single-device engine"
)

# 9. async serving: the engines above take pre-assembled batches, but live
#    traffic arrives one query at a time.  ServingFront assembles the
#    batches itself — submit() returns a Future immediately, a driver
#    thread collects requests under a deadline, pads each micro-batch to a
#    fixed bucket ladder (so jit recompiles are bounded by the ladder, not
#    the traffic), and dispatches through the SAME fused engines: results
#    are bit-identical to direct engine calls.  Range requests may each
#    carry their own threshold (served via per-query radii in one batch);
#    stats() snapshots queue wait / batch sizes / padding waste.
from repro.serve.front import ServingFront  # noqa: E402

with ServingFront(idx, max_delay_s=0.005) as front:
    futures = [front.submit(qv, "range", t=t * (1 + 0.2 * (i % 2)))
               for i, qv in enumerate(queries[:20])]
    futures += [front.submit(qv, "knn", k=5) for qv in queries[:10]]
    answers = [f.result(timeout=120) for f in futures]
assert answers[0].hits == hits[0]  # == the direct fused call of step 4
fstats = front.stats()
print(
    f"async front: {fstats['completed']} requests in {fstats['batches']} "
    f"micro-batches (mean batch {fstats['batch_size_mean']:.1f}, "
    f"p95 queue wait {1e3 * fstats['queue_wait_s']['p95']:.1f}ms) — "
    f"results == direct engine calls"
)

# 10. bf16 exact phase: precision="bf16" streams a bfloat16 mirror of the
#     corpus through the exact phase (half the HBM bytes per evaluated
#     point) and re-checks only the comparison-margin boundary band
#     |d - t| <= eps in fp32 — so hits, kNN results AND per-query distance
#     counts stay bit-identical to the fp32 engine.  eps comes from the
#     measured rounding displacement: eps = 2*max_p d(p, p~) + a small
#     fp32-arithmetic term (see repro/core/precision.py).
h16, s16 = flat_index.bss_query_batched(
    idx, queries, t, opts=EngineOpts(precision="bf16"))
assert h16 == hits  # bit-identical to the fp32 engine of step 4
assert (s16["per_query_dists"] == stats["per_query_dists"]).all()
print(
    f"bf16 exact phase: hits + counts == fp32 engine, band eps="
    f"{s16['band_eps']:.2e}, {s16['recheck_points_per_query']:.1f} "
    f"fp32 re-checked points/query"
)

# 11. the invariant checker: everything above leans on conventions (no
#     host syncs inside the jitted engines, fp32/bf16 only, monotonic
#     timing, tile sizes routed through repro.kernels.tiles).  The AST
#     lint enforces them in milliseconds; `python -m repro.analysis`
#     additionally traces every engine entry point and audits the jaxprs
#     (no f64, no callbacks, bf16 confinement, bounded recompiles).
from pathlib import Path  # noqa: E402

from repro.analysis.lint import lint_repo  # noqa: E402
from repro.analysis.rules import load_allowlist  # noqa: E402

repo_root = Path(__file__).resolve().parents[1]
violations = lint_repo(repo_root, load_allowlist())
for v in violations:
    print(v.format())
assert not violations
print("invariant lint: clean (run `python -m repro.analysis` for the "
      "full jaxpr audit)")

# 12. observability: the front (and every engine) reports what it pruned
#     and why.  Device-side counts (per-mechanism exclusion attribution,
#     tile counts, bf16 re-check volume) are FUNCTIONAL jit outputs in the
#     stats dicts — no callbacks, nothing the invariant checker of step 11
#     would reject, and provably zero effect on results — folded into a
#     metrics registry at the jit boundary.  front.metrics().render() is
#     the one-screen dashboard (.to_prometheus() the scrape endpoint), and
#     front.explain(trace_id) replays one request: stage-by-stage span
#     timings plus that row's share of the batch accounting.
with ServingFront(idx, max_delay_s=0.005) as front:
    answers = [front.submit(qv, "range", t=t).result(timeout=120)
               for qv in queries[:8]]
    print(front.metrics().render())
    trace = front.explain(answers[0].trace_id)
assert answers[0].hits == hits[0]  # metrics on: results still identical
print(
    f"explain {trace['trace_id']}: {trace['n_dists']} exact distances, "
    f"excluded {trace['excluded']} blocks, span total "
    f"{1e3 * trace['spans']['total']:.1f}ms "
    f"(engine {1e3 * trace['spans']['engine']:.1f}ms)"
)

# 13. living corpus: the index of step 4 is not frozen.  append() packs new
#     rows into fresh blocks against the EXISTING pivots (m x P distances,
#     no rebuild), delete() tombstones, compact() re-permutes the layout —
#     and every mutation bumps a monotonic generation the front swaps
#     between micro-batches (in-flight queries finish on their snapshot,
#     the answer cache keys on the generation, so nothing stale is ever
#     served).  Results after any mutation are bit-identical to a fresh
#     build_bss over the same live rows.
new_rows = metricsets.colors_surrogate(512, dim=64, seed=7)
with ServingFront(idx, max_delay_s=0.005, metrics=True) as front:
    g0 = front.metrics().series()
    gen0 = int(next(s.value for s in g0 if s.name == "index/generation"))
    ms_a = front.append(new_rows)
    grown = [front.submit(qv, "range", t=t).result(timeout=120)
             for qv in queries[:8]]
    ms_d = front.delete(np.arange(64))
    ms_c = front.compact()
    g1 = front.metrics().series()
    gen1 = int(next(s.value for s in g1 if s.name == "index/generation"))
    final = [front.submit(qv, "range", t=t).result(timeout=120)
             for qv in queries[:8]]
    live_index = front.index
assert gen1 == gen0 + 3  # append, delete, compact: one generation each
assert all(r.generation == gen1 for r in final)
new_ids = len(db) + np.arange(len(new_rows))  # appended rows: ids next_id..
live_ids = np.concatenate([np.arange(64, len(db)), new_ids])
fresh = flat_index.build_bss(
    "l2", np.concatenate([db[64:], new_rows]), n_pivots=16, n_pairs=24,
    block=128, seed=idx.seed,
)
fresh_hits, _ = flat_index.bss_query_batched(fresh, queries[:8], t)
remap = [sorted(live_ids[j] for j in h) for h in fresh_hits]
assert [sorted(r.hits) for r in final] == remap  # == fresh rebuild
print(
    f"living corpus: +{ms_a.rows} rows ({ms_a.new_blocks} new blocks, "
    f"{ms_a.table_dists} table distances), -{ms_d.rows} tombstoned, "
    f"compacted to {ms_c.n_blocks} blocks — generation {gen0} -> {gen1}, "
    f"results == fresh rebuild over the live rows"
)

# 14. performance tracing: everything the front does — each request's
#     queue/batch/engine/demux span slices, the driver's per-dispatch
#     phases, every index mutation — lands in one trace buffer on one
#     monotonic clock.  export_trace() writes Chrome trace-event JSON:
#     open it at https://ui.perfetto.dev (or chrome://tracing) and each
#     request is its own track, with mutations inline on the driver
#     track.  The dispatch and engine phases are also profiler
#     annotations: wrap any stretch of serving in jax.profiler.trace(...)
#     and they appear on its host plane beside the device operations,
#     on the same clock as ServeResult.batch.spans.  On a sharded index
#     the same stats carry per-shard work splits — the shard/imbalance
#     gauge in render() (max/mean, 1.0 = perfectly balanced) is the row a
#     rebalancing policy would watch.
from repro.obs import load_trace, validate_trace  # noqa: E402

with ServingFront(idx, max_delay_s=0.005) as front:
    for qv in queries[:8]:
        front.submit(qv, "range", t=t).result(timeout=120)
    front.append(metricsets.colors_surrogate(256, dim=64, seed=8))
    front.submit(queries[0], "knn", k=5).result(timeout=120)
    trace_path = front.export_trace("TRACE_quickstart.json")
payload = load_trace(trace_path)
assert validate_trace(payload) == []
kinds = {e["name"] for e in payload["traceEvents"]}
assert {"queue", "engine", "demux", "dispatch/engine",
        "mutation/append"} <= kinds
print(
    f"trace: {len(payload['traceEvents'])} events -> {trace_path} "
    "(load in https://ui.perfetto.dev; benchmarks/regress.py watches "
    "the matching BENCH_* numbers for regressions in CI)"
)
