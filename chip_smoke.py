#!/usr/bin/env python3
"""Chip smoke: the served exact-search path, once, on one TPU chip.

    python3 chip_smoke.py [--seed 0]      # one chip, every phase
    python3 chip_smoke.py --chips 4       # the sharded engine on 4 chips only

Drives the entry points a user calls, at deployment size, on data made from
``--seed``, all in this one process (a chip belongs to one process):

* ``l2``: ``build_bss`` over a 1,000,000 x 128 clustered corpus (the shape
  of ANN-benchmarks ``sift-128-euclidean``) -> ``ServingFront.submit`` ->
  ``Future.result()``; range at ~1e-4 selectivity and kNN (k=10), in fp32
  and bf16, in bursts that dispatch both the 8-row and the 512-row bucket.
* ``cosine``: ``RetrievalServer(corpus).search`` (the server's default
  metric) over the same rows, range and kNN.
* ``jsd`` / ``triangular``: the front over a 1,000,000 x 64 topic-histogram
  corpus, range and kNN, fp32.
* ``forest``: ``RetrievalServer(index="forest")`` (``hpt_fft_log``, Hilbert
  exclusion) over the 112,682 x 112 colors corpus at the paper's Table 3
  threshold 0.083.

Every phase checks at least 32 queries against a float64 numpy brute-force
scan: range hit sets equal, kNN index sets equal.  The engines compute in
float32 and are exact wherever float32 and float64 agree on ``d <= t`` (and
on the k-th/(k+1)-th order); so the range threshold is placed in a gap of
the checked queries' float64 distances, and a query with a float64
distance within ``MARGIN`` (relative) of the threshold, or a k-th/(k+1)-th
pair closer than that, is not among the checked ones (the count set aside
is printed).

``--chips 4`` runs only the sharded engine (``ShardedBSSIndex`` over a
``("data",)`` mesh of 4 devices) on the same l2 corpus, range and kNN, and
requires hits and per-query distance counts identical to the single-device
engine on device 0, with every shard's rows on its own device.

It exits non-zero, printing no result line, when JAX finds no TPU, when a
phase resolves a backend other than ``"pallas"`` or runs Pallas in
interpret mode, on any mismatch and on any exception.  The timings it
prints are smoke timings (cold = first burst, compilation included; warm =
a repeat), not benchmark metrics.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import os
import resource
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MARGIN = 2e-5  # relative distance band treated as a float32/float64 tie
N_CHECK = 32
BURST = 512
SMALL = 8
K = 10
SELECTIVITY = 1e-4


class SmokeFailure(Exception):
    pass


def _say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def _oracle_dists(metric: str, queries: np.ndarray, corpus: np.ndarray,
                  chunk: int = 2048) -> np.ndarray:
    """(Q, N) float64 brute-force distances, corpus chunks in threads
    (numpy releases the GIL in its ufuncs).  A JSD chunk holds ~0.4 GB
    of float64 temporaries, so the pool stays small."""
    from repro.core.npdist import pairwise_np

    out = np.empty((len(queries), len(corpus)), np.float64)

    def one(lo: int) -> None:
        out[:, lo:lo + chunk] = pairwise_np(metric, queries,
                                            corpus[lo:lo + chunk])

    workers = min(4, os.cpu_count() or 1)
    with cf.ThreadPoolExecutor(workers) as pool:
        list(pool.map(one, range(0, len(corpus), chunk)))
    return out


def _widest_gap_mid(vals: np.ndarray) -> float:
    """Midpoint of the widest gap between consecutive sorted ``vals``."""
    i = int(np.argmax(np.diff(vals)))
    return float(0.5 * (vals[i] + vals[i + 1]))


class Oracle:
    """float64 brute force over a pool of queries: range hit sets, kNN
    index sets, and which pool queries are clear of float32 ties."""

    def __init__(self, metric: str, pool: np.ndarray, corpus: np.ndarray):
        self.d = _oracle_dists(metric, pool, corpus)

    def per_query_t(self, selectivity: float) -> np.ndarray:
        """(pool,) radii: each query's own ~``selectivity`` quantile (within
        a factor 1.5), moved to the middle of the widest gap between its
        float64 distances there."""
        r = max(2, int(round(selectivity * self.d.shape[1])))
        out = np.empty(len(self.d))
        for i, row in enumerate(self.d):
            near = np.sort(np.partition(row, 2 * r)[: 2 * r + 1])
            out[i] = _widest_gap_mid(near[r // 2 : 3 * r // 2 + 1])
        return out

    def pooled_t(self, selectivity: float) -> float:
        """One radius returning ``selectivity`` of the corpus per query on
        average over the pool, moved to the middle of the widest gap of
        the pool's distances within 0.5% of it."""
        flat = self.d.reshape(-1)
        target = float(np.partition(flat, int(selectivity * flat.size))[
            int(selectivity * flat.size)])
        near = np.sort(flat[np.abs(flat - target) <= 0.005 * target])
        return _widest_gap_mid(near) if near.size > 1 else target

    def range_hits(self, t) -> list[list[int]]:
        t = np.broadcast_to(np.asarray(t, np.float64), (len(self.d),))
        return [sorted(np.nonzero(row <= ti)[0].tolist())
                for row, ti in zip(self.d, t)]

    def knn(self, k: int) -> tuple[list[set], np.ndarray]:
        """Per query: the k nearest ids, and the relative gap between the
        k-th and (k+1)-th distance."""
        part = np.argpartition(self.d, k, axis=1)[:, : k + 1]
        sets, gaps = [], []
        for row, ids in zip(self.d, part):
            ids = ids[np.argsort(row[ids], kind="stable")]
            sets.append(set(ids[:k].tolist()))
            kth, nxt = row[ids[k - 1]], row[ids[k]]
            gaps.append((nxt - kth) / max(kth, 1e-30))
        return sets, np.asarray(gaps)

    def clear_of(self, t) -> np.ndarray:
        """Pool queries with no float64 distance within MARGIN of their
        radius."""
        t = np.broadcast_to(np.asarray(t, np.float64), (len(self.d),))
        return ~np.any(
            np.abs(self.d - t[:, None]) <= MARGIN * t[:, None], axis=1
        )


def _pick(clear: np.ndarray, what: str) -> np.ndarray:
    rows = np.nonzero(clear)[0][:N_CHECK]
    if rows.size < N_CHECK:
        raise SmokeFailure(
            f"{what}: only {rows.size} of {clear.size} pool queries are "
            f"clear of float32 ties; need {N_CHECK}"
        )
    _say(f"{what}: checking {rows.size} queries "
         f"({int((~clear[: rows[-1] + 1]).sum())} set aside as float32 ties)")
    return rows


def _check_range(what: str, got: list, want: list, rows) -> None:
    for i in rows:
        if sorted(got[i]) != want[i]:
            raise SmokeFailure(
                f"{what}: query {i} hits differ from the float64 oracle "
                f"(got {len(got[i])}, want {len(want[i])})"
            )


def _check_knn(what: str, got: np.ndarray, want: list, rows) -> None:
    for i in rows:
        if set(np.asarray(got[i]).tolist()) != want[i]:
            raise SmokeFailure(
                f"{what}: query {i} kNN ids differ from the float64 oracle"
            )


def _host_mem() -> str:
    """This process's resident host memory and, where readable, its
    cgroup's charge (what an out-of-memory kill is decided on), GiB."""
    def gib(path: str, field: int = 0) -> str:
        try:
            with open(path) as fh:
                v = int(fh.read().split()[field])
        except (OSError, ValueError, IndexError):
            return "n/a"
        scale = os.sysconf("SC_PAGE_SIZE") if path.endswith("statm") else 1
        return f"{v * scale / 2**30:.1f}"

    cgroup = gib("/sys/fs/cgroup/memory.current")
    if cgroup == "n/a":
        cgroup = gib("/sys/fs/cgroup/memory/memory.usage_in_bytes")
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    return (f"host_rss_gib={gib('/proc/self/statm', 1)} "
            f"peak_rss_gib={peak:.1f} cgroup_gib={cgroup}")


def _peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _require_pallas(what: str, backend: str) -> None:
    from repro.kernels import pairwise_dist, planar_exclusion

    if backend != "pallas":
        raise SmokeFailure(f"{what}: resolved backend {backend!r}, not pallas")
    if pairwise_dist._interpret_default() or planar_exclusion._interpret_default():
        raise SmokeFailure(f"{what}: Pallas interpret mode is on")


def _report(what: str, *, rows: int, backend: str, cold: float, warm: float,
            dists: float) -> None:
    _say(f"{what}: rows={rows} backend={backend} cold_s={cold:.3f} "
         f"warm_s={warm:.3f} dists_per_query={dists:.1f} "
         f"peak_bytes_in_use={_peak_bytes()} {_host_mem()} (smoke timings)")


def _front_burst(front, queries, kind: str, ts=None, **kw):
    """Submit one request per query (range: ``ts[i]`` is query i's own
    radius) and wait for every future."""
    t0 = time.perf_counter()
    futs = [
        front.submit(q, kind, **kw) if ts is None
        else front.submit(q, kind, t=float(ts[i]), **kw)
        for i, q in enumerate(queries)
    ]
    res = [f.result() for f in futs]
    return res, time.perf_counter() - t0


def _front_phase(what: str, front, queries, pool_rows, kind: str,
                 oracle_want, **kw) -> None:
    """Cold 512-burst, 8-burst, warm 512-burst through the front; checks
    the oracle rows of every burst."""
    from repro.core.backends import resolve_backend

    backend = resolve_backend(front.opts.backend)
    _require_pallas(what, backend)
    cold_res, cold = _front_burst(front, queries, kind, **kw)
    small_kw = dict(kw, ts=kw["ts"][:SMALL]) if "ts" in kw else kw
    small_res, _ = _front_burst(front, queries[:SMALL], kind, **small_kw)
    warm_res, warm = _front_burst(front, queries, kind, **kw)
    small_rows = [i for i in pool_rows if i < SMALL]
    for res, rows in ((cold_res, pool_rows), (small_res, small_rows),
                      (warm_res, pool_rows)):
        if kind == "range":
            _check_range(what, [r.hits for r in res], oracle_want, rows)
        else:
            _check_knn(what, [r.indices for r in res], oracle_want, rows)
    buckets = {r.padded_to for r in cold_res + small_res}
    if not {SMALL, BURST} <= buckets:
        raise SmokeFailure(f"{what}: buckets dispatched {sorted(buckets)}")
    dists = float(np.mean([r.n_dists for r in warm_res]))
    _report(what, rows=front.index.n_valid, backend=backend, cold=cold,
            warm=warm, dists=dists)


def _bss_front_phases(name: str, metric: str, corpus, queries, seed: int,
                      precisions) -> None:
    from repro.core import flat_index
    from repro.serve.front import ServingFront

    t0 = time.perf_counter()
    index = flat_index.build_bss(metric, corpus, seed=seed)
    _say(f"{name}: build_bss {corpus.shape} in "
         f"{time.perf_counter() - t0:.1f}s ({index.n_blocks} blocks, "
         f"{_host_mem()})")
    oracle = Oracle(metric, queries[: 2 * N_CHECK], corpus)
    # per-request radii (the front batches mixed thresholds): each pool
    # query at its own ~1e-4 quantile, the other requests at their median
    t_pool = oracle.per_query_t(SELECTIVITY)
    ts = np.full(len(queries), np.median(t_pool))
    ts[: len(t_pool)] = t_pool
    hits = oracle.range_hits(t_pool)
    range_rows = _pick(oracle.clear_of(t_pool), f"{name}/range ({_host_mem()})")
    _say(f"{name}/range: median t {float(np.median(t_pool))!r}, mean oracle hits "
         f"{np.mean([len(h) for h in hits]):.1f}")
    knn_sets, gaps = oracle.knn(K)
    knn_rows = _pick(gaps > MARGIN, f"{name}/knn k={K}")
    # a long deadline: a whole burst lands in one batch of its bucket
    with ServingFront(index, max_delay_s=0.5) as front:
        for precision in precisions:
            _front_phase(f"{name}/range/{precision}", front, queries,
                         range_rows, "range", hits, ts=ts,
                         precision=precision)
            _front_phase(f"{name}/knn/{precision}", front, queries, knn_rows,
                         "knn", knn_sets, k=K, precision=precision)
    if metric == "l2":
        _dense_copy_time(index, queries, ts)


def _dense_copy_time(index, queries, ts: np.ndarray) -> None:
    """Wall time of the dense range path's device->host copy of the
    (Q, n_pad) fp32 distance matrix (a measured cost for the benchmark)."""
    import jax.numpy as jnp

    from repro.core import flat_index

    q = jnp.asarray(queries, jnp.float32)
    t_vec = jnp.asarray(ts, jnp.float32)
    dist, _, _ = flat_index._query_batched_jit(
        "l2", q, t_vec, index.device, block=index.block,
        bq=flat_index._DEFAULT_BQ, backend="pallas", interpret=None,
    )
    dist.block_until_ready()
    t0 = time.perf_counter()
    host = np.asarray(dist)
    dt = time.perf_counter() - t0
    _say(f"l2/range dense host copy: {host.shape} fp32 "
         f"({host.nbytes / 2**20:.0f} MiB) in {dt:.3f}s (smoke timing)")


def _cosine_phase(corpus, queries, seed: int) -> None:
    from repro.core.backends import resolve_backend
    from repro.serve.retrieval import RetrievalServer

    what = "cosine"
    server = RetrievalServer(corpus, seed=seed)
    # one radius for the whole call (search takes a scalar t): a larger
    # pool, so that enough queries are clear of float32 ties
    oracle = Oracle("cosine", queries[: 4 * N_CHECK], corpus)
    t = oracle.pooled_t(SELECTIVITY)
    hits = oracle.range_hits(t)
    range_rows = _pick(oracle.clear_of(t), f"{what}/range t={t!r}")
    knn_sets, gaps = oracle.knn(K)
    knn_rows = _pick(gaps > MARGIN, f"{what}/knn k={K}")
    backend = resolve_backend(server.opts.backend)
    _require_pallas(what, backend)
    for kind, kw in (("range", {"t": t}), ("knn", {"k": K})):
        t0 = time.perf_counter()
        res = server.search(queries, kind, **kw)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = server.search(queries, kind, **kw)
        warm = time.perf_counter() - t0
        _require_pallas(f"{what}/{kind}", res.stats["backend"])
        if kind == "range":
            _check_range(f"{what}/range", res.hits, hits, range_rows)
        else:
            _check_knn(f"{what}/knn", res.indices, knn_sets, knn_rows)
        _report(f"{what}/{kind}/fp32", rows=len(corpus), backend=backend,
                cold=cold, warm=warm, dists=res.stats["dists_per_query"])


def _forest_phase(seed: int, n: int = 112_682) -> None:
    from repro.configs.supermetric import SISAP_COLORS
    from repro.core.backends import resolve_backend
    from repro.data import metricsets
    from repro.serve.retrieval import RetrievalServer

    what = "forest/colors"
    data = metricsets.colors_surrogate(n + BURST, seed=seed).astype(np.float32)
    corpus, queries = data[:n], data[n:]
    t = SISAP_COLORS.thresholds[1]  # 0.083, the paper's Table 3
    t0 = time.perf_counter()
    server = RetrievalServer(corpus, metric="l2", index="forest",
                             forest_variant=SISAP_COLORS.tree_variant,
                             forest_mechanism=SISAP_COLORS.exclusion,
                             seed=seed)
    _say(f"{what}: build {SISAP_COLORS.tree_variant} {corpus.shape} in "
         f"{time.perf_counter() - t0:.1f}s")
    oracle = Oracle("l2", queries[: 4 * N_CHECK], corpus)
    hits = oracle.range_hits(t)
    rows = _pick(oracle.clear_of(t), f"{what}/range t={t}")
    backend = resolve_backend(server.opts.backend)
    _require_pallas(what, backend)
    t0 = time.perf_counter()
    res = server.search(queries, "range", t=t)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = server.search(queries, "range", t=t)
    warm = time.perf_counter() - t0
    _require_pallas(what, res.stats["backend"])
    _check_range(what, res.hits, hits, rows)
    _report(f"{what}/range/fp32", rows=n, backend=backend, cold=cold,
            warm=warm, dists=res.stats["dists_per_query"])


def one_chip(seed: int, n: int = 1_000_000, n_colors: int = 112_682) -> None:
    from repro.data import metricsets

    t0 = time.perf_counter()
    sift = metricsets.sift_surrogate(n + BURST, seed=seed)
    corpus, queries = sift[:n], sift[n:]
    _say(f"l2: corpus {corpus.shape} made in {time.perf_counter() - t0:.1f}s, "
         f"{_host_mem()}")
    _bss_front_phases("l2", "l2", corpus, queries, seed, ("fp32", "bf16"))
    _cosine_phase(corpus, queries, seed)
    del sift, corpus, queries
    topics = metricsets.topics_surrogate(n + BURST, 64, seed=seed)
    topics = topics.astype(np.float32)
    corpus, queries = topics[:n], topics[n:]
    for metric in ("jsd", "triangular"):
        _bss_front_phases(metric, metric, corpus, queries, seed, ("fp32",))
    del topics, corpus, queries
    _forest_phase(seed, n_colors)


def four_chips(seed: int, n: int = 1_000_000) -> None:
    """The sharded engine over a ("data",) mesh of 4 devices against the
    single-device engine on device 0 (hits and per-query counts)."""
    import jax
    from jax.sharding import Mesh

    from repro.core import flat_index
    from repro.core.backends import EngineOpts
    from repro.data import metricsets
    from repro.parallel.shard_index import (
        sharded_knn_batched,
        sharded_query_batched,
    )

    devs = jax.devices()
    if len(devs) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    sift = metricsets.sift_surrogate(n + BURST, seed=seed)
    corpus, queries = sift[:n], sift[n:]
    index = flat_index.build_bss("l2", corpus, seed=seed)
    mesh = Mesh(np.array(devs[:4]), ("data",))
    sidx = index.sharded(mesh)
    for shard in sidx.dev.data.addressable_shards:
        lo = shard.index[0].start or 0
        want_dev = devs[lo // sidx.rows_per_shard]
        if shard.device != want_dev or shard.data.shape[0] != sidx.rows_per_shard:
            raise SmokeFailure(
                f"shard rows {lo}.. on {shard.device}, expected {want_dev}"
            )
    if len({s.device for s in sidx.dev.data.addressable_shards}) != 4:
        raise SmokeFailure("corpus shards do not sit on 4 distinct devices")
    _say(f"sharded: {sidx.n_shards} shards x {sidx.rows_per_shard} rows, "
         f"each on its own device")
    oracle = Oracle("l2", queries[:SMALL], corpus)
    t = np.full(len(queries), np.median(oracle.per_query_t(SELECTIVITY)),
                np.float32)
    # the sharded engine always runs the dense exact phase; pin the
    # single-device engine to it too (the pallas backend has no other)
    dense = EngineOpts(realisation="dense")
    for kind in ("range", "knn"):
        if kind == "range":
            single = lambda: flat_index.bss_query_batched(  # noqa: E731
                index, queries, t, opts=dense)
            sharded = lambda: sharded_query_batched(  # noqa: E731
                sidx, queries, t)
        else:
            single = lambda: flat_index.bss_knn_batched(  # noqa: E731
                index, queries, K, opts=dense)
            sharded = lambda: sharded_knn_batched(  # noqa: E731
                sidx, queries, K)
        want = single()
        t0 = time.perf_counter()
        got = sharded()
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = sharded()
        warm = time.perf_counter() - t0
        ws, gs = want[-1], got[-1]
        _require_pallas(f"sharded/{kind}", gs["backend"])
        _require_pallas(f"single/{kind}", ws["backend"])
        same = (want[0] == got[0]) if kind == "range" else (
            np.array_equal(want[0], got[0]) and np.array_equal(want[1], got[1])
        )
        if not same:
            raise SmokeFailure(f"sharded/{kind}: results differ from device 0")
        if not np.array_equal(ws["per_query_dists"], gs["per_query_dists"]):
            raise SmokeFailure(
                f"sharded/{kind}: per-query distance counts differ"
            )
        _say(f"sharded/{kind}/fp32: identical to device 0 over "
             f"{len(queries)} queries; shard_dists={gs['shard_dists'].tolist()}")
        _report(f"sharded/{kind}/fp32", rows=n, backend=gs["backend"],
                cold=cold, warm=warm, dists=gs["dists_per_query"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repo ({e})",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 1
    _say(f"{len(devs)} x {devs[0].device_kind}, compile cache {cache}, "
         f"{_host_mem()}, LD_PRELOAD={os.environ.get('LD_PRELOAD', '')!r}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    _say(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
