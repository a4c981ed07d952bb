"""Observability layer tests (repro.obs + the serving wiring).

The load-bearing guarantee is the ISSUE-8 acceptance bar: collecting
metrics must change NOTHING — a metrics-on front and a metrics-off front
return bit-identical results on all four supermetrics, and the
instrumented engine jits still contain zero callback primitives (the
device-side counters are functional outputs, not debug hooks).  Around
that: registry/histogram unit semantics, the shared stats schema on real
engine output, exclusion-attribution cross-checks, spans/explain, the
exposition round-trip, and the recompile counter.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import flat_index, tree
from repro.core.backends import EngineOpts, jit_cache_size
from repro.core.npdist import pairwise_np
from repro.forest import encode_tree, forest_range_search
from repro.obs import (
    DEFAULT_LADDER,
    MECHANISMS,
    METRIC_NAMES,
    MetricsRegistry,
    Span,
    TraceBuffer,
    check_stats,
    complete_event,
    fold_engine_stats,
    instant_event,
    ladder_for,
    load_trace,
    log_ladder,
    metadata_event,
    metric_key,
    new_trace_id,
    parse_prometheus,
    poll_compile,
    shard_imbalance,
    validate_exposition,
    validate_stats,
    validate_trace,
    write_snapshot,
    write_trace,
)
from repro.serve.front import ServingFront

_DENSE = EngineOpts(realisation="dense")

DIM = 12


def _space(metric: str, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = rng.random((n, DIM)).astype(np.float32) + 1e-3
    if metric in ("jsd", "triangular"):
        x /= x.sum(axis=1, keepdims=True)
    return x


def _snap(dvals: np.ndarray, frac: float) -> float:
    vals = np.unique(np.sort(np.asarray(dvals, np.float64).ravel()))
    i = int(np.clip(frac * len(vals), 0, len(vals) - 2))
    for j in range(i, len(vals) - 1):
        if vals[j + 1] - vals[j] > 1e-4 * max(1.0, vals[j]):
            return float(0.5 * (vals[j] + vals[j + 1]))
    return float(vals[-1] + 1.0)


# ---------------------------------------------------------------- registry


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("engine/dists", engine="bss", kind="range")
    c.inc(5)
    c.inc()
    assert c.value == 6.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    g = reg.gauge("compile/cache_size", fn="lb")
    g.set(3)
    g.set(2)  # gauges go down
    assert g.value == 2.0
    # same (name, labels) -> the same live series
    assert reg.counter("engine/dists", kind="range", engine="bss") is c


def test_metric_key_is_canonical():
    assert metric_key("m", {}) == "m"
    assert metric_key("m", {"b": 1, "a": "x"}) == "m{a=x,b=1}"
    assert metric_key("m", {"a": "x", "b": 1}) == metric_key(
        "m", {"b": 1, "a": "x"}
    )


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("x")


def test_histogram_ring_units():
    """Percentiles are a WINDOW statistic over the bounded ring; count/sum
    are lifetime tallies that survive ring eviction."""
    reg = MetricsRegistry()
    h = reg.histogram("serve/span_s", window=4, stage="queue")
    for v in range(1, 11):
        h.observe(float(v))
    assert h.count == 10 and h.sum == 55.0
    assert list(h.ring) == [7.0, 8.0, 9.0, 10.0]
    assert h.percentile(0.5) == 8.0  # nearest-rank over the window
    assert h.percentile(0.99) == 10.0
    s = h.summary()
    assert s["count"] == 10 and s["window"] == 4 and s["max"] == 10.0
    with pytest.raises(ValueError, match="window"):
        reg.histogram("serve/span_s", window=8, stage="queue")
    with pytest.raises(ValueError, match="window"):
        MetricsRegistry().histogram("h", window=0)


def test_snapshot_and_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("engine/dists", engine="bss", kind="range").inc(100)
    reg.gauge("compile/ladder_buckets").set(4)
    h = reg.histogram("serve/engine_s", kind="range")
    h.observe(0.25)
    h.observe(0.75)
    snap = reg.snapshot()
    assert snap["counters"]["engine/dists{engine=bss,kind=range}"] == 100.0
    assert snap["gauges"]["compile/ladder_buckets"] == 4.0
    assert snap["histograms"]["serve/engine_s{kind=range}"]["count"] == 2
    json.loads(reg.to_json())  # JSON-serialisable as claimed

    text = reg.to_prometheus()
    assert validate_exposition(text) == []
    samples = parse_prometheus(text)
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["engine_dists"] == [
        ({"engine": "bss", "kind": "range"}, 100.0)
    ]
    assert by_name["serve_engine_s_count"][0][1] == 2.0
    assert by_name["serve_engine_s_sum"][0][1] == 1.0
    # real cumulative buckets: monotone counts over the le ladder ending
    # at +Inf == _count (0.25 and 0.75 land in adjacent seconds buckets)
    buckets = by_name["serve_engine_s_bucket"]
    counts = [v for _, v in buckets]
    assert counts == sorted(counts) and counts[-1] == 2.0
    by_le = {lbl["le"]: v for lbl, v in buckets}
    assert by_le["+Inf"] == 2.0
    assert by_le["0.1"] == 0.0
    assert by_le["0.316227766"] == 1.0 and by_le["1"] == 2.0
    assert "# TYPE engine_dists counter" in text
    assert "# TYPE serve_engine_s histogram" in text


def test_prometheus_label_escaping_parses_back():
    reg = MetricsRegistry()
    reg.counter("m", path='a"b\\c').inc(1)
    samples = parse_prometheus(reg.to_prometheus())
    assert samples[0][1] == {"path": 'a"b\\c'}


def test_prometheus_malformed_label_values_round_trip():
    """Text-format spec escapes: backslash, double-quote AND newline must
    survive exposition -> parse, including the adversarial ``\\n``
    (escaped backslash followed by a literal n), which a sequential
    str.replace unescaper corrupts into a newline."""
    nasty = {
        "newline": "a\nb",
        "backslash_n": "a\\nb",   # literal backslash + 'n', NOT a newline
        "mixed": 'q"\\\n"end',
    }
    reg = MetricsRegistry()
    for key, val in nasty.items():
        reg.counter("m", which=key, v=val).inc(1)
    text = reg.to_prometheus()
    assert validate_exposition(text) == []
    got = {lbl["which"]: lbl["v"] for _, lbl, _ in parse_prometheus(text)}
    assert got == nasty
    # every exposition line is a single sample line (newlines escaped)
    assert all(
        line.startswith(("#", "m{")) for line in text.strip().splitlines()
    )


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError, match="malformed"):
        parse_prometheus("this is not a sample line{")
    with pytest.raises(ValueError, match="non-numeric"):
        parse_prometheus("ok_name notanumber")


def test_render_groups_by_prefix():
    reg = MetricsRegistry()
    reg.counter("engine/dists").inc(7)
    reg.histogram("serve/engine_s").observe(0.5)
    out = reg.render()
    assert "== engine " in out and "== serve " in out
    assert "engine/dists" in out and "p95=" in out
    assert MetricsRegistry().render() == "(no metrics recorded)"


# ------------------------------------------------------------------- spans


def test_span_marks_and_durations():
    sp = Span()
    for i, stage in enumerate(("admit", "batch", "dispatch", "engine",
                               "demux")):
        sp.mark(stage, t=10.0 + i)
    d = sp.durations()
    assert d == {"queue": 1.0, "batch": 1.0, "engine": 1.0, "demux": 1.0,
                 "total": 4.0}
    with pytest.raises(ValueError, match="unknown stage"):
        sp.mark("teleport")


def test_span_partial_marks():
    sp = Span()
    sp.mark("admit", t=1.0)
    assert sp.durations() == {}  # one mark, no interval
    sp.mark("engine", t=3.0)  # batch/dispatch never marked
    d = sp.durations()
    assert d == {"admit_to_engine": 2.0, "total": 2.0}


def test_trace_ids_unique_and_sortable():
    ids = [new_trace_id() for _ in range(5)]
    assert len(set(ids)) == 5
    assert ids == sorted(ids)  # zero-padded -> lexicographic == numeric


# ----------------------------------------------- schema on real engine stats


def _bss_built(metric="l2"):
    data = _space(metric, 660, seed=3)
    db, q = data[:640], data[640:]
    idx = flat_index.build_bss(metric, db, n_pivots=8, n_pairs=10,
                               block=64, seed=5)
    t = _snap(pairwise_np(metric, q, db), 0.04)
    return idx, db, q, t


def test_bss_stats_conform_and_cross_check():
    idx, db, q, t = _bss_built()
    hits, stats = flat_index.bss_query_batched(idx, q, t, opts=_DENSE)
    check_stats(stats)
    assert stats["engine"] == "bss" and stats["kind"] == "range"
    # attribution cross-check: the scan's only mechanism is the Hilbert
    # four-point bound, so excluded blocks == blocks whose lower bound
    # clears the radius
    lb = flat_index.bss_lower_bounds(idx, q)
    expect = (np.asarray(lb) > t).sum(axis=1)
    assert (stats["excluded"]["hilbert"] == expect).all()

    _, _, ks = flat_index.bss_knn_batched(idx, q, 4, opts=_DENSE)
    check_stats(ks)
    assert ks["kind"] == "knn" and ks["rounds"] >= 1
    assert set(ks["excluded"]) == {"hilbert"}

    # empty batch still conforms
    _, es = flat_index.bss_query_batched(idx, q[:0], t)
    check_stats(es)
    _, _, eks = flat_index.bss_knn_batched(idx, q[:0], 4)
    check_stats(eks)


def test_bss_bf16_stats_conform():
    idx, db, q, t = _bss_built()
    _, stats = flat_index.bss_query_batched(
        idx, q, t, opts=EngineOpts(realisation="dense", precision="bf16"))
    check_stats(stats)
    assert stats["precision"] == "bf16"
    assert "band_eps" in stats and "recheck_points_per_query" in stats


def test_forest_stats_attribution_and_frontier():
    db = _space("l2", 600, seed=21)
    q = _space("l2", 8, seed=22)
    tr = tree.build_tree("hpt_fft_log", "l2", db, seed=23)
    enc = encode_tree(tr)
    t = _snap(pairwise_np("l2", q, db), 0.04)
    hits, stats = forest_range_search(enc, q, t)
    check_stats(stats)
    assert stats["engine"] == "forest"
    excl = stats["excluded"]
    assert set(excl) <= set(MECHANISMS) and "cover" in excl
    # the walker attributes disjointly (priority cover > hyperplane >
    # centre), so per-mechanism counts are individually sane and the
    # batch pruned *something* at this selective radius
    assert all((v >= 0).all() for v in excl.values())
    assert sum(int(v.sum()) for v in excl.values()) > 0
    assert stats["frontier_occupancy"].shape == (len(enc.levels),)
    assert int(stats["frontier_occupancy"][0]) >= len(q)  # roots all live

    # empty batch conforms with all-zero attribution
    _, es = forest_range_search(enc, q[:0], t)
    check_stats(es)
    assert all(v.shape == (0,) for v in es["excluded"].values())


def test_monotone_stats_conform():
    from repro.core import lrt
    from repro.forest import encode_monotone, monotone_range_search

    db = _space("l2", 500, seed=31)
    q = _space("l2", 6, seed=32)
    mt = lrt.build_monotone_tree("closer", "far", "l2", db, seed=1)
    enc = encode_monotone(mt)
    t = _snap(pairwise_np("l2", q, db), 0.04)
    _, stats = monotone_range_search(enc, q, t)
    check_stats(stats)
    assert stats["engine"] == "monotone"
    assert set(stats["excluded"]) <= set(MECHANISMS)


def test_validator_catches_tampering():
    idx, db, q, t = _bss_built()
    _, stats = flat_index.bss_query_batched(idx, q, t)
    assert validate_stats(stats) == []
    bad = dict(stats)
    bad["excluded"] = {"warp-drive": stats["excluded"]["hilbert"]}
    assert any("warp-drive" in p for p in validate_stats(bad))
    bad = dict(stats)
    bad["excluded"] = {"hilbert": np.zeros(3, np.int64)}  # wrong shape
    assert any("hilbert" in p for p in validate_stats(bad))
    bad = dict(stats)
    bad["dists_per_query"] = stats["dists_per_query"] + 5.0
    assert any("dists_per_query" in p for p in validate_stats(bad))
    bad = dict(stats)
    del bad["engine"]
    assert any("missing core key" in p for p in validate_stats(bad))
    assert validate_stats("nope") == ["stats is str, expected dict"]
    with pytest.raises(ValueError, match="schema violation"):
        check_stats({"schema": 1})


# ----------------------------------------------------------------- folding


def test_fold_engine_stats_counters():
    reg = MetricsRegistry()
    stats = {
        "engine": "bss", "kind": "range", "n_queries": 3,
        "per_query_dists": np.array([10, 20, 30], np.int64),
        "dists_per_query": 20.0,
        "excluded": {"hilbert": np.array([1, 2, 3], np.int64)},
        "tiles_computed": 7, "tile_exclusion_rate": 0.5,
        "frontier_occupancy": np.array([3, 5], np.int64),
        "precision": "fp32",
    }
    fold_engine_stats(reg, stats)
    fold_engine_stats(reg, stats)  # counters accumulate across calls
    snap = reg.snapshot()
    c = snap["counters"]
    assert c["engine/queries{engine=bss,kind=range}"] == 6.0
    assert c["engine/dists{engine=bss,kind=range}"] == 120.0
    assert c["engine/excluded{engine=bss,kind=range,mechanism=hilbert}"] \
        == 12.0
    assert c["engine/tiles_computed{engine=bss,kind=range}"] == 14.0
    assert c["engine/frontier_nodes{engine=bss,kind=range,level=1}"] == 10.0
    assert snap["gauges"]["engine/tile_exclusion_rate{engine=bss,kind=range}"] \
        == 0.5
    h = snap["histograms"]["engine/dists_per_query{engine=bss,kind=range}"]
    assert h["count"] == 6
    # pre-schema dicts fold without error and contribute only what they have
    fold_engine_stats(MetricsRegistry(), {"dists_per_query": 4.0})


def test_poll_compile_counts_growth():
    import jax

    f = jax.jit(lambda x: x + 1)
    if jit_cache_size(f) < 0:
        pytest.skip("this jax exposes no jit cache hook")
    reg = MetricsRegistry()
    f(np.zeros(3, np.float32))
    last = poll_compile(reg, {"f": f})
    f(np.zeros(4, np.float32))  # new shape -> new cache entry
    poll_compile(reg, {"f": f}, last)
    snap = reg.snapshot()
    assert snap["counters"]["compile/recompiles{fn=f}"] == 1.0
    assert snap["gauges"]["compile/cache_size{fn=f}"] == 2.0


# --------------------------------------- metrics-on/off bit-identity (ISSUE)


@pytest.mark.parametrize("metric", ["l2", "cosine", "jsd", "triangular"])
def test_metrics_on_off_bit_identity(metric):
    """The acceptance bar: a metrics-on front and a metrics-off front
    return bit-identical hits, neighbours, distances and counts on every
    supermetric — collection is observation, never perturbation."""
    data = _space(metric, 660, seed=7)
    db, q = data[:640], data[640:]
    idx = flat_index.build_bss(metric, db, n_pivots=8, n_pairs=10,
                               block=64, seed=9)
    t = _snap(pairwise_np(metric, q, db), 0.04)
    k = 4

    def run(metrics_on):
        with ServingFront(idx, buckets=(8, 32), max_delay_s=0.02,
                          metrics=metrics_on) as front:
            futs = [
                front.submit(qv, "knn", k=k) if i % 3 == 1
                else front.submit(qv, "range", t=t)
                for i, qv in enumerate(q)
            ]
            return [f.result(timeout=120) for f in futs]

    on, off = run(True), run(False)
    ref_hits, ref_s = flat_index.bss_query_batched(idx, q, t, opts=_DENSE)
    ref_i, ref_d, _ = flat_index.bss_knn_batched(idx, q, k, opts=_DENSE)
    for i, (a, b) in enumerate(zip(on, off)):
        assert a.n_dists == b.n_dists, (metric, i)
        if i % 3 == 1:
            assert (a.indices == b.indices).all(), (metric, i)
            assert (a.distances == b.distances).all(), (metric, i)
            assert (a.indices == ref_i[i]).all(), (metric, i)
            assert (a.distances == ref_d[i]).all(), (metric, i)
        else:
            assert a.hits == b.hits == ref_hits[i], (metric, i)
            assert a.n_dists == ref_s["per_query_dists"][i], (metric, i)


def test_metrics_off_front_stays_dark():
    idx, db, q, t = _bss_built()
    with ServingFront(idx, max_delay_s=0.01, metrics=False) as front:
        r = front.submit(q[0], "range", t=t).result(timeout=120)
        snap = front.metrics().snapshot()
    assert r.trace_id  # spans always ride the request
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert front.explain() is None


# ------------------------------------------------- spans + explain through


def test_front_spans_and_explain():
    idx, db, q, t = _bss_built()
    with ServingFront(idx, buckets=(8,), max_delay_s=0.01,
                      cache_size=16) as front:
        res = [front.submit(qv, "range", t=t).result(timeout=120)
               for qv in q[:5]]
        hit = front.submit(q[0], "range", t=t).result(timeout=120)
        reg = front.metrics()
        snap = reg.snapshot()
        rec = front.explain(res[2].trace_id)
        latest = front.explain()

    ids = [r.trace_id for r in res]
    assert len(set(ids)) == 5 and all(ids)
    for r in res:
        assert set(r.spans) == {"queue", "batch", "engine", "demux",
                                "total"}
        assert all(v >= 0.0 for v in r.spans.values())
        assert r.spans["total"] >= r.spans["engine"]
    # cache hits keep their own trace but never reach the engine: asking
    # for their id is a KeyError naming the ring capacity
    assert hit.cache_hit and hit.trace_id not in ids
    with pytest.raises(KeyError, match="last 256 dispatched"):
        front.explain(hit.trace_id)

    assert rec is not None and rec["trace_id"] == res[2].trace_id
    assert rec["kind"] == "range" and rec["n_dists"] == res[2].n_dists
    assert set(rec["excluded"]) == {"hilbert"}
    assert rec["excluded"]["hilbert"] >= 0
    assert latest["trace_id"] == res[-1].trace_id

    c = snap["counters"]
    assert c["engine/queries{engine=bss,kind=range}"] == 5.0
    assert c["serve/cache_hits"] == 1.0
    assert snap["histograms"]["serve/batch_size{kind=range}"]["count"] >= 1
    assert any(k.startswith("serve/span_s") for k in snap["histograms"])
    assert snap["gauges"]["compile/ladder_buckets"] >= 1
    assert validate_exposition(reg.to_prometheus()) == []


def test_front_forest_explain_attribution():
    db = _space("l2", 600, seed=41)
    q = _space("l2", 6, seed=42)
    tr = tree.build_tree("hpt_fft_log", "l2", db, seed=43)
    enc = encode_tree(tr)
    t = _snap(pairwise_np("l2", q, db), 0.04)
    with ServingFront(enc, buckets=(8,), max_delay_s=0.01) as front:
        res = [front.submit(qv, "range", t=t).result(timeout=120)
               for qv in q]
        recs = [front.explain(r.trace_id) for r in res]
        snap = front.metrics().snapshot()
    for rec in recs:
        assert rec["engine"] == "forest"
        assert set(rec["excluded"]) <= set(MECHANISMS)
    assert any(
        k.startswith("engine/frontier_nodes") for k in snap["counters"]
    )


# --------------------------------------------------- jaxpr-audit self-check


def test_instrumented_engines_have_zero_callbacks():
    """The obs outputs are functional jit returns: tracing the very entry
    points that now carry the counters shows no callback primitive
    anywhere in their jaxprs (the PR 7 audit, run on the PR 8 engines)."""
    from repro.analysis.jaxpr_audit import (
        _check_no_callbacks,
        _patched_engines,
        _Recorder,
    )

    idx, db, q, t = _bss_built()
    tr = tree.build_tree("hpt_fft_log", "l2", db, seed=51)
    enc = encode_tree(tr)
    rec = _Recorder()
    with _patched_engines(rec):
        flat_index.bss_query_batched(idx, q, t, opts=_DENSE)
        flat_index.bss_knn_batched(idx, q, 3, opts=_DENSE)
        forest_range_search(enc, q, t)
    fns = {c.fn for c in rec.captures}
    assert "_forest_walk_jit" in fns and "_dense_hit_mask_jit" in fns
    assert "_knn_round_jit" in fns
    for cap in rec.captures:
        assert _check_no_callbacks(cap) == [], cap.fn


# ----------------------------------------------------------------- export


def test_write_snapshot(tmp_path):
    reg = MetricsRegistry()
    reg.counter("engine/dists").inc(3)
    p = write_snapshot(reg, tmp_path / "OBS_snapshot.json",
                       extra={"stats": {"x": np.int64(4),
                                        "a": np.arange(2)}})
    payload = json.loads(p.read_text())
    assert payload["metrics"]["counters"]["engine/dists"] == 3.0
    assert payload["stats"] == {"x": 4, "a": [0, 1]}


def test_retrieval_server_folds_metrics():
    from repro.serve.retrieval import RetrievalServer

    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(400, DIM)).astype(np.float32)
    srv = RetrievalServer(corpus, metric="cosine", seed=1)
    q = rng.normal(size=(4, DIM)).astype(np.float32)
    srv.range_query(q, 0.2)
    srv.top_k(q, 3)
    c = srv.metrics.snapshot()["counters"]
    assert c["engine/queries{engine=bss,kind=range}"] == 4.0
    assert c["engine/queries{engine=bss,kind=knn}"] == 4.0
    assert srv.metrics.snapshot()["histograms"]["serve/call_s"]["count"] == 2


# ----------------------------------------------------------------- buckets


def test_log_ladder_shape_and_overrides():
    lad = log_ladder(1e-2, 1e2, per_decade=2)
    assert lad[0] == pytest.approx(1e-2) and lad[-1] == pytest.approx(1e2)
    assert all(a < b for a, b in zip(lad, lad[1:]))
    assert len(lad) == 9  # 4 decades x 2 + endpoint
    # per-metric overrides resolve; unknown names get the default ladder
    assert ladder_for("serve/engine_s") != DEFAULT_LADDER
    assert ladder_for("serve/batch_size") == (1, 2, 4, 8, 16, 32, 64, 128,
                                              256)
    assert ladder_for("not/a_metric") == DEFAULT_LADDER
    with pytest.raises(ValueError, match="lo < hi"):
        log_ladder(10.0, 1.0)


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0, 10.0):  # 10.0 on the boundary: le
        h.observe(v)
    bc = h.bucket_counts()
    assert [le for le, _ in bc] == [1.0, 10.0, 100.0, float("inf")]
    assert [c for _, c in bc] == [1, 3, 4, 5]
    assert h.summary()["buckets"] == {"1": 1, "10": 3, "100": 4, "+Inf": 5}
    # same series again is fine; a DIFFERENT ladder for the same series is
    # a registration error, as is a malformed ladder
    assert reg.histogram("h", buckets=(1.0, 10.0, 100.0)) is h
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="strictly increase"):
        reg.histogram("h2", buckets=(3.0, 2.0))


def test_validate_exposition_catches_broken_histogram():
    reg = MetricsRegistry()
    reg.histogram("serve/engine_s", kind="x").observe(0.2)
    good = reg.to_prometheus()
    assert validate_exposition(good) == []
    # non-cumulative bucket counts must be flagged
    broken = good.replace(
        'serve_engine_s_bucket{kind="x",le="+Inf"} 1',
        'serve_engine_s_bucket{kind="x",le="+Inf"} 0',
    )
    assert broken != good
    assert any("cumulative" in p or "+Inf" in p
               for p in validate_exposition(broken))
    # a histogram family without its +Inf bucket is invalid
    lines = [ln for ln in good.splitlines() if 'le="+Inf"' not in ln]
    assert any("+Inf" in p for p in validate_exposition("\n".join(lines)))


# ------------------------------------------------ shard-imbalance telemetry


def test_shard_imbalance_units():
    assert shard_imbalance([]) == 1.0
    assert shard_imbalance([0, 0, 0]) == 1.0
    assert shard_imbalance([5, 5, 5, 5]) == 1.0
    assert shard_imbalance([12, 0, 0, 0]) == 4.0
    assert shard_imbalance(np.array([3, 1])) == pytest.approx(1.5)


def test_fold_shard_telemetry():
    reg = MetricsRegistry()
    stats = {
        "engine": "sharded", "kind": "range", "n_queries": 2,
        "per_query_dists": np.array([5, 7], np.int64),
        "dists_per_query": 6.0, "excluded": {},
        "shard_dists": np.array([9, 3], np.int64),
        "shard_blocks": np.array([2, 1], np.int64),
    }
    fold_engine_stats(reg, stats)
    snap = reg.snapshot()
    c = snap["counters"]
    assert c["shard/dists{engine=sharded,kind=range,shard=0}"] == 9.0
    assert c["shard/dists{engine=sharded,kind=range,shard=1}"] == 3.0
    assert c["shard/blocks{engine=sharded,kind=range,shard=0}"] == 2.0
    g = snap["gauges"]["shard/imbalance{engine=sharded,kind=range}"]
    assert g == pytest.approx(shard_imbalance([9, 3])) == pytest.approx(1.5)
    assert "shard/imbalance" in reg.render()
    # single-device stats without the shard split fold nothing shard-wise
    reg2 = MetricsRegistry()
    fold_engine_stats(reg2, {k: v for k, v in stats.items()
                             if not k.startswith("shard_")})
    assert not any(k.startswith("shard/")
                   for k in reg2.snapshot()["counters"])


def test_metric_names_schema_is_complete():
    # every name the obs layer itself registers is in the R6 namespace
    for name in ("engine/dists", "shard/imbalance", "serve/span_s",
                 "index/mutation_s", "compile/recompiles"):
        assert name in METRIC_NAMES


# ------------------------------------------------------- trace-event export


def test_trace_event_round_trip(tmp_path):
    evs = [
        complete_event("phase", 1.0, 0.5, tid=3, args={"k": 1}),
        instant_event("ping", 2.0, tid=3),
        metadata_event("thread_name", "req t000003", tid=3),
    ]
    p = write_trace(tmp_path / "t.json", evs, extra={"note": "unit"})
    payload = load_trace(p)
    assert validate_trace(payload) == []
    got = payload["traceEvents"]
    # metadata events sort first; ts/dur are microseconds on one clock
    assert got[0]["ph"] == "M"
    x = [e for e in got if e["ph"] == "X"][0]
    assert x["ts"] == pytest.approx(1.0e6) and x["dur"] == pytest.approx(5e5)
    assert payload["otherData"]["note"] == "unit"
    # negative duration is clamped, never emitted
    assert complete_event("x", 5.0, -1.0, tid=0)["dur"] == 0


def test_trace_buffer_is_a_ring():
    buf = TraceBuffer(capacity=3)
    buf.extend(instant_event(f"e{i}", float(i), tid=0) for i in range(5))
    names = [e["name"] for e in buf.events()]
    assert names == ["e2", "e3", "e4"] and len(buf) == 3


def test_validate_trace_flags_problems():
    assert validate_trace({"nope": 1})
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1, "tid": 0, "ts": 0.0},
        {"ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": 1.0},
        {"ph": "X", "name": "y", "pid": 1, "tid": 0, "ts": float("nan"),
         "dur": 1.0},
    ]}
    problems = validate_trace(bad)
    assert len(problems) >= 3


def _xplane_host_events(trace_dir) -> list:
    """(name, start_ns, duration_ns) of every event on the host planes of
    the one ``.xplane.pb`` a ``jax.profiler.trace`` wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = list(Path(trace_dir).rglob("*.xplane.pb"))
    prof = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in prof.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_front_trace_export_end_to_end(tmp_path):
    """End to end: a serving run, wrapped in a
    ``jax.profiler.trace`` of the caller's own, exports a
    Perfetto-loadable trace holding the admit->demux request spans, the
    driver's dispatch phase slices, and the index mutation events — all
    on the one serving clock — and the profiler's host plane holds the
    program's own dispatch and engine spans, with no profiler session
    opened by the front."""
    import jax

    idx, db, q, t = _bss_built()
    prof = tmp_path / "prof"
    with jax.profiler.trace(str(prof)):
        with ServingFront(idx, buckets=(8,), max_delay_s=0.01,
                          cache_size=4) as front:
            r1 = front.submit(q[0], "range", t=t).result(timeout=120)
            ms = front.append(_space("l2", 64, seed=6))
            r2 = front.submit(q[1], "knn", k=3).result(timeout=120)
            front.compact()
            r3 = front.submit(q[2], "range", t=t).result(timeout=120)
            path = front.export_trace(tmp_path / "trace.json")

    payload = load_trace(path)
    assert validate_trace(payload) == []
    evs = payload["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"queue", "batch", "engine", "demux"} <= names
    assert {"dispatch/assemble", "dispatch/engine", "dispatch/demux"} \
        <= names
    assert {"mutation/append", "mutation/compact"} <= names
    assert payload["otherData"]["engine"] == "bss"
    assert ms.generation == 1

    # each request rides its own tid track with the four stage slices
    for r in (r1, r2, r3):
        tid = int(r.trace_id[1:])
        mine = {e["name"] for e in evs
                if e.get("tid") == tid and e["ph"] == "X"}
        assert mine == {"queue", "batch", "engine", "demux"}, r.trace_id
    # one clock: r1 finished before the append started, which finished
    # before r2 was admitted — event timestamps must agree on that order
    append_ev = next(e for e in evs if e["name"] == "mutation/append")
    r1_demux = next(e for e in evs if e["name"] == "demux"
                    and e["tid"] == int(r1.trace_id[1:]))
    r2_queue = next(e for e in evs if e["name"] == "queue"
                    and e["tid"] == int(r2.trace_id[1:]))
    assert r1_demux["ts"] + r1_demux["dur"] <= append_ev["ts"] + 1.0
    assert append_ev["ts"] + append_ev["dur"] <= r2_queue["ts"] + 1.0
    # the dispatch/* events are the batch records' spans, stamp for stamp
    rec = dict((name, (start, end)) for name, start, end, _ in r1.batch.spans)
    ev = next(e for e in evs if e["name"] == "dispatch/engine"
              and e["ts"] == pytest.approx(rec["dispatch/engine"][0] * 1e6))
    assert ev["dur"] == pytest.approx(
        (rec["dispatch/engine"][1] - rec["dispatch/engine"][0]) * 1e6)
    # the caller's profiler session saw the program's own spans
    host = {name for name, _, _ in _xplane_host_events(prof)}
    assert {"dispatch", "dispatch/assemble", "dispatch/engine",
            "dispatch/demux", "engine/range/launch", "engine/range/device",
            "engine/range/d2h", "engine/range/select", "engine/knn/bounds",
            "engine/knn/round"} <= host
    assert not any(name.startswith("serve/engine") for name in host)


def test_explain_and_spans_survive_generation_swap():
    """Trace ids and explain records must survive living-corpus mutations:
    a request dispatched on generation g keeps its record (stamped with g)
    after appends and compactions have swapped the index under the
    front."""
    idx, db, q, t = _bss_built()
    with ServingFront(idx, buckets=(8,), max_delay_s=0.01) as front:
        r1 = front.submit(q[0], "range", t=t).result(timeout=120)
        front.append(_space("l2", 96, seed=16))          # gen 0 -> 1
        r2 = front.submit(q[1], "range", t=t).result(timeout=120)
        front.compact()                                  # gen 1 -> 2
        r3 = front.submit(q[2], "knn", k=3).result(timeout=120)
        recs = {r.trace_id: front.explain(r.trace_id)
                for r in (r1, r2, r3)}
        trace_evs = front._trace.events()

    assert [recs[r.trace_id]["generation"] for r in (r1, r2, r3)] \
        == [0, 1, 2]
    for r in (r1, r2, r3):
        rec = recs[r.trace_id]
        assert rec["trace_id"] == r.trace_id
        assert rec["n_dists"] == r.n_dists
        assert set(rec["spans"]) >= {"queue", "engine", "total"}
        # the span slices for every request are still in the trace buffer
        tids = {e.get("tid") for e in trace_evs}
        assert int(r.trace_id[1:]) in tids
    # generation swaps were real: results were served on three snapshots
    assert (r1.generation, r2.generation, r3.generation) == (0, 1, 2)


# ------------------------------------------ host spans, copies and compiles


def _sparse_built():
    """A 2-d l2 corpus whose bound leaves ~6% of (query, block) cells
    alive at its radius: the adaptive engine takes its cell-gather
    realisation there."""
    rng = np.random.default_rng(61)
    x = rng.random((3016, 2)).astype(np.float32)
    db, q = x[:3000], x[3000:]
    idx = flat_index.build_bss("l2", db, n_pivots=8, n_pairs=10,
                               block=64, seed=5)
    t = _snap(pairwise_np("l2", q, db), 0.005)
    return idx, db, q, t


_RANGE_PATHS = {
    "jnp-dense-fp32": (_bss_built, EngineOpts(realisation="dense")),
    "jnp-cells-fp32": (_sparse_built, EngineOpts()),
    "jnp-dense-bf16": (_bss_built,
                       EngineOpts(realisation="dense", precision="bf16")),
    "jnp-cells-bf16": (_sparse_built, EngineOpts(precision="bf16")),
    "pallas-fp32": (_bss_built, EngineOpts(backend="pallas",
                                           interpret=True)),
}


def _children(spans, i):
    return [s[0] for s in spans if s[3] == i]


@pytest.mark.parametrize("path", sorted(_RANGE_PATHS))
def test_range_spans_nest_inside_the_call(path):
    """Every host path of the range engine records its phases: they nest
    (each inside its parent), lie inside the call's own interval on the
    serving clock, keep device time apart from the copies, and leave the
    answers equal to the oracle's."""
    from repro.serve.queue import now

    built, opts = _RANGE_PATHS[path]
    idx, db, q, t = built()
    a = now()
    hits, stats = flat_index.bss_query_batched(idx, q, t, opts=opts)
    b = now()
    check_stats(stats)  # parents hold their children, start <= end
    spans = stats["spans"]
    assert all(a <= s[1] <= s[2] <= b for s in spans)
    roots = [s[0] for s in spans if s[3] is None]
    assert set(roots) <= {"engine/range/launch", "engine/range/device",
                          "engine/range/d2h", "engine/range/select",
                          "engine/range/stats"}
    assert roots[0] == "engine/range/launch"
    assert roots[-2:] == ["engine/range/select", "engine/range/stats"]
    # every copy follows a wait for the device (or another copy of the
    # arrays it waited for), never overlapping it
    for i, s in enumerate(spans):
        if s[0] == "engine/range/d2h" and s[3] is None:
            prev = spans[i - 1]
            assert prev[0] in ("engine/range/device", "engine/range/d2h")
            assert prev[2] <= s[1]
    ref, _ = flat_index.bss_query(idx, q, t)
    assert hits == ref, path
    if "cells" in path:  # the sparse realisation really ran
        assert (flat_index.bss_lower_bounds(idx, q) <= t).mean() \
            <= flat_index._DENSE_ALIVE_FRAC


@pytest.mark.parametrize("opts", [
    EngineOpts(realisation="dense"),
    EngineOpts(realisation="dense", precision="bf16"),
    EngineOpts(),
], ids=["dense-fp32", "dense-bf16", "adaptive-fp32"])
def test_knn_spans_one_round_span_per_round(opts):
    from repro.serve.queue import now

    idx, db, q, t = _sparse_built()
    a = now()
    ids, dists, stats = flat_index.bss_knn_batched(idx, q, 5, opts=opts)
    b = now()
    check_stats(stats)
    spans = stats["spans"]
    assert all(a <= s[1] <= s[2] <= b for s in spans)
    rounds = [i for i, s in enumerate(spans) if s[0] == "engine/knn/round"]
    assert len(rounds) == stats["rounds"] >= 1
    roots = [s[0] for s in spans if s[3] is None]
    assert roots == (["engine/knn/bounds"]
                     + ["engine/knn/round"] * stats["rounds"]
                     + ["engine/knn/stats"])
    for i in rounds:
        kids = _children(spans, i)
        assert set(kids) == {"engine/knn/device", "engine/knn/d2h",
                             "engine/knn/schedule"}, kids
        assert kids[-1] == "engine/knn/schedule"
    assert _children(spans, 0) == ["engine/knn/device", "engine/knn/d2h"]
    # answers: the exact k nearest by brute force
    d = pairwise_np("l2", q, db)
    for i in range(len(q)):
        assert set(ids[i].tolist()) == set(np.argsort(d[i])[:5].tolist())


def test_d2h_bytes_are_the_copied_arrays():
    """``d2h_bytes`` is the summed ``nbytes`` of exactly the device
    arrays each path copies, reckoned here from their shapes."""
    idx, db, q, t = _bss_built()
    nq, nb, npad = len(q), idx.n_blocks, idx.n_blocks * idx.block
    qt = -(-nq // flat_index._DEFAULT_BQ)
    masks = nq * nb + qt * nb  # alive (Q, B) and tile_mask bools
    # jnp dense: lb (Q, B) f32, the (Q, N) hit bitmask, the tile mask
    _, s = flat_index.bss_query_batched(idx, q, t, opts=_DENSE)
    assert s["d2h_bytes"] == 4 * nq * nb + nq * npad + qt * nb
    # fused (Pallas, interpreted): hit counts (Q,) and hit slots (cap,)
    # i32 from the device compaction, and both masks
    _, s = flat_index.bss_query_batched(
        idx, q, t, opts=EngineOpts(backend="pallas", interpret=True))
    assert s["compact_overflow"] == 0
    assert s["d2h_bytes"] == 4 * nq + 4 * flat_index._compact_cap(nq) + masks
    # bf16 dense: hit (Q, N) bool, masks, recheck_tiles i32, band (Q,) i32
    _, s = flat_index.bss_query_batched(
        idx, q, t, opts=EngineOpts(realisation="dense", precision="bf16"))
    assert s["d2h_bytes"] == nq * npad + masks + 4 + 4 * nq
    # kNN dense: lb once; per round ids i32 + dists f32 (Q, k), kth f32,
    # done bool, alive and tile masks
    k = 4
    _, _, s = flat_index.bss_knn_batched(idx, q, k, opts=_DENSE)
    per_round = 8 * nq * k + 4 * nq + nq + masks
    assert s["d2h_bytes"] == 4 * nq * nb + s["rounds"] * per_round
    # the registry counter carries the bytes to the exposition
    reg = MetricsRegistry()
    fold_engine_stats(reg, s)
    snap = reg.snapshot()["counters"]
    assert snap["engine/d2h_bytes{engine=bss,kind=knn}"] == s["d2h_bytes"]
    assert "engine/d2h_bytes" in METRIC_NAMES


def test_compiles_count_fresh_shapes_only():
    """A shape no call has used compiles (``stats["compiles"]`` names
    the jit); the same call again compiles nothing.  The front polls the
    same list of jits."""
    data = _space("l2", 731, seed=71)  # sizes no other test uses
    idx = flat_index.build_bss("l2", data[:700], n_pivots=8, n_pairs=10,
                               block=64, seed=5)
    q = data[700:]
    t = _snap(pairwise_np("l2", q, data[:700]), 0.04)
    _, s1 = flat_index.bss_query_batched(idx, q, t, opts=_DENSE)
    _, s2 = flat_index.bss_query_batched(idx, q, t, opts=_DENSE)
    _, _, k1 = flat_index.bss_knn_batched(idx, q, 3, opts=_DENSE)
    _, _, k2 = flat_index.bss_knn_batched(idx, q, 3, opts=_DENSE)
    if jit_cache_size(flat_index._dense_hit_mask_jit) < 0:
        pytest.skip("this jax exposes no jit cache hook")
    assert sum(s1["compiles"].values()) > 0 and "range/dense" in s1["compiles"]
    assert sum(k1["compiles"].values()) > 0 and "knn/round" in k1["compiles"]
    assert s2["compiles"] == {} and k2["compiles"] == {}
    assert set(s1["compiles"]) <= set(flat_index.ENGINE_JITS)
    front = ServingFront(idx, start=False)
    assert front._compile_watch is flat_index.ENGINE_JITS


def test_front_rows_share_one_batch_record():
    """Rows of one micro-batch share ONE frozen record (not copies);
    another batch gets another id.  The record's spans are the
    dispatch's: ``dispatch`` holding assemble, engine (with the engine's
    spans under it) and demux."""
    import dataclasses

    idx, db, q, t = _bss_built()
    with ServingFront(idx, buckets=(8,), max_delay_s=0.2) as front:
        first = [f.result(timeout=120) for f in
                 [front.submit(qv, "range", t=t) for qv in q[:5]]]
        second = [f.result(timeout=120) for f in
                  [front.submit(qv, "knn", k=3) for qv in q[5:8]]]
    by_id: dict = {}
    for r in first + second:
        by_id.setdefault(r.batch.id, []).append(r)
    for rows in by_id.values():
        assert all(r.batch is rows[0].batch for r in rows)
    assert {r.batch.id for r in first}.isdisjoint(
        {r.batch.id for r in second})
    assert any(len(rows) > 1 for rows in by_id.values())
    b = first[0].batch
    with pytest.raises(dataclasses.FrozenInstanceError):
        b.id = 0
    spans = b.spans
    assert spans[0][0] == "dispatch" and spans[0][3] is None
    assert _children(spans, 0) == ["dispatch/assemble", "dispatch/engine",
                                   "dispatch/demux"]
    eng = next(i for i, s in enumerate(spans) if s[0] == "dispatch/engine")
    assert all(spans[i][3] is not None for i, s in enumerate(spans)
               if s[0].startswith("engine/"))
    assert {s[0] for s in spans if s[3] == eng} >= {
        "engine/range/launch", "engine/range/select"}
    check_stats({**_stats_core(), "spans": spans})
    assert isinstance(b.d2h_bytes, int) and b.d2h_bytes > 0
    assert isinstance(b.compiles, dict)
    # engine_s is the dispatch/engine span
    e = spans[eng]
    assert first[0].engine_s == pytest.approx(e[2] - e[1])


def _stats_core() -> dict:
    """The smallest stats dict the schema accepts, for checking the
    optional keys alone."""
    return {"schema": 1, "engine": "bss", "kind": "range",
            "backend": "jnp", "precision": "fp32", "n_queries": 0,
            "per_query_dists": np.zeros(0, np.int64),
            "dists_per_query": 0.0, "excluded": {}}


def test_schema_checks_the_host_keys():
    ok = {**_stats_core(), "spans": [("a", 1.0, 3.0, None),
                                     ("a/b", 1.5, 2.0, 0)],
          "d2h_bytes": 12, "compiles": {"range/lb": 1}}
    assert validate_stats(ok) == []
    bad = [
        {"spans": [("a", 2.0, 1.0, None)]},             # end before start
        {"spans": [("a", 1.0, 2.0, None), ("b", 1.5, 2.5, 0)]},  # outside
        {"spans": [("a", 1.0, 2.0, 1)]},                # parent not earlier
        {"spans": [("a", 1.0, None, None)]},            # still open
        {"spans": "a"},
        {"d2h_bytes": -1}, {"d2h_bytes": 1.5},
        {"compiles": {"f": -1}}, {"compiles": [1]},
    ]
    for extra in bad:
        assert validate_stats({**_stats_core(), **extra}), extra


def test_compact_overflow_schema_and_counter():
    """``compact_overflow`` is an optional 0-or-1 key of the schema, and
    the registry counter ``engine/range/compact_overflow`` sums it over
    calls (a call that fit adds 0, and still shows the counter)."""
    for v in (0, 1, np.int64(1)):
        assert validate_stats({**_stats_core(), "compact_overflow": v}) == []
    for v in (2, -1, 0.5, True, "1", None):
        assert validate_stats({**_stats_core(), "compact_overflow": v}), v
    reg = MetricsRegistry()
    for v in (0, 1, 0, 1, 1):
        fold_engine_stats(reg, {**_stats_core(), "compact_overflow": v})
    snap = reg.snapshot()["counters"]
    assert snap["engine/range/compact_overflow{engine=bss,kind=range}"] == 3
    reg2 = MetricsRegistry()
    fold_engine_stats(reg2, {**_stats_core(), "compact_overflow": 0})
    assert reg2.snapshot()["counters"][
        "engine/range/compact_overflow{engine=bss,kind=range}"] == 0
    assert "engine/range/compact_overflow" in METRIC_NAMES


def test_program_spans_share_the_profiler_clock(tmp_path):
    """Under ``jax.profiler.trace`` a program span's recorded start, mapped
    through an anchor annotation opened at a known ``now()``, lies within
    1 ms of the same span's event in the ``.xplane.pb``."""
    import jax

    from repro.serve.queue import now

    idx, db, q, t = _bss_built()
    flat_index.bss_query_batched(idx, q, t, opts=_DENSE)  # compile first
    with jax.profiler.trace(str(tmp_path)):
        anchor_at = now()
        with jax.profiler.TraceAnnotation("test/anchor"):
            pass
        _, stats = flat_index.bss_query_batched(idx, q, t, opts=_DENSE)
        _, _, kstats = flat_index.bss_knn_batched(idx, q, 3, opts=_DENSE)
    events = _xplane_host_events(tmp_path)
    (anchor_ns,) = [s for name, s, _ in events if name == "test/anchor"]
    off = anchor_at - anchor_ns * 1e-9
    for name, spans in (("engine/range/select", stats["spans"]),
                        ("engine/knn/bounds", kstats["spans"])):
        recorded = next(s for s in spans if s[0] == name)
        (ev,) = [(s, d) for n, s, d in events if n == name]
        assert abs(ev[0] * 1e-9 + off - recorded[1]) < 1e-3, name
        assert abs(ev[1] * 1e-9 - (recorded[2] - recorded[1])) < 1e-3


def test_server_search_span_holds_the_engine_spans():
    """``RetrievalServer.search`` roots the call's spans at
    ``server/search``; the answers stay the oracle's."""
    from repro.serve.retrieval import RetrievalServer

    idx, db, q, t = _bss_built()
    srv = RetrievalServer(db, metric="l2", n_pivots=8, n_pairs=10, block=64,
                          seed=5, opts=_DENSE)
    for kind, kw in (("range", {"t": t}), ("knn", {"k": 3})):
        res = srv.search(q, kind, **kw)
        check_stats(res.stats)
        spans = res.stats["spans"]
        assert spans[0][0] == "server/search" and spans[0][3] is None
        assert all(s[3] is not None for s in spans[1:])
        assert {s[0].split("/")[0] for s in spans[1:]} == {"engine"}
        assert res.stats["d2h_bytes"] > 0
    ref, _ = flat_index.bss_query(srv.index, q, t)
    assert srv.search(q, "range", t=t).hits == ref


def test_span_log_nests_and_adopts():
    from repro.obs import SpanLog

    inner = SpanLog()
    with inner.span("engine/x/a", n=1):
        with inner.span("engine/x/b"):
            pass
    log = SpanLog()
    with log.span("outer") as outer:
        with log.span("outer/engine"):
            log.adopt(inner.records)
    names = [(r[0], r[3]) for r in log.records]
    assert names == [("outer", None), ("outer/engine", 0),
                     ("engine/x/a", 1), ("engine/x/b", 2)]
    assert log.records[0][1:3] == (outer.start, outer.end)
    assert all(r[2] is not None for r in log.records)
