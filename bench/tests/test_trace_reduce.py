"""The trace reduction: busy time, idle gaps and their labels, the top
operations, kernel matching, and reading a trace the profiler wrote."""

from __future__ import annotations

import json
import time
from pathlib import Path

import pytest

from bench import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def _small() -> T.Trace:
    return T.Trace(
        window=(0.0, 10.0),
        ops=[(DEV, "m/a", 1.0, 2.0), (DEV, "m/b", 1.5, 3.0),
             (DEV, "m/a", 6.0, 6.5), (DEV, "m/c", 9.5, 11.0)],
        spans=[("bench/search/knn", 0.5, 4.0), ("bench/search/knn", 5.0, 7.0)],
    )


def test_busy_and_idle_by_hand():
    tr = _small()
    # union [1, 3] + [6, 6.5] + [9.5, 10] (clipped to the window)
    assert T.busy_s(tr) == pytest.approx(3.0)
    gaps = T.idle_gaps(tr)
    assert gaps == [(3.0, 6.0), (6.5, 9.5), (0.0, 1.0)]
    assert sum(b - a for a, b in gaps) + T.busy_s(tr) == pytest.approx(10.0)
    assert T.idle_share(tr) == pytest.approx(70.0)


def test_top_ops_and_matching():
    tr = _small()
    assert T.top_ops(tr, 2) == [["m/a", 1.5], ["m/b", 1.5]] or \
        T.top_ops(tr, 2) == [["m/b", 1.5], ["m/a", 1.5]]
    assert [o[1] for o in T.matching(tr, [r"/a$"])] == ["m/a", "m/a"]


def test_gap_labels_take_the_innermost_span():
    tr = _small()
    assert T.host_label(tr, 3.0, 6.0) == "idle"          # middle 4.5
    assert T.host_label(tr, 5.5, 6.0) == "bench/search/knn"
    extra = [("front/engine", 5.6, 5.9)]
    assert T.host_label(tr, 5.7, 5.8, extra) == "front/engine"


def test_empty_trace_is_all_idle():
    tr = T.Trace(window=(0.0, 2.0), ops=[], spans=[])
    assert T.busy_s(tr) == 0.0
    assert T.idle_gaps(tr) == [(0.0, 2.0)]
    # no device operation: nothing to read, never a share of 100
    assert T.idle_share(tr) is None and T.idle_share(None) is None


def test_recorded_chip_trace():
    """1.2 s of a traced chip run of ``sift1m-l2.knn-batch``, reduced by
    ``load`` and kept beside the tests; its busy time was counted apart,
    on a 0.1-microsecond grid."""
    kept = json.loads((DATA / "trace_knn.json").read_text())
    tr = T.Trace.from_json(kept["trace"])
    expect = kept["expect"]
    assert T.devices(tr) == [DEV]
    assert T.busy_s(tr) == pytest.approx(expect["busy_s"], abs=1e-6)
    gaps = T.idle_gaps(tr)
    window = tr.window[1] - tr.window[0]
    assert sum(b - a for a, b in gaps) + T.busy_s(tr) == pytest.approx(window)
    assert all(g1[1] - g1[0] >= g2[1] - g2[0] for g1, g2 in zip(gaps, gaps[1:]))
    top = T.top_ops(tr, 10)
    assert [t[0] for t in top[:3]] == expect["top3"]
    assert len(T.matching(tr, expect["kernel_patterns"])) == expect["n_kernels"]


def test_load_aligns_the_profiler_clock(tmp_path):
    """A trace the profiler writes here (CPU: host planes only) loads with
    the benchmark's spans on the host clock, within a few milliseconds."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    anchor = time.perf_counter()
    with jax.profiler.TraceAnnotation(T.ANCHOR):
        pass
    a = time.perf_counter()
    time.sleep(0.05)
    s0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/span"):
        time.sleep(0.1)
    s1 = time.perf_counter()
    b = time.perf_counter()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path), anchor, (a, b))
    (span,) = [s for s in tr.spans if s[0] == "bench/span"]
    assert span[1] == pytest.approx(s0, abs=5e-3)
    assert span[2] == pytest.approx(s1, abs=5e-3)
    assert tr.window == (a, b)
