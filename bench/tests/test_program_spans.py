"""The readers of the program's own spans and counters
(``bench/program_spans.py``), on hand-built results: the values they
give, and None on results of a program that records none of them."""

from __future__ import annotations

import types

import pytest

from bench import trace_reduce
from bench.cell import named

OPEN = ["engine.d2h_bytes_per_query.open", "device.idle_in_engine_host.open",
        "front.driver_self_ms", "engine.compiles.open"]
BATCH = ["engine.d2h_bytes_per_query.batch",
         "device.idle_in_engine_host.batch", "engine.compiles.batch"]

# device busy over [1, 2] and [5, 6] of a 10 s window: 80% idle
TRACE = trace_reduce.Trace(window=(0.0, 10.0),
                           ops=[("/device:TPU:0", "op", 1.0, 2.0),
                                ("/device:TPU:0", "op", 5.0, 6.0)],
                           spans=[])

# a range batch: engine host work [0.7, 1.0] and [2.5, 3.4] (1.2 s, none of
# it under a device op), a device wait [1.0, 2.5]; dispatch 3.5 s of which
# the engine 2.9 s
RANGE_SPANS = [
    ("dispatch", 0.5, 4.0, None), ("dispatch/assemble", 0.5, 0.6, 0),
    ("dispatch/engine", 0.6, 3.5, 0),
    ("engine/range/launch", 0.7, 1.0, 2), ("engine/range/device", 1.0, 2.5, 2),
    ("engine/range/d2h", 2.5, 3.0, 2), ("engine/range/select", 3.0, 3.4, 2),
    ("dispatch/demux", 3.5, 4.0, 0),
]
# a kNN batch: one round [4.5, 7.0] holding a device wait [4.6, 5.2]; the
# device runs [5, 6]: idle engine host time 2.5 - 1.4 = 1.1 s; dispatch
# 3.4 s of which the engine 2.7 s
KNN_SPANS = [
    ("dispatch", 4.2, 7.6, None), ("dispatch/engine", 4.4, 7.1, 0),
    ("engine/knn/round", 4.5, 7.0, 1), ("engine/knn/device", 4.6, 5.2, 2),
    ("engine/knn/schedule", 6.5, 7.0, 2),
]


def _batch(i, spans, d2h, compiles):
    return types.SimpleNamespace(id=i, spans=spans, d2h_bytes=d2h,
                                 compiles=compiles)


def _open_ctx(with_records=True, trace=TRACE):
    b1 = _batch(7, RANGE_SPANS, 1_000, {"range/dense": 2})
    b2 = _batch(8, KNN_SPANS, 500, {})

    def row(b):
        res = types.SimpleNamespace(n_dists=10)
        if with_records:
            res.batch = b
        return {"ok": True, "res": res}

    requests = [row(b1), row(b1), row(b2),
                {"ok": False, "res": None}]
    return types.SimpleNamespace(rec={"loop": "open", "requests": requests},
                                 trace=trace)


def _batch_ctx(with_records=True, trace=TRACE):
    def call(n, stats):
        base = {"per_query_dists": [1] * n}
        return {"qidx": list(range(n)), "res": types.SimpleNamespace(
            stats={**base, **stats} if with_records else base)}

    search = [("server/search", 0.6, 3.6, None)] + [
        (n, a, b, 0 if p == 2 else p) for n, a, b, p in RANGE_SPANS[3:7]]
    calls = [
        call(4, {"d2h_bytes": 4_096, "compiles": {"knn/round": 1,
                                                  "knn/lb": 1},
                 "spans": search}),
        call(4, {"d2h_bytes": 1_024, "compiles": {},
                 "spans": [("server/search", 4.4, 7.1, None)] + [
                     (n, a, b, p - 1) for n, a, b, p in KNN_SPANS[2:]]}),
        {"qidx": [0, 1], "res": None},
    ]
    return types.SimpleNamespace(rec={"loop": "closed", "calls": calls},
                                 trace=trace)


def _read(name, ctx):
    return named("metrics", name).read(ctx)


def test_open_readers_hand_computed():
    ctx = _open_ctx()
    # two batches, 1,500 bytes, over the three answered requests
    assert _read("engine.d2h_bytes_per_query.open", ctx) == 500.0
    # (1.2 + 1.1) s of a 10 s window
    assert _read("device.idle_in_engine_host.open", ctx) == pytest.approx(23.0)
    # mean of 3.5 - 2.9 and 3.4 - 2.7 seconds
    assert _read("front.driver_self_ms", ctx) == pytest.approx(650.0)
    assert _read("engine.compiles.open", ctx) == 2.0


def test_batch_readers_hand_computed():
    ctx = _batch_ctx()
    # 5,120 bytes over the 8 queries the answered calls answered
    assert _read("engine.d2h_bytes_per_query.batch", ctx) == 640.0
    assert _read("device.idle_in_engine_host.batch", ctx) == \
        pytest.approx(23.0)
    assert _read("engine.compiles.batch", ctx) == 2.0


def test_idle_in_engine_host_within_idle_share():
    for ctx in (_open_ctx(), _batch_ctx()):
        name = ("device.idle_in_engine_host.open" if ctx.rec["loop"] == "open"
                else "device.idle_in_engine_host.batch")
        assert _read(name, ctx) <= trace_reduce.idle_share(TRACE)


@pytest.mark.parametrize("name", OPEN + BATCH)
def test_readers_give_none_without_the_records(name):
    """A program that records no spans or counters (an older checkout):
    every reader returns None and raises nothing, in either loop."""
    for ctx in (_open_ctx(with_records=False),
                _batch_ctx(with_records=False)):
        assert _read(name, ctx) is None


@pytest.mark.parametrize("name", ["device.idle_in_engine_host.open",
                                  "device.idle_in_engine_host.batch"])
def test_idle_readers_give_none_without_a_trace(name):
    for ctx in (_open_ctx(trace=None), _batch_ctx(trace=None)):
        assert _read(name, ctx) is None


def test_readers_on_a_real_front_batch():
    """The open readers on records the program itself made."""
    import numpy as np

    from repro.core import flat_index
    from repro.serve.front import ServingFront

    rng = np.random.default_rng(3)
    x = rng.random((660, 8)).astype(np.float32)
    idx = flat_index.build_bss("l2", x[:640], n_pivots=8, n_pairs=10,
                               block=64, seed=5)
    with ServingFront(idx, buckets=(8,), max_delay_s=0.05) as front:
        futs = [front.submit(q, "range", t=0.3) for q in x[640:]]
        res = [f.result(timeout=120) for f in futs]
    ctx = types.SimpleNamespace(
        rec={"loop": "open", "requests": [{"ok": True, "res": r}
                                          for r in res]},
        trace=None)
    ids = {r.batch.id: r.batch for r in res}
    want = sum(b.d2h_bytes for b in ids.values()) / len(res)
    assert _read("engine.d2h_bytes_per_query.open", ctx) == want > 0
    assert _read("front.driver_self_ms", ctx) > 0
    assert _read("engine.compiles.open", ctx) >= 0
