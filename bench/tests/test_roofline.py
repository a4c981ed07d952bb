"""The roofline's work functions against hand counts, and its share on a
small trace."""

from __future__ import annotations

import types

import numpy as np
import pytest

from bench import roofline
from bench.cell import named
from bench.trace_reduce import Trace

V5E = roofline.peaks("TPU v5 lite")
L2, JSD = named("distances", "l2"), named("distances", "jsd")


def test_l2_work_by_hand():
    # two queries evaluating 100 and 300 distances over a 1000-row corpus
    # of 128-d rows, returning 10 hits (40 bytes)
    ops, nbytes = roofline.batch_work(L2, 128, [100, 300], 1000, 40)
    assert ops == 400 * 2 * 128
    # the largest query's 300 rows + 2 query rows, 512 bytes each
    assert nbytes == (300 + 2) * 128 * 4 + 40


def test_jsd_work_has_no_operation_bound():
    ops, nbytes = roofline.batch_work(JSD, 112, [50, 20, 70], 60, 0)
    assert ops == 0.0
    # rows capped at the corpus (60), + 3 queries
    assert nbytes == (60 + 3) * 112 * 4


def test_least_time_takes_the_larger_bound():
    t, which = roofline.least_time(L2, 197e12, 819e9, V5E)
    assert which in ("mxu_bf16_flops_per_s", "hbm")
    assert t == pytest.approx(1.0)
    t, which = roofline.least_time(L2, 1e9, 819e9, V5E)
    assert which == "hbm" and t == pytest.approx(1.0)
    t, which = roofline.least_time(L2, 4 * 197e12, 819e9, V5E)
    assert which == "mxu_bf16_flops_per_s" and t == pytest.approx(4.0)
    t, which = roofline.least_time(JSD, 1e20, 819e9, V5E)
    assert which == "hbm" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["mxu_bf16_flops_per_s"] == 197e12


def test_share_counts_only_batches_inside_the_trace():
    dev = "/device:TPU:0"
    tr = Trace(window=(10.0, 20.0), spans=[], ops=[
        (dev, "%masked_pairwise_kernel_call.1 = f32[128,1000064] custom-call", 9.5, 9.9),    # batch before the window
        (dev, "%masked_pairwise_kernel_call.1 = f32[128,1000064] custom-call", 11.0, 11.002),
        (dev, "m/other", 11.002, 11.5),
        (dev, "%masked_pairwise_kernel_call.1 = f32[128,1000064] custom-call", 15.0, 15.004),
        (dev, "%masked_pairwise_kernel_call.1 = f32[128,1000064] custom-call", 19.9, 20.0),   # batch runs past it
    ])
    exact = np.full(4, 1000)  # 4 queries, 1000 rows each; 128-d l2
    batches = [
        {"t0": 9.0, "t1": 10.5, "exact": exact, "answer_bytes": 0},
        {"t0": 10.9, "t1": 12.0, "exact": exact, "answer_bytes": 0},
        {"t0": 14.9, "t1": 15.5, "exact": exact, "answer_bytes": 0},
        {"t0": 19.8, "t1": 21.0, "exact": exact, "answer_bytes": 0},
    ]
    ctx = types.SimpleNamespace(trace=tr, peak=V5E, batches=batches,
                                cfg={"metric": "l2", "dim": 128},
                                cell={"distance": L2},
                                n_valid=10_000)
    nbytes = (1000 + 4) * 128 * 4
    want = 100 * 2 * (nbytes / 819e9) / (0.002 + 0.004)
    assert roofline.share(ctx, [r"^%masked_pairwise_kernel_call\b"]) == pytest.approx(want)
    assert roofline.share(ctx, [r"no_such_kernel"]) is None
    ctx.trace = None
    assert roofline.share(ctx, [r"^%masked_pairwise_kernel_call\b"]) is None
