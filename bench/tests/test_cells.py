"""Every cell, driven end to end on the CPU at a small size with the chip
look skipped: sound runs come out correct, and a run whose timed path is
broken underneath, or whose answers come from the control, comes out not
correct.

The faults a search cell can have (no training state, no exchange between
chips): an answer altered where the engine produces it, and half of each
batch left out.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench import run

CELLS = ["sift1m-l2.range-open", "colors-jsd.range-batch", "sift1m-l2.knn-batch"]
SEED = 2**31 + 12345


def _small(c: dict, n: int = 20_000, radius_scale: float = 1.4) -> None:
    """A size a test run holds: ``n`` rows, 1,000 pool queries, and by
    default a radius that keeps most queries' hit lists non-empty at that
    size (a fault that empties an answer then shows)."""
    cfg = c["config"]
    if "n_base" in cfg:
        cfg["n_base"], cfg["n_queries"] = n, 1_000
    else:
        cfg["n_rows"] = n
    cfg["index"]["backend"] = "jnp"
    cell = c["cell"]
    cell["n_check"] = min(cell["n_check"], 48)
    if "radius" in cell:
        cell["radius"] *= radius_scale
    if "rate_per_s" in cell:
        cell["rate_per_s"] = 600
    c["traffic"]["batch"] = 128
    c["traffic"]["drain_s"] = 20


def _run(name: str) -> dict:
    return run.run_cell(name, SEED, 2.0, False, require_tpu=False,
                        overrides=_small)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def _alter_answers(monkeypatch, flat_index):
    """Every answer altered where the engine produces it: range answers
    flip whether row 0 is a hit; kNN answers lose their k-th id."""
    query, knn = flat_index.bss_query_batched, flat_index.bss_knn_batched

    def bad_query(*a, **kw):
        hits, stats = query(*a, **kw)
        return [h[1:] if h[:1] == [0] else [0] + h for h in hits], stats

    def bad_knn(*a, **kw):
        idx, dist, stats = knn(*a, **kw)
        idx = idx.copy()
        idx[:, -1] = (idx[:, -1] + 1) % (idx.max() + 2)
        return idx, dist, stats

    monkeypatch.setattr(flat_index, "bss_query_batched", bad_query)
    monkeypatch.setattr(flat_index, "bss_knn_batched", bad_knn)


def _half_batch(monkeypatch, flat_index):
    """Half of each batch left out: the engine answers every other row
    and returns empty answers for the rest."""
    query, knn = flat_index.bss_query_batched, flat_index.bss_knn_batched

    def bad_query(*a, **kw):
        hits, stats = query(*a, **kw)
        return [h if i % 2 == 0 else [] for i, h in enumerate(hits)], stats

    def bad_knn(*a, **kw):
        idx, dist, stats = knn(*a, **kw)
        idx, dist = idx.copy(), dist.copy()
        idx[1::2], dist[1::2] = -1, np.inf
        return idx, dist, stats

    monkeypatch.setattr(flat_index, "bss_query_batched", bad_query)
    monkeypatch.setattr(flat_index, "bss_knn_batched", bad_knn)


@pytest.mark.parametrize("fault", [_alter_answers, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    from repro.core import flat_index

    fault(monkeypatch, flat_index)
    out = _run(name)
    assert not out["correct"], out["checks"]


# the control's errors show only on rows near a radius or a k-th distance:
# the l2 cells need more rows than 20,000 for enough of them
CONTROL_ROWS = {"sift1m-l2.range-open": 100_000, "sift1m-l2.knn-batch": 100_000,
                "colors-jsd.range-batch": 30_000}


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name, control):
    """At a size where the control's errors show, a run comes out correct,
    and the same run with the control's answers to the checked requests
    compared in place of the program's (the reference in the next
    precision down) comes out not correct, at the cell's own limits."""
    def size(c: dict) -> None:
        n_check = c["cell"]["n_check"]
        _small(c, CONTROL_ROWS[name], radius_scale=1.0)
        c["cell"]["n_check"] = n_check

    out = run.run_cell(name, SEED, 2.0, False, require_tpu=False,
                       overrides=size, control=control)
    assert out["correct"] is not control, out["checks"]
