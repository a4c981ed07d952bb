"""The least time the chip could take over the exact phase's work, from
counts that do not depend on how the kernel is written.

* Operations: every distance the engine reports evaluated, counted once,
  at the metric's own count (``bench/distances/<metric>.py``: ``ops``,
  and ``OPS_PEAK``, the published peak of the unit that does the work, or
  ``None`` where the chip publishes none: bandwidth alone bounds it).
* Bytes: every corpus row that at least one query of the batch needs, read
  once per batch, plus the queries and the answers returned.  The engine
  reports each query's own count, not the union over the batch, so the
  rows read are taken as the largest single query's count (capped at the
  corpus): never more than the union, so the bound never overstates the
  work.

The least time is the larger of bytes over the HBM peak and operations over
that unit's peak.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import trace_reduce

_PEAKS = Path(__file__).resolve().parent / "peaks.json"
_ROW_BYTES = 4  # float32 corpus and queries


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PEAKS.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def batch_work(dist, dim: int, exact_per_query, n_rows: int,
               answer_bytes: int) -> tuple[float, float]:
    """(operations, bytes) of one batch's exact phase under the metric
    ``dist`` (its distances module).  ``exact_per_query``
    holds each real query's exact-phase distance count (pivot distances
    excluded); ``n_rows`` is the corpus size."""
    exact = np.asarray(exact_per_query, np.float64)
    if exact.size == 0:
        return 0.0, 0.0
    ops = float(exact.sum()) * dist.ops(dim)
    rows = min(float(exact.max()), float(n_rows))
    nbytes = (rows + exact.size) * dim * _ROW_BYTES + float(answer_bytes)
    return ops, nbytes


def least_time(dist, ops: float, nbytes: float,
               peak: dict) -> tuple[float, str]:
    """(seconds, which bound) for the given work on a chip with ``peak``."""
    t_bytes = nbytes / float(peak["hbm_bytes_per_s"])
    unit = dist.OPS_PEAK
    t_ops = ops / float(peak[unit]) if unit else 0.0
    return (t_ops, unit) if t_ops > t_bytes else (t_bytes, "hbm")


def share(ctx, patterns) -> float | None:
    """Percent of the least time over the summed device time of the
    exact-phase kernel events (names matching ``patterns``) of every batch
    whose engine call lies wholly inside the traced window.  A kernel event
    belongs to the batch whose host interval holds its start.  None where
    the trace holds no such batch or kernel."""
    tr = ctx.trace
    if tr is None or ctx.peak is None:
        return None
    a, b = tr.window
    kernels = trace_reduce.matching(tr, patterns)
    dist, dim = ctx.cell["distance"], int(ctx.cfg["dim"])
    least = busy = 0.0
    for bt in ctx.batches:
        if bt["t0"] < a or bt["t1"] > b:
            continue
        evs = [o for o in kernels if bt["t0"] <= o[2] <= bt["t1"]]
        if not evs:
            continue
        busy += sum(o[3] - o[2] for o in evs)
        ops, nbytes = batch_work(dist, dim, bt["exact"], ctx.n_valid,
                                 bt["answer_bytes"])
        least += least_time(dist, ops, nbytes, ctx.peak)[0]
    return 100.0 * least / busy if busy > 0 else None
