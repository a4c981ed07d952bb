"""What the program records about its own host work, as the per-layer
metrics read it.

The serving front puts one record on every answer of a micro-batch
(``ServeResult.batch``: a process-unique ``id``, the dispatch's
``spans``, ``d2h_bytes``, ``compiles``); ``RetrievalServer.search`` puts
the same keys in the call's ``stats``.  Spans are rows ``(name, start,
end, parent)`` on ``time.perf_counter``, the clock the trace's device
operations are mapped onto (``trace_reduce.load``).  A program without
these records (an older checkout) gives empty lists here, and every
reader then returns None.
"""

from __future__ import annotations

import numpy as np

from bench import trace_reduce


def open_batches(ctx) -> tuple[list, list]:
    """The answered requests of an open-loop run, and the distinct batch
    records among them (each batch once, by id)."""
    rows = [r["res"] for r in ctx.rec.get("requests", ()) if r["ok"]]
    seen: dict = {}
    for res in rows:
        b = getattr(res, "batch", None)
        if b is not None:
            seen.setdefault(b.id, b)
    return rows, list(seen.values())


def closed_calls(ctx, key: str) -> list[dict]:
    """The answered calls of a closed-loop run whose stats carry
    ``key``."""
    return [c for c in ctx.rec.get("calls", ())
            if c["res"] is not None and key in (c["res"].stats or {})]


def _measure(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if lo < hi:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def idle_in_engine_host(trace, span_lists) -> float | None:
    """Percent of the traced window in which no device operation ran (the
    union of ``trace.ops``) and the host was inside an ``engine/*`` span
    other than an ``engine/*/device`` wait.  None without a trace, a
    device operation, or any engine span."""
    if trace is None or not trace.ops:
        return None
    host, wait = [], []
    for spans in span_lists:
        for name, start, end, _ in spans:
            if end is None or not str(name).startswith("engine/"):
                continue
            (wait if name.endswith("/device") else host).append((start, end))
    if not host:
        return None
    lo, hi = trace.window
    host_u = trace_reduce.union(host, lo, hi)
    covered = trace_reduce.union(
        wait + [(a, b) for _, _, a, b in trace.ops], lo, hi)
    idle_host = _measure(host_u) - _measure(_intersect(host_u, covered))
    return 100.0 * idle_host / (hi - lo)
