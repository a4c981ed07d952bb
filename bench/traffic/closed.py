"""Closed loop: one client sends a batch, waits for its answer, and sends
the next, back to back, until the window closes.

Batches of ``batch`` queries walk through the pool in an order the seed
shuffles (a fresh permutation each time the pool is used up), so every
seed sends the same amount of work per batch, in another order.  The
window is timed from the first call to the end of the last one, which
starts before the close.
"""

from __future__ import annotations

import time

import numpy as np

from bench.cell import request_params


def run(entry, pool: np.ndarray, traffic: dict, cell: dict, seed: int,
        seconds: float, clock=time.perf_counter) -> dict:
    """Drive ``entry.search`` for ``seconds``; returns the run's record."""
    import jax

    rng = np.random.default_rng([int(seed), 11])
    bsz = int(traffic["batch"])
    kind = traffic["kind"]
    kw = request_params(traffic, cell)
    order = np.empty(0, np.int64)
    calls = []
    t0 = clock()
    t1 = t0 + seconds
    while clock() < t1:
        while len(order) < bsz:
            order = np.concatenate([order, rng.permutation(len(pool))])
        qidx, order = order[:bsz], order[bsz:]
        a = clock()
        res = None
        try:
            with jax.profiler.TraceAnnotation(f"bench/search/{kind}"):
                res = entry.search(pool[qidx], kind, **kw)
        except Exception:  # noqa: BLE001 — a failed call fails its queries
            pass
        calls.append({"t0": a, "t1": clock(), "qidx": qidx, "res": res})
    return {"loop": "closed", "kind": kind, "t0": t0, "t1": t1,
            "end": calls[-1]["t1"] if calls else t1, "calls": calls,
            "k": traffic.get("k"), **kw}


def answers(rec: dict) -> list[dict]:
    out = []
    for c in rec["calls"]:
        res = c["res"]
        if res is None:
            continue
        for j, qi in enumerate(c["qidx"]):
            out.append({
                "qidx": int(qi), "t": rec.get("t"),
                "hits": res.hits[j] if res.hits is not None else None,
                "ids": res.indices[j] if res.indices is not None else None,
                "dists": (res.distances[j] if res.distances is not None
                          else None),
            })
    return out


def batches(rec: dict, n_pivots: int) -> list[dict]:
    """One entry per answered call: its host interval and exact-phase
    counts per query."""
    out = []
    for c in rec["calls"]:
        res = c["res"]
        if res is None:
            continue
        exact = np.asarray(res.stats["per_query_dists"]) - n_pivots
        if rec["kind"] == "range":
            answer_bytes = 4 * sum(len(h) for h in res.hits)
        else:
            answer_bytes = 8 * int(rec["k"]) * len(c["qidx"])
        out.append({"t0": c["t0"], "t1": c["t1"], "n": len(c["qidx"]),
                    "exact": exact, "answer_bytes": answer_bytes,
                    "stats": res.stats})
    return out
