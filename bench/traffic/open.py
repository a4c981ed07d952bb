"""Open loop: independent users send single requests on a schedule,
whether or not earlier ones have finished.

The schedule is a Poisson process at a fixed rate, conditioned on its
count, and the same for every seed up to its order: ``round(rate *
seconds)`` arrivals whose gaps are one set of exponential draws (fixed by
the count, scaled to fill the window), shuffled by the seed.  Each request
draws its query from the pool (a fresh permutation per seed) and, for
range, its radius as the cell's radius times a factor from
``radius_factor``; the factors are one evenly spaced set, shuffled by the
seed.  So every seed offers the same work, in another order.

Each request is timed from when it was due to when its future resolved, so
a stall delays every request due during it.  The generator runs in the
calling thread and records how late it submitted each request.  After the
last arrival it waits for every answer, up to ``drain_s`` past the close
of the window; a request that never resolves, or resolves with an
exception, is failed and is timed to the end of that wait.

Adapted from ``benchmarks/retrieval_serving.py::run_async`` (its
generator, not its sizes).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from bench.cell import request_params


def schedule(traffic: dict, cell: dict, seed: int, seconds: float,
             pool_size: int) -> dict:
    """The arrivals of one run: due times (seconds from the window's
    start), pool rows and, for range, radii."""
    n = max(1, int(round(float(cell["rate_per_s"]) * seconds)))
    gaps = np.random.default_rng([n, 9]).exponential(size=n + 1)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng([int(seed), 10])
    due = np.cumsum(rng.permutation(gaps))[:n]
    reps = -(-n // pool_size)
    qidx = np.concatenate([rng.permutation(pool_size)
                           for _ in range(reps)])[:n]
    out = {"due": due, "qidx": qidx}
    if traffic["kind"] == "range":
        lo, hi = traffic["radius_factor"]
        factors = lo + (hi - lo) * (rng.permutation(n) + 0.5) / n
        out["t"] = float(cell["radius"]) * factors
    return out


def run(entry, pool: np.ndarray, traffic: dict, cell: dict, seed: int,
        seconds: float, clock=time.perf_counter) -> dict:
    """Drive ``entry.submit`` for ``seconds``; returns the run's record."""
    import jax

    sched = schedule(traffic, cell, seed, seconds, len(pool))
    n = len(sched["due"])
    kind = traffic["kind"]
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    futs: list = [None] * n
    remaining = threading.Semaphore(0)

    def finished(i: int):
        def cb(_fut) -> None:
            done[i] = clock()
            remaining.release()
        return cb

    t0 = clock()
    due = t0 + sched["due"]
    with jax.profiler.TraceAnnotation("bench/submit_loop"):
        for i in range(n):
            wait = due[i] - clock()
            if wait > 0:
                time.sleep(wait)
            kw = ({"t": float(sched["t"][i])} if kind == "range"
                  else request_params(traffic, cell))
            sent[i] = clock()
            try:
                futs[i] = entry.submit(pool[sched["qidx"][i]], kind, **kw)
            except Exception:  # noqa: BLE001 — a refused request is failed
                done[i] = np.nan
                remaining.release()
                continue
            futs[i].add_done_callback(finished(i))
    t1 = t0 + seconds
    deadline = t1 + float(traffic["drain_s"])
    with jax.profiler.TraceAnnotation("bench/result_wait"):
        for _ in range(n):
            if not remaining.acquire(timeout=max(0.0, deadline - clock())):
                break
    requests = []
    for i in range(n):
        f = futs[i]
        res = None
        if f is not None and f.done() and not f.cancelled() \
                and f.exception() is None:
            res = f.result()
        ok = res is not None and not np.isnan(done[i])
        requests.append({
            "due": float(due[i]), "sent": float(sent[i]),
            "done": float(done[i]) if ok else float(deadline),
            "ok": ok, "qidx": int(sched["qidx"][i]),
            "t": float(sched["t"][i]) if kind == "range" else None,
            "res": res,
        })
    return {"loop": "open", "kind": kind, "t0": t0, "t1": t1,
            "requests": requests, "k": traffic.get("k")}


def latencies(rec: dict) -> np.ndarray:
    """Seconds from due to resolution of every request due in the window
    (failed ones to the end of the wait)."""
    return np.asarray([r["done"] - r["due"] for r in rec["requests"]])


def lateness(rec: dict) -> np.ndarray:
    """Seconds by which the generator submitted each request late."""
    return np.asarray([r["sent"] - r["due"] for r in rec["requests"]
                       if not np.isnan(r["sent"])])


def answers(rec: dict) -> list[dict]:
    """Every answered request: its pool row, parameter and answer."""
    out = []
    for r in rec["requests"]:
        if not r["ok"]:
            continue
        res = r["res"]
        out.append({"qidx": r["qidx"], "t": r["t"], "hits": res.hits,
                    "ids": res.indices, "dists": res.distances})
    return out


def batches(rec: dict, n_pivots: int) -> list[dict]:
    """The front's micro-batches, rebuilt from the answers: requests of
    one batch share its engine time and size.  Each batch's engine work
    ran within ``[t0, t1]`` on the host clock (the front's dispatch time
    is the request's submission plus its queue wait)."""
    groups: dict = {}
    for r in rec["requests"]:
        if r["ok"]:
            res = r["res"]
            key = (res.engine_s, res.batch_size, res.padded_to)
            groups.setdefault(key, []).append(r)
    out = []
    for (engine_s, size, padded), rs in groups.items():
        start = float(np.median([r["sent"] + r["res"].queue_wait_s
                                 for r in rs]))
        exact = np.asarray([r["res"].n_dists - n_pivots for r in rs])
        hits = sum(len(r["res"].hits or ()) for r in rs)
        k = rec.get("k") or 0
        out.append({
            "t0": start, "t1": start + engine_s, "n": len(rs),
            "size": size, "padded_to": padded, "exact": exact,
            "answer_bytes": 4 * hits if rec["kind"] == "range"
            else 8 * k * len(rs),
        })
    return sorted(out, key=lambda b: b["t0"])
