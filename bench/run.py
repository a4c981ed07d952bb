#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one chip.  It makes the corpus and the query pool
from ``--seed``, builds the system through its normal entry point, warms
the shapes this cell's traffic uses (set-up, reported as ``setup_s``),
drives the traffic for ``--seconds``, checks the answers the window
produced against the float64 reference, and prints one JSON object as its
last line of standard output.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` the middle third of the
window is traced and the metrics are the cell's per-layer metrics.

Everything about a cell is found by name (``bench/cell.py``): the
configuration, its data generator and its metric's reference, the traffic
mix, its entry point and loop, the cell's own numbers and one reader per
metric, each a file of its own under ``bench/``.  It exits non-zero, printing no result, without a TPU (or with fewer
chips than the cell asks for), outside a checkout of the program, and on
any error.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import control as control_ref  # noqa: E402
from bench import reference, roofline, trace_reduce  # noqa: E402
from bench.cell import load_cell, reader, request_params  # noqa: E402

clock = time.perf_counter
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    pass


class Tracer:
    """Traces ``[start, stop]`` (host clock) from a thread of its own, so
    that neither loop waits on the profiler."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.window = None
        self.anchor = None
        self.error = None
        self._thread = None

    def schedule(self, start: float, stop: float) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False

        def body() -> None:
            try:
                time.sleep(max(0.0, start - clock()))
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self.anchor = clock()
                with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
                    pass
                a = clock()
                time.sleep(max(0.0, stop - clock()))
                self.window = (a, clock())
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — reported by join()
                self.error = e

        self._thread = threading.Thread(target=body, name="bench-tracer")
        self._thread.start()

    def join(self) -> trace_reduce.Trace:
        self._thread.join()
        if self.error is not None:
            raise self.error
        return trace_reduce.load(self.dir, self.anchor, self.window)


def _quartiles_line(name: str, vals) -> str:
    v = np.asarray(vals, np.float64)
    if v.size == 0:
        return f"{name}: none"
    return (f"{name}: p50={np.percentile(v, 50):.6g} "
            f"p95={np.percentile(v, 95):.6g} max={v.max():.6g} n={v.size}")


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _overhead(c: dict, rec: dict, tr: trace_reduce.Trace) -> str:
    """The end-to-end number inside the traced window against the rest of
    the same run."""
    a, b = tr.window
    if rec["loop"] == "open":
        lat = c["loop"].latencies(rec)
        due = np.asarray([r["due"] for r in rec["requests"]])
        inside = (due >= a) & (due <= b)
        if inside.any() and (~inside).any():
            return (f"p95_ms traced {1e3 * np.percentile(lat[inside], 95):.6g}"
                    f" untraced {1e3 * np.percentile(lat[~inside], 95):.6g}")
        return "not measurable"
    calls = [x for x in rec["calls"] if x["res"] is not None]
    inside = [x for x in calls if x["t0"] >= a and x["t1"] <= b]
    outside = [x for x in calls if x["t1"] < a or x["t0"] > b]

    def qps(xs):
        return sum(len(x["qidx"]) for x in xs) / sum(x["t1"] - x["t0"]
                                                     for x in xs)

    if inside and outside:
        return f"qps traced {qps(inside):.6g} untraced {qps(outside):.6g}"
    return "not measurable"


def _breakdown(tr: trace_reduce.Trace, batches: list, rec: dict) -> dict:
    """Top device operations, and the longest idle gaps labelled by what
    the host was doing: a benchmark span, the runtime's device-to-host copy
    or, in the open loop, the front's engine call (``front/engine``) or no
    batch in flight (``front/waiting``)."""
    extra, default = [], "bench/between_calls"
    if rec["loop"] == "open":
        extra = [("front/engine", b["t0"], b["t1"]) for b in batches]
        default = "front/waiting"
    gaps = trace_reduce.idle_gaps(tr)[:10]
    return {
        "device_ops": trace_reduce.top_ops(tr, 10),
        "idle_gaps": [[trace_reduce.host_label(tr, a, b, extra, default),
                       b - a] for a, b in gaps],
    }


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             overrides=None, trace_dir: str | None = None,
             control: bool = False) -> dict:
    """One run of cell ``name``; returns the result object.  Tests pass
    ``require_tpu=False`` and ``overrides(cell)`` (a function that edits
    the loaded cell in place, e.g. to a tiny size).  ``control=True``
    compares the control's answers to the checked requests
    (``bench/control.py``) in place of the program's: the run then has to
    come out not correct."""
    c = load_cell(name, root)
    if overrides is not None:
        overrides(c)
    cfg, traffic, cell = c["config"], c["traffic"], c["cell"]
    import jax

    if require_tpu:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
            root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < c["chips"]):
        raise NoChip(f"cell {name} needs {c['chips']} TPU chip(s); JAX "
                     f"found {len(devs)} x {devs[0].platform}")
    dev = devs[0]
    peak = roofline.peaks(dev.device_kind) if require_tpu else None
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    lowerings: list[float] = []
    cache_use = {_CACHE_HIT: 0, _CACHE_MISS: 0}

    def on_event(event: str, _secs: float, **_kw) -> None:
        if event == _LOWERING:
            lowerings.append(clock())

    def on_cache(event: str, **_kw) -> None:
        if event in cache_use:
            cache_use[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    jax.monitoring.register_event_listener(on_cache)
    try:
        corpus, pool = c["data"].make(cfg, seed)
        entry = c["entry"].Entry(cfg, corpus)
        kind = traffic["kind"]
        backend = entry.warm(pool, traffic, request_params(traffic, cell))
        if backend != cfg["index"]["backend"]:
            raise RuntimeError(f"engine resolved backend {backend!r}, the "
                               f"configuration states "
                               f"{cfg['index']['backend']!r}")
        tmp = None
        tracer = None
        if trace:
            tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            tracer = Tracer(tmp)
        setup_s = clock() - _T_START
        setup_cache = dict(cache_use)
        if tracer is not None:
            now = clock()
            tracer.schedule(now + seconds / 3, now + 2 * seconds / 3)
        rec = c["loop"].run(entry, pool, traffic, cell, seed, seconds)
        tr = tracer.join() if tracer is not None else None
        in_window = sum(rec["t0"] <= t <= rec["t1"] for t in lowerings)
        stats = dev.memory_stats() or {}
        mem_peak = int(stats.get("peak_bytes_in_use", 0))
        entry.close()
        del entry
        answers = c["loop"].answers(rec)
        attempted = (len(rec["requests"]) if rec["loop"] == "open"
                     else sum(len(x["qidx"]) for x in rec["calls"]))
        failed = attempted - len(answers)
        t_ref = clock()
        ref = reference.Reference(c["distance"], corpus)
        checked = reference.sample(answers, int(cell["n_check"]), seed, kind)
        if control:
            checked = control_ref.answers(c["distance"], kind,
                                          traffic.get("k"), corpus, pool,
                                          checked)
        nums = reference.numbers(ref, kind, traffic.get("k"), pool, checked,
                                 failed)
        ref_s = clock() - t_ref
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        jax.monitoring.unregister_event_listener(on_cache)
    limits = cell["checks"]
    correct = set(nums) == set(limits) and all(
        nums[k] <= float(limits[k]) for k in limits)
    batches = c["loop"].batches(rec, int(cfg["index"]["n_pivots"]))
    ctx = types.SimpleNamespace(
        cell=c, cfg=cfg, traffic=traffic, rec=rec, batches=batches,
        trace=tr, peak=peak, n_valid=len(corpus), setup_s=setup_s,
        loop=c["loop"],
    )
    metrics = {}
    for m in (c["per_layer"] if trace else c["end_to_end"]):
        v = reader(c, m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    _say(f"{len(devs)} x {dev.device_kind}; setup_s={setup_s:.3f}; "
         f"reference check {ref_s:.1f}s over {len(checked)} "
         f"{'control' if control else 'program'} answers")
    _say(f"set-up programs: {setup_cache[_CACHE_HIT]} found in the compile "
         f"cache, {setup_cache[_CACHE_MISS]} compiled")
    _say(f"compiles inside the window: {in_window}")
    if rec["loop"] == "open":
        _say(_quartiles_line("generator lateness s",
                             c["loop"].lateness(rec)))
        _say(_quartiles_line("front batch rows", [b["n"] for b in batches]))
    _say(f"peak_bytes_in_use={mem_peak}")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if tr is not None:
        busy = trace_reduce.busy_s(tr)
        device["busy_s"] = busy
        device["window_s"] = tr.window[1] - tr.window[0]
        _say(f"tracing overhead: {_overhead(c, rec, tr)}")
        out["breakdown"] = _breakdown(tr, batches, rec)
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        else:
            keep = {"trace": tr.to_json(), "batches": [
                {k: (v.tolist() if isinstance(v, np.ndarray) else v)
                 for k, v in b.items() if k != "stats"} for b in batches]}
            (Path(trace_dir) / "reduced.json").write_text(json.dumps(keep))
    out["checks"] = {k: {"value": nums[k], "limit": float(limits[k])}
                     for k in limits}
    for k in limits:
        _say(f"check {k} = {nums.get(k)!r} (limit {float(limits[k])!r})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace here instead of a temporary "
                         "directory")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401 — the program under test
    except ImportError:
        sys.path.insert(0, str(ROOT / "src"))
        try:
            import repro  # noqa: F401
        except ImportError as e:
            print(f"bench: the program is not in this checkout ({e})",
                  file=sys.stderr)
            return 2
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), trace_dir=args.trace_dir)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
