"""From a profiler trace to numbers: device busy time, idle gaps, kernel
time.

Two steps.  :func:`load` reads the ``.xplane.pb`` that ``jax.profiler``
wrote into a plain :class:`Trace`: the device operations of every TPU
plane (the ``XLA Ops`` line), the benchmark's own host spans (names
starting ``bench/``) and the runtime's device-to-host copies, all in
seconds on the host's ``time.perf_counter`` clock.  The profiler keeps its own epoch; the ``bench/anchor`` span, opened
just after the trace starts at a ``perf_counter`` time the harness
records, gives the offset.  The rest of the module works on a
:class:`Trace` alone, so it is tested on a small trace kept beside the
tests.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

import numpy as np

ANCHOR = "bench/anchor"
HOST_PREFIX = "bench/"
# runtime host events kept beside the benchmark's spans: the copy of a
# device buffer into a host array (what a long idle gap often waits on)
HOST_RUNTIME = ("CommonPjRtBuffer::ToLiteral",)
_LAYOUT = re.compile(r"\{[^{}]*\}")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    """Device operations and host spans of one traced window, in seconds
    on the host clock.  ``ops`` rows: (device, name, start, end); ``spans``
    rows: (name, start, end)."""

    window: tuple[float, float]
    ops: list
    spans: list

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(tuple(d["window"]), [tuple(o) for o in d["ops"]],
                   [tuple(s) for s in d["spans"]])


def op_name(name) -> str:
    """An operation's name as the reduction groups it: the HLO instruction
    the trace names it by, without layouts, cut to 160 characters."""
    s = str(name)
    for _ in range(2):  # layouts nest one level inside operand lists
        s = _LAYOUT.sub("", s)
    return s[:160]


def load(trace_dir: str, anchor_at: float, window: tuple[float, float]) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``.  ``anchor_at`` is
    the ``perf_counter`` time at which the ``bench/anchor`` span opened;
    ``window`` the host times at which tracing started and stopped."""
    from jax.profiler import ProfileData

    paths = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    prof = ProfileData.from_file(paths[0])
    spans, anchor_ns = [], None
    ops = []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((plane.name, op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR and anchor_ns is None:
                        anchor_ns = ev.start_ns
                    elif (str(ev.name).startswith(HOST_PREFIX)
                          or ev.name in HOST_RUNTIME):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    if anchor_ns is None:
        raise RuntimeError("the trace holds no bench/anchor span")
    off = anchor_at - anchor_ns * 1e-9

    def host(ns: float) -> float:
        return ns * 1e-9 + off

    return Trace(
        window=tuple(window),
        ops=sorted((d, n, host(a), host(b)) for d, n, a, b in ops),
        spans=sorted(((n, host(a), host(b)) for n, a, b in spans),
                     key=lambda s: s[1]),
    )


def _clip(intervals, lo: float, hi: float) -> np.ndarray:
    iv = np.asarray([(max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi], np.float64).reshape(-1, 2)
    return iv[np.argsort(iv[:, 0], kind="stable")] if len(iv) else iv


def union(intervals, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted intervals covering the union of ``intervals``
    within ``[lo, hi]``."""
    out: list[list[float]] = []
    for a, b in _clip(intervals, lo, hi):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, np.float64).reshape(-1, 2)


def devices(tr: Trace) -> list[str]:
    return sorted({o[0] for o in tr.ops})


def busy_s(tr: Trace) -> float:
    """Seconds within the window in which some operation ran, averaged
    over the devices that ran any."""
    lo, hi = tr.window
    devs = devices(tr)
    if not devs:
        return 0.0
    per = [union([(a, b) for d, _, a, b in tr.ops if d == dev], lo, hi)
           for dev in devs]
    return float(np.mean([float(np.sum(u[:, 1] - u[:, 0])) for u in per]))


def idle_share(tr: Trace | None) -> float | None:
    """Percent of the window in which no operation ran on the chip:
    1 - busy / window.  None without a trace or without any device
    operation in it."""
    if tr is None or not tr.ops:
        return None
    return 100.0 * (1.0 - busy_s(tr) / (tr.window[1] - tr.window[0]))


def idle_gaps(tr: Trace, device: str | None = None) -> list[tuple[float, float]]:
    """The intervals of the window in which ``device`` (default: the first
    device) ran nothing, longest first."""
    lo, hi = tr.window
    devs = devices(tr)
    if not devs:
        return [(lo, hi)]
    dev = device or devs[0]
    u = union([(a, b) for d, _, a, b in tr.ops if d == dev], lo, hi)
    edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
    gaps = [(float(a), float(b)) for a, b in edges if b > a]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """[name, seconds] of the ``n`` operations that took most device time
    in the window, summed over their events."""
    lo, hi = tr.window
    tot: dict[str, float] = {}
    for _, name, a, b in tr.ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            tot[name] = tot.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def matching(tr: Trace, patterns) -> list[tuple]:
    """Operations whose name matches any of ``patterns`` (regexes)."""
    rx = [re.compile(p) for p in patterns]
    return [o for o in tr.ops if any(r.search(o[1]) for r in rx)]


def host_label(tr: Trace, a: float, b: float, extra=(),
               default: str = "idle") -> str:
    """What the host was doing over ``[a, b]``: the innermost span (of the
    trace's, or of ``extra`` (name, start, end) intervals) that covers the
    gap's middle, else ``default``."""
    mid = 0.5 * (a + b)
    best = None
    for name, s, e in list(tr.spans) + list(extra):
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else default
