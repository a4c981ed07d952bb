"""Per-request serving spans, and the host spans of one call.

A request through the ServingFront passes admit -> queue -> batch ->
dispatch -> engine -> demux; a :class:`Span` carries one monotonic
timestamp per stage (the serving stack's clock, ``repro.serve.queue.now``
— R1 forbids ``time.time`` anywhere in src).  ``durations()`` turns the
marks into per-stage intervals, which the front records into
``serve/span_s{stage=...}`` histograms and returns on each
``ServeResult`` for the per-request "explain" trace.

Trace ids are process-unique monotonically increasing ints (cheap,
lock-free via ``itertools.count``) rendered as ``t000042`` strings so
they sort lexicographically in logs.

:class:`SpanLog` records the host phases of one call (the engine's
``engine/*`` phases, the front's ``dispatch/*``, the server's
``server/search``) as ``(name, start, end, parent)`` rows stamped with
the same ``now()``.  Each span also opens a ``jax.profiler.TraceAnnotation``
of its name, so inside any ``jax.profiler.trace`` the same spans appear
on the profiler's host plane, beside the device operations they caused.
Recording is unconditional: a span costs about a microsecond with no
profiler running.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from repro.serve.queue import now

__all__ = ["STAGES", "Span", "SpanLog", "new_trace_id"]

# stage marks in causal order: `admit` is stamped on submit(); the rest
# are stamped by the driver thread as the batch moves through dispatch
STAGES = ("admit", "batch", "dispatch", "engine", "demux")

_ids = itertools.count(1)


def new_trace_id() -> str:
    return f"t{next(_ids):06d}"


@dataclass
class Span:
    """Monotonic stage timestamps for one request."""

    trace_id: str = field(default_factory=new_trace_id)
    marks: dict = field(default_factory=dict)

    def mark(self, stage: str, t: float | None = None) -> float:
        """Stamp ``stage`` at monotonic time ``t`` (default: now)."""
        if stage not in STAGES:
            raise ValueError(f"unknown stage {stage!r}, expected {STAGES}")
        t = now() if t is None else float(t)
        self.marks[stage] = t
        return t

    def durations(self) -> dict:
        """Intervals between consecutive *recorded* marks, in seconds.

        Keys are named for what the request was doing during the
        interval: ``queue`` (admit->batch), ``batch`` (batch->dispatch,
        padding/assembly), ``engine`` (dispatch->engine, the jitted
        call), ``demux`` (engine->demux, per-request slicing), plus
        ``total`` (first mark -> last mark).  Stages never marked are
        simply absent.
        """
        names = {
            ("admit", "batch"): "queue",
            ("batch", "dispatch"): "batch",
            ("dispatch", "engine"): "engine",
            ("engine", "demux"): "demux",
        }
        seen = [s for s in STAGES if s in self.marks]
        out: dict = {}
        for a, b in zip(seen, seen[1:]):
            out[names.get((a, b), f"{a}_to_{b}")] = (
                self.marks[b] - self.marks[a]
            )
        if len(seen) >= 2:
            out["total"] = self.marks[seen[-1]] - self.marks[seen[0]]
        return out


class _OpenSpan:
    """One span of a :class:`SpanLog` while it is open; after the ``with``
    block its ``start`` and ``end`` hold the recorded times."""

    __slots__ = ("log", "name", "ann", "i", "start", "end")

    def __init__(self, log: "SpanLog", name: str, args: dict):
        self.log = log
        self.name = name
        self.ann = TraceAnnotation(name, **args)

    def __enter__(self) -> "_OpenSpan":
        log = self.log
        self.ann.__enter__()
        self.i = len(log.records)
        self.start = now()
        log.records.append(
            (self.name, self.start, None, log._open[-1] if log._open else None)
        )
        log._open.append(self.i)
        return self

    def __exit__(self, *exc) -> bool:
        self.end = now()
        log = self.log
        log._open.pop()
        name, start, _, parent = log.records[self.i]
        log.records[self.i] = (name, start, self.end, parent)
        self.ann.__exit__(*exc)
        return False


class SpanLog:
    """The host spans of one call, in the order they opened.

    ``records`` rows are ``(name, start, end, parent)``: seconds on the
    serving clock, and ``parent`` the row index of the enclosing span
    (None for a root).  A span still open has ``end`` None.  Names are
    fixed strings; what varies goes in the keyword ``args``, which reach
    the profiler's annotation only.  One log belongs to one thread.
    """

    __slots__ = ("records", "_open")

    def __init__(self):
        self.records: list[tuple] = []
        self._open: list[int] = []

    def span(self, name: str, **args) -> _OpenSpan:
        """``with log.span(name, **args):`` records the block's interval."""
        return _OpenSpan(self, name, args)

    def adopt(self, records) -> None:
        """Append another log's ``records`` under the span open now (or as
        roots when none is): how a caller nests the spans of the engine
        call it made."""
        base = len(self.records)
        top = self._open[-1] if self._open else None
        self.records.extend(
            (name, start, end, top if parent is None else parent + base)
            for name, start, end, parent in records
        )
