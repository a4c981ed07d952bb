"""Runtime observability: registry, schema, spans, folding, export, trace.

Why engine metrics are *functional jit outputs*
-----------------------------------------------
The obvious way to instrument a jitted engine — ``jax.debug_callback`` or
host-side counters poked from inside the traced function — is exactly
what this repo's invariants forbid: lint rule R2 rejects host syncs in
jit-reachable code, and the jaxpr audit (``python -m repro.analysis``)
fails on *any* callback primitive in an engine jaxpr, because callbacks
serialise the device stream and make performance measurements lie.

So every device-side metric here is an ordinary traced array returned in
the engine's ``stats`` pytree, next to the results: per-mechanism
exclusion attribution, frontier occupancy, tile counts, bf16 re-check
volume, and the sharded engine's per-shard exact-phase work split.  The
device computes them as part of the same fused program (a few masked
reductions over masks the engine already materialises), and the host
folds them into the :class:`~repro.obs.registry.MetricsRegistry` at the
jit boundary (``repro.obs.fold``) — where the results are being
materialised anyway, so observability adds no synchronisation points and
cannot change results (the bit-identity test in ``tests/test_obs.py``
proves it).

The host side of each call is timed where it runs, outside any jit: the
engines' host drivers, the serving front and the retrieval server record
``SpanLog`` spans on the serving clock (``stats["spans"]``,
``ServeResult.batch``), count every device-to-host copy's bytes
(``stats["d2h_bytes"]``) and the compiles a call causes
(``stats["compiles"]``).  The engine's one added wait, a
``block_until_ready`` before the copy that would wait anyway, splits
device time from copy time.

Layout
------
- ``registry`` — counters / gauges / bounded-ring histograms with real
  cumulative buckets, JSON snapshot, Prometheus text exposition,
  ``render()`` dashboard
- ``buckets`` — the log-spaced default bucket ladder + per-metric
  overrides used by every histogram
- ``schema`` — the shared engine-stats schema + validator, and
  ``METRIC_NAMES``, the one registry of runtime metric names (lint R6)
- ``spans`` — per-request trace ids and monotonic stage timestamps, and
  ``SpanLog``: the host phases of one engine / dispatch / server call on
  the same clock, each also a ``jax.profiler.TraceAnnotation``
- ``trace`` — Chrome trace-event JSON (Perfetto) export of spans, engine
  phases, and mutation events, all on the serving clock
- ``fold`` — stats -> registry at the jit boundary; compile-cache polling
- ``export`` — snapshot files + exposition round-trip checks
"""

from repro.obs.buckets import DEFAULT_LADDER, LADDERS, ladder_for, log_ladder
from repro.obs.export import parse_prometheus, validate_exposition, write_snapshot
from repro.obs.fold import fold_engine_stats, poll_compile, shard_imbalance
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    fmt_le,
    metric_key,
    prom_name,
)
from repro.obs.schema import (
    MECHANISMS,
    METRIC_NAMES,
    SCHEMA_VERSION,
    check_stats,
    normalise_stats,
    validate_stats,
)
from repro.obs.spans import STAGES, Span, SpanLog, new_trace_id
from repro.obs.trace import (
    TraceBuffer,
    complete_event,
    instant_event,
    load_trace,
    metadata_event,
    span_events,
    validate_trace,
    write_trace,
)

__all__ = [
    "Counter",
    "DEFAULT_LADDER",
    "Gauge",
    "Histogram",
    "LADDERS",
    "MetricsRegistry",
    "MECHANISMS",
    "METRIC_NAMES",
    "SCHEMA_VERSION",
    "STAGES",
    "Span",
    "SpanLog",
    "TraceBuffer",
    "check_stats",
    "complete_event",
    "fmt_le",
    "fold_engine_stats",
    "instant_event",
    "ladder_for",
    "load_trace",
    "log_ladder",
    "metadata_event",
    "metric_key",
    "new_trace_id",
    "normalise_stats",
    "parse_prometheus",
    "poll_compile",
    "prom_name",
    "shard_imbalance",
    "span_events",
    "validate_exposition",
    "validate_stats",
    "validate_trace",
    "write_snapshot",
    "write_trace",
]
