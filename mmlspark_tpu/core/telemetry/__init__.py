"""Unified observability layer: records, counters, gauges, histograms,
spans, and exposition — one package, one registry.

Grown from the single-module `core/telemetry.py` (flat event counters +
verb records); the historical surface is preserved verbatim:

* ``incr`` / ``counters`` / ``reset_counters`` — the PR-4 event-counter
  ledger, now backed by :data:`metrics.REGISTRY` so every counter a
  fault/shed/breaker path bumps shows up in ``/metrics`` and
  ``export_snapshot()`` with zero changes at the call sites.
* ``log_verb`` / ``recent_records`` / ``clear_records`` — stage-verb
  JSON records (:mod:`.records`).
* ``StopWatch`` — re-export of the ONE canonical
  :class:`mmlspark_tpu.utils.stopwatch.StopWatch` (the duplicate that
  lived here was merged into it; identity is pinned by tests).

New surface (see docs/observability.md):

* spans — ``span()``, ``use_trace()``, ``record_span()``,
  ``trace_headers()`` / ``extract_trace()`` for X-Trace-Id propagation,
  ``get_trace()`` / ``span_tree()`` behind ``/trace/<id>``.
* metrics — ``histogram(name)`` / ``gauge(name)`` on the process
  registry; names follow ``layer.component.metric`` and must be
  declared in :data:`metrics.DECLARED_METRICS` (CI-linted).
* exposition — ``render_prometheus()`` (``/metrics``),
  ``export_snapshot()`` (bench / chaos_soak / obs_report),
  ``render_chrome_trace()`` (``/trace.json`` → Perfetto).
* device — ``track_compiles()`` / ``watch_compiles()`` (the XLA compile
  sentry), ``sample_device_memory()`` / ``start_memory_sampler()`` (HBM
  + live-buffer gauges); ``span()``, ``phase()`` and
  ``device_annotation()`` enter a ``jax.profiler.TraceAnnotation`` of
  their name whenever jax is imported, so a profiler capture holds the
  program's spans on the device trace's clock.
* goodput plane — ``STORE`` (:class:`timeseries.TimeSeriesStore`,
  bounded recent history with rate/delta/quantile-over-time) and
  ``LEDGER`` (:class:`goodput.GoodputLedger`, per-step timelines +
  lost-time attribution), federated by ``merge_timeseries_exports`` /
  ``merge_goodput_exports`` and served in the ``timeseries`` /
  ``goodput`` blocks of ``export_snapshot()``.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from ...utils.stopwatch import StopWatch
from .metrics import (
    BUCKET_FAMILIES,
    BYTE_BUCKETS,
    DECLARED_METRICS,
    FILL_BUCKETS,
    Gauge,
    HISTOGRAM_FAMILY,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    buckets_for,
    default_buckets,
    is_declared,
)
from .records import clear_records, log_verb, logger, recent_records
from .spans import (
    clear_spans,
    current_context,
    current_trace_id,
    extract_trace,
    get_trace,
    phase,
    recent_spans,
    record_span,
    span,
    span_tree,
    trace_headers,
    use_trace,
)
from .exposition import (
    export_snapshot,
    format_latency_table,
    format_span_tree,
    render_chrome_trace,
    render_prometheus,
)
from .fleet import (
    FlightRecorder,
    SLO,
    SLOEngine,
    default_slos,
    merge_snapshots,
    merge_goodput_exports,
    merge_histogram_snapshots,
    merge_timeseries_exports,
    render_fleet_prometheus,
    stitch_spans,
)
from .goodput import (
    GoodputLedger,
    LEDGER,
    LOST_KINDS,
    StepTimeline,
    detect_straggler,
)
from .timeseries import SAMPLED_SERIES, STORE, TimeSeriesStore
from .device import (
    SENTRY,
    CompileSentry,
    MemorySampler,
    device_annotation,
    sample_device_memory,
    start_memory_sampler,
    track_compiles,
    watch_compiles,
)

__all__ = [
    # counters (historical surface, registry-backed)
    "incr", "counters", "reset_counters",
    # records
    "log_verb", "recent_records", "clear_records", "logger",
    # stopwatch
    "StopWatch",
    # metrics
    "REGISTRY", "MetricsRegistry", "Gauge", "Histogram", "gauge",
    "histogram", "default_buckets", "BYTE_BUCKETS", "FILL_BUCKETS",
    "BUCKET_FAMILIES", "HISTOGRAM_FAMILY", "buckets_for",
    "DECLARED_METRICS", "is_declared",
    # spans
    "span", "phase", "record_span", "use_trace", "current_context",
    "current_trace_id", "trace_headers", "extract_trace", "get_trace",
    "span_tree", "recent_spans", "clear_spans",
    # exposition
    "render_prometheus", "export_snapshot", "render_chrome_trace",
    "format_span_tree", "format_latency_table",
    # fleet federation (merge / stitch / SLO / incidents)
    "merge_snapshots", "merge_histogram_snapshots",
    "merge_timeseries_exports", "merge_goodput_exports",
    "render_fleet_prometheus", "stitch_spans", "SLO", "SLOEngine",
    "default_slos", "FlightRecorder",
    # goodput plane (timeseries engine + lost-time ledger, PR 20)
    "TimeSeriesStore", "STORE", "SAMPLED_SERIES",
    "GoodputLedger", "StepTimeline", "LEDGER", "LOST_KINDS",
    "detect_straggler",
    # device (compile sentry, memory gauges, annotations)
    "SENTRY", "CompileSentry", "track_compiles", "watch_compiles",
    "sample_device_memory", "MemorySampler", "start_memory_sampler",
    "device_annotation",
]


def incr(name: str, n: int = 1) -> None:
    """Bump a named event counter (dotted names: 'serving.shed')."""
    REGISTRY.incr(name, n)


def counters(prefix: Optional[str] = None) -> Dict[str, int]:
    """Snapshot the event counters, optionally filtered by name prefix."""
    return REGISTRY.counter_values(prefix)


def reset_counters(prefix: Optional[str] = None) -> None:
    """Zero the counters (tests); with `prefix`, only matching names."""
    REGISTRY.reset_counters(prefix)


def gauge(name: str) -> Gauge:
    """The process-registry gauge `name` (created on first touch)."""
    return REGISTRY.gauge(name)


def histogram(name: str, boundaries: Optional[Sequence[float]] = None,
              **labels: str) -> Histogram:
    """The process-registry histogram `name` (first touch fixes the
    bucket ladder for the whole labeled family)."""
    return REGISTRY.histogram(name, boundaries, **labels)
