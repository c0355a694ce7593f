"""In-graph observability: metric streams, span tracing, flight recorder.

This package deliberately imports nothing from :mod:`repro.core` — the core
engines import ``repro.obs`` at module level, and a reverse import would
create a cycle. See DESIGN.md §14 for the semantics.
"""
from repro.obs.metrics import (
    DEFAULT_STREAMS,
    ENGINE_STREAMS,
    STREAMS,
    MetricsFrame,
    MetricsSpec,
    build_frame,
    compute_host_streams,
    compute_scan_streams,
    scan_stream_names,
    stream_engines,
    unsupported_streams,
)
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import (
    count,
    disable_tracing,
    enable_tracing,
    span,
    take_counters,
    tracing_enabled,
)

__all__ = [
    "DEFAULT_STREAMS",
    "ENGINE_STREAMS",
    "STREAMS",
    "MetricsFrame",
    "MetricsSpec",
    "build_frame",
    "compute_host_streams",
    "compute_scan_streams",
    "scan_stream_names",
    "stream_engines",
    "unsupported_streams",
    "FlightRecorder",
    "count",
    "disable_tracing",
    "enable_tracing",
    "span",
    "take_counters",
    "tracing_enabled",
]
