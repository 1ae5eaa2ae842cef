"""Observability: span tracing, Chrome-trace export, metrics, profiling,
and the perf-regression gate.

Import layering matters here: :mod:`repro.obs.regress` (and this package
``__init__``) must stay stdlib-only so the CI regress-gate lane can run
``benchmarks/check_regress.py`` on a bare interpreter, and
:mod:`repro.obs.profile` and :func:`repro.obs.trace.span` import jax lazily
inside their context managers.
"""
from repro.obs.export import (
    to_chrome_trace,
    validate_chrome_trace,
    write_trace,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    read_jsonl,
)
from repro.obs.profile import CompileStats, profile_capture, track_compile_time
from repro.obs.regress import (
    GATES,
    Finding,
    MetricGate,
    bench_key,
    compare_dirs,
    compare_payloads,
    format_findings,
)
from repro.obs.trace import (
    DEFAULT_ROUND_S,
    ROUND_TRACK,
    SPAN_PREFIX,
    Span,
    TraceRecorder,
    span,
)

__all__ = [
    "CompileStats",
    "Counter",
    "DEFAULT_ROUND_S",
    "Finding",
    "GATES",
    "Gauge",
    "Histogram",
    "MetricGate",
    "MetricsRegistry",
    "ROUND_TRACK",
    "SPAN_PREFIX",
    "Span",
    "TraceRecorder",
    "bench_key",
    "compare_dirs",
    "compare_payloads",
    "format_findings",
    "profile_capture",
    "read_jsonl",
    "span",
    "to_chrome_trace",
    "track_compile_time",
    "validate_chrome_trace",
    "write_trace",
]
