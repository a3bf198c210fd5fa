"""Observability: statistics, device profiling, distributed tracing,
management surface (reference L13)."""

from .export import (  # noqa: F401
    OtlpMetricsSink,
    OtlpSink,
    chrome_trace_events,
    snapshots_to_otlp_metrics,
    spans_to_otlp,
    write_chrome_trace,
)
from .metrics import (  # noqa: F401
    MetricsHttpServer,
    MetricsSampler,
    WindowedGauge,
    prometheus_exposition,
)
from .profiling import (  # noqa: F401
    LOOP_CATEGORIES,
    LoopProfiler,
    Profiler,
    install_loop_profiler,
    loop_profiler,
    mark_loop_category,
    uninstall_loop_profiler,
)
from .slo import (  # noqa: F401
    SloMonitor,
    SloSpec,
    default_specs,
)
from .stats import (  # noqa: F401
    INGEST_STAGES,
    INGEST_STATS,
    REBALANCE_STATS,
    SLO_STATS,
    CallSiteStats,
    Histogram,
    StageSpan,
    StatsRegistry,
)
from .tracing import (  # noqa: F401
    TRACE_KEY,
    LatencyErrorPolicy,
    RetentionPolicy,
    Span,
    SpanCollector,
    critical_path_breakdown,
    current_trace,
    span_from_dict,
)
