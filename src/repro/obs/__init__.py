"""Telemetry plane: span tracing, labeled metrics registry, per-tenant
SLO tracking, OpenMetrics export, flight recorder.

Zero-dependency (stdlib + optional jax profiler bridge) observability for
the solve → fusion → kernel stack.  See docs/observability.md.
"""
from .compile import (  # noqa: F401
    compile_stats,
    reset_compile_stats,
)
from .export import (  # noqa: F401
    parse_openmetrics,
    render_openmetrics,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter_delta,
    registry,
)
from .provenance import provenance  # noqa: F401
from .recorder import FlightRecorder  # noqa: F401
from .slo import (  # noqa: F401
    P2Quantile,
    SLOTracker,
    TenantSLO,
    solve_slo_summary,
)
from .trace import (  # noqa: F401
    Span,
    Tracer,
    active,
    install,
    span,
    tracing,
    uninstall,
    validate_chrome_trace,
)
