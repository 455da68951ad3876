"""Runtime observability: request-lifecycle Chrome/Perfetto tracing, the
program's profiler spans (``trace.span``), the memory-gap auditor,
windows and SLOs, and registry-backed metrics export. See
:mod:`repro.serving.obs.observer` for the wiring overview.

Submodule attributes resolve lazily (PEP 562): the engine imports
``obs.series`` at module load, and an eager ``obs.export`` import here
would cycle back through ``serving.cluster`` into the half-initialized
engine module.
"""
import importlib

_EXPORTS = {
    "BoundedSeries": "series", "DEFAULT_SERIES_MAXLEN": "series",
    "Tracer": "trace", "span": "trace", "validate_chrome_trace": "trace",
    "EngineObserver": "observer", "Observability": "observer",
    "CLUSTER_SPECS": "export", "SERVING_SPECS": "export",
    "MetricSpec": "export", "MetricsEmitter": "export",
    "lint_prometheus": "export", "metrics_from_json": "export",
    "metrics_to_json": "export", "prometheus_text": "export",
    "MemoryGapAuditor": "auditor", "MemoryGapStats": "auditor",
    "WasteBreakdown": "auditor", "audit_engine": "auditor",
    "SLO": "windows", "SLOEvent": "windows", "SLOMonitor": "windows",
    "WindowAggregator": "windows", "WindowStat": "windows",
    "default_slos": "windows",
    "Dashboard": "dashboard", "html_report": "dashboard",
    "write_html_report": "dashboard",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{mod}"), name)


def __dir__():
    return __all__
