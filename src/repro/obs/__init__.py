"""Pipeline observability: telemetry spans/counters/gauges, Chrome-trace
export, and per-step/per-series rollups.

Importing the package wires the pieces together (``trace`` registers the
jax TraceAnnotation bridge with ``telemetry``); all three submodules are
stdlib-only at import time, so ``repro.obs`` is safe to import from the
most import-light core modules.
"""
from repro.obs import telemetry
from repro.obs import trace
from repro.obs import report
from repro.obs.telemetry import (Registry, capture, counter, enabled, gauge,
                                 histo, span, start, stop)
from repro.obs.trace import chrome_trace, write_chrome_trace
from repro.obs.report import rollup, series_rollup

__all__ = ["telemetry", "trace", "report", "Registry", "capture", "counter",
           "enabled", "gauge", "histo", "span", "start", "stop",
           "chrome_trace", "write_chrome_trace",
           "rollup", "series_rollup"]
