"""Chrome-trace (``chrome://tracing`` / Perfetto) export of a telemetry
capture, plus the jax device-annotation bridge.

``chrome_trace(reg)`` converts a :class:`~repro.obs.telemetry.Registry`
into the Trace Event Format dict Chrome/Perfetto load directly:

  * every span becomes a complete ("ph": "X") event on its own thread
    lane -- the entropy pool threads ("entropy_N"), the overlap/finalize
    workers ("finalize_N", "shard-finalize_N", "ckpt-save_N") and the
    main thread each render as a separate track, so "where did the time
    go" for one compressed step is visible at a glance;
  * gauge sample series become counter ("ph": "C") events (e.g. the
    FinalizeQueue depth over time);
  * counters and histogram summaries ride in ``otherData``.

Open a written file at chrome://tracing or https://ui.perfetto.dev.

Device bridging: importing this module registers a
``jax.profiler.TraceAnnotation`` factory with the telemetry layer, so
while a capture is active every host span also appears inside a jax
profiler capture, on the profiler's clock and lined up with the device
kernels it launched.  The import is lazy and failure-tolerant --
environments without jax still get host spans.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Optional

from repro.obs import telemetry

__all__ = ["chrome_trace", "write_chrome_trace"]

_PID = 0                    # single-process trace; lanes are threads


def _jax_annotation(name: str):
    """Annotation factory: a TraceAnnotation when jax's profiler is
    importable, else None (span records host-side only)."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # pragma: no cover - jax is present in this repo
        return None
    return TraceAnnotation(name)


telemetry.set_annotation_factory(_jax_annotation)


def chrome_trace(reg: Optional[telemetry.Registry] = None) -> Dict[str, Any]:
    """Trace Event Format dict of a capture (the active one by default)."""
    reg = reg if reg is not None else telemetry.active()
    if reg is None:
        raise ValueError("no registry: pass one or run inside capture()")
    snap = reg.snapshot()
    events = []
    # Lane key is (os tid, thread name), not the tid alone: the OS reuses
    # idents, so a finalize worker that exits before an entropy pool
    # thread starts would otherwise be merged into the pool's lane.
    lanes: Dict[tuple, int] = {}
    for rec in snap["spans"]:
        tid = lanes.setdefault((rec.tid, rec.tname), len(lanes))
        args = {k: _jsonable(v) for k, v in rec.attrs.items()}
        if rec.error is not None:
            args["error"] = rec.error
        events.append({
            "name": rec.name, "cat": "host", "ph": "X",
            "ts": (rec.t0 - reg.t0) * 1e6, "dur": rec.duration * 1e6,
            "pid": _PID, "tid": tid, "args": args,
        })
    for (_, tname), tid in sorted(lanes.items(), key=lambda kv: kv[1]):
        events.append({"name": "thread_name", "ph": "M", "pid": _PID,
                       "tid": tid, "args": {"name": tname}})
    for name, samples in sorted(snap["gauges"].items()):
        for t, v in samples:
            events.append({"name": name, "ph": "C", "ts": t * 1e6,
                           "pid": _PID, "args": {"value": v}})
    hist_summary = {
        name: {"count": len(vs), "mean": sum(vs) / len(vs), "max": max(vs)}
        for name, vs in sorted(snap["hists"].items()) if vs}
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"counters": snap["counters"],
                          "histograms": hist_summary}}


def write_chrome_trace(path: str,
                       reg: Optional[telemetry.Registry] = None) -> str:
    """Write the Chrome-trace JSON for `reg` to `path`; returns `path`."""
    with open(path, "w") as f:
        json.dump(chrome_trace(reg), f)
    return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)
