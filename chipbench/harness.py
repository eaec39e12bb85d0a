"""Lookup by name and the pieces every cell shares.

``BENCHMARK.json`` at the root names the cells; each cell names its
configuration (``chipbench/configs/<config>.json``) and its traffic mix
(``chipbench/traffic/<traffic>.json``); each per-layer metric is a reader
of its own (``chipbench/metrics/<metric>.py`` with ``read(ctx)``).
Adding any of them needs no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, List, Optional

from chipbench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ------------------------------------------------------------------ lookup

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in bench['workloads']]})")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _json(os.path.join(HERE, "configs", f"{name}.json"))


def load_traffic(name: str) -> dict:
    return _json(os.path.join(HERE, "traffic", f"{name}.json"))


def metric_reader(name: str) -> Callable:
    """``read`` of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to ``cell``."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------------ records

@dataclass
class Unit:
    """One completed unit of the window (perf_counter seconds)."""
    t0: float
    t1: float
    nbytes: int           # source bytes
    stored: int           # bytes of the file written (0 when none)
    b_bits: int
    field: int
    step: int
    exceptions: int = 0   # elements stored as they are


@dataclass
class Check:
    """One number compared against its limit."""
    name: str
    value: float
    limit: float
    failed: int = 0       # units over the limit

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class BenchSpan:
    name: str
    t0: float
    t1: float
    tid: int


class Spans:
    """The benchmark's own spans around its calls into the program.  When
    ``annotate`` is set they also go into the profiler trace."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: List[BenchSpan] = []

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append(BenchSpan(name, t0, t1,
                                          threading.get_ident()))


# ------------------------------------------------------------------ ctx

class Ctx:
    """What a per-layer metric reader gets from a traced run.

    ``units`` are the window's units; ``spans`` the program's telemetry
    spans (``repro.obs.telemetry.SpanRecord``) of the window; ``bench``
    the benchmark's own spans; ``trace`` the reduced device trace (or
    None); ``clock`` maps perf_counter seconds to the trace's nanoseconds.
    """

    def __init__(self, mode: str, units: List[Unit], spans: list,
                 bench: List[BenchSpan], trace: Optional[tracing.DeviceTrace],
                 clock: Callable[[float], float], peaks: dict, n: int,
                 itemsize: int, window_tid: int):
        self.mode = mode
        self.units = units
        self.spans = spans
        self.bench = bench
        self.trace = trace
        self.clock = clock
        self.peaks = peaks
        self.n = n
        self.itemsize = itemsize
        self.window_tid = window_tid

    def per_unit_ms(self, names) -> Optional[float]:
        """Summed duration of the named program spans, in ms per unit."""
        names = set(names)
        tot = sum(s.duration for s in self.spans if s.name in names)
        if not self.units or not any(s.name in names for s in self.spans):
            return None
        return 1e3 * tot / len(self.units)

    def self_ms(self, bench_name: str) -> Optional[float]:
        """Self time of a benchmark span, ms per unit: its duration minus
        the top-level program spans of the same thread inside it."""
        if not self.units:
            return None
        lo, hi = self.units[0].t0, self.units[-1].t1
        # Set-up calls through the same span with no program spans on.
        outer = [b for b in self.bench if b.name == bench_name
                 and b.t0 >= lo and b.t1 <= hi]
        if not outer:
            return None
        tot = 0.0
        for b in outer:
            inner = [(s.t0, s.t1) for s in self.spans
                     if s.tid == b.tid and s.depth == 0
                     and s.t0 >= b.t0 and s.t1 <= b.t1]
            tot += (b.t1 - b.t0) - sum(y - x for x, y in
                                       tracing.union(inner, b.t0, b.t1))
        return 1e3 * tot / len(self.units)

    def host_spans(self) -> List[tracing.HostSpan]:
        """The window thread's spans on the trace clock, for naming gaps:
        the benchmark's own at depth -1, the program's at their depth."""
        out = [tracing.HostSpan(b.name, self.clock(b.t0), self.clock(b.t1),
                                -1) for b in self.bench
               if b.tid == self.window_tid]
        out += [tracing.HostSpan(s.name, self.clock(s.t0), self.clock(s.t1),
                                 s.depth) for s in self.spans
                if s.tid == self.window_tid]
        return out


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: List[Check],
                breakdown: Optional[dict] = None) -> str:
    """The last line of a run: its result keys, then the numbers
    compared, each beside its limit, under the last key."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)
