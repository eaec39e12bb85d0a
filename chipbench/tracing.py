"""Reduction of a JAX profiler trace to device metrics.

``load_xplane`` flattens the ``.xplane.pb`` the profiler writes into plain
``Event`` records; everything after that works on those records alone, so
the arithmetic is checked on a small trace without a chip.

Times are in nanoseconds on the trace's own clock.  The benchmark wraps its
measured window in a host annotation named ``WINDOW``; device busy time,
idle gaps and per-op time are all clipped to that annotation.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

WINDOW = "chipbench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    plane: str
    line: str
    name: str
    t0: float
    dur: float
    module: str = ""

    @property
    def t1(self) -> float:
        return self.t0 + self.dur


@dataclass
class HostSpan:
    """A span of the host's work, on the trace's clock."""
    name: str
    t0: float
    t1: float
    depth: int = 0


def load_xplane(log_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                module = ""
                for k, v in e.stats:
                    if k == "hlo_module":
                        module = str(v)
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 module))
    return out


def window(events: List[Event]) -> Tuple[float, float]:
    """The measured window: the host annotation ``WINDOW``."""
    for e in events:
        if e.name == WINDOW and not e.plane.startswith(DEVICE_PREFIX):
            return e.t0, e.t1
    raise ValueError(f"trace holds no {WINDOW!r} annotation")


def device_planes(events: List[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)},
                  key=lambda p: int(p[len(DEVICE_PREFIX):] or 0))


def device_ops(events: List[Event], plane: str, lo: float, hi: float,
               line: str = OPS_LINE) -> List[Event]:
    """Events of one device's ``line`` that overlap [lo, hi)."""
    return [e for e in events if e.plane == plane and e.line == line
            and e.t1 > lo and e.t0 < hi]


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Disjoint sorted union of intervals, clipped to [lo, hi)."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi) between the busy ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans: List[HostSpan], t: float) -> str:
    """Name of the innermost host span open at time ``t``."""
    best: Optional[HostSpan] = None
    for s in spans:
        if s.t0 <= t < s.t1 and (best is None or s.depth > best.depth
                                 or (s.depth == best.depth
                                     and s.t0 >= best.t0)):
            best = s
    return best.name if best is not None else "(no span)"


@dataclass
class DeviceTrace:
    """The measured window of one trace, per device: its op events and
    its program (module) events."""
    lo: float
    hi: float
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)

    @classmethod
    def from_events(cls, events: List[Event]) -> "DeviceTrace":
        lo, hi = window(events)
        planes = device_planes(events)
        if not planes:
            raise ValueError("trace holds no device plane")
        return cls(lo, hi, {p: device_ops(events, p, lo, hi)
                            for p in planes},
                   {p: device_ops(events, p, lo, hi, MODULES_LINE)
                    for p in planes})

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy(self, plane: str) -> List[Tuple[float, float]]:
        return union(((e.t0, e.t1) for e in self.ops[plane]), self.lo,
                     self.hi)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        tot = sum(b - a for p in self.ops for a, b in self.busy(p))
        return tot * 1e-9 / len(self.ops)

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def op_seconds(self, keep: Callable[[Event], bool]) -> float:
        """Device seconds of the kept ops inside the window, averaged over
        the devices (each op clipped to the window)."""
        tot = sum(min(e.t1, self.hi) - max(e.t0, self.lo)
                  for p in self.ops for e in self.ops[p] if keep(e))
        return tot * 1e-9 / len(self.ops)

    def program_seconds(self, name: str) -> float:
        """Device seconds of the compiled programs whose name holds
        ``name`` (``jit_chain_advance`` for "chain_advance"), averaged
        over the devices: the program events where the trace has them,
        else the ops that name the program as their module."""
        if any(self.modules.values()):
            tot = sum(min(e.t1, self.hi) - max(e.t0, self.lo)
                      for p in self.modules for e in self.modules[p]
                      if name in e.name)
            return tot * 1e-9 / len(self.modules)
        return self.op_seconds(lambda e: name in e.module)

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` op names with the most device time (seconds averaged
        over the devices)."""
        tot: Dict[str, float] = {}
        for p in self.ops:
            for e in self.ops[p]:
                d = min(e.t1, self.hi) - max(e.t0, self.lo)
                tot[e.name] = tot.get(e.name, 0.0) + d
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, d * 1e-9 / len(self.ops)] for name, d in ranked]

    def idle_gaps(self, spans: List[HostSpan], k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of the first device, each named by
        the innermost host span open at its middle."""
        plane = sorted(self.ops)[0]
        g = gaps(self.busy(plane), self.lo, self.hi)
        g.sort(key=lambda ab: ab[0] - ab[1])
        return [[span_at(spans, (a + b) / 2), (b - a) * 1e-9]
                for a, b in g[:k]]
