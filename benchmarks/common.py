"""Shared helpers for the per-table benchmarks.

Besides the CSV row helpers, this is the home of the diffable BENCH JSON
schema (``write_bench_json``): every committed BENCH_*.json artifact has
the same shape --

    {"schema": 2, "bench": "...",
     "machine": {cpu_count, platform, python, jax_version, jax_x64,
                 backend, device_kind, device_count},
     "config": {...bench-specific knobs...},
     "rows": [{"name": ..., "us_per_call": ..., "derived": ...}, ...]}

-- so ``benchmarks/check_regression.py`` can compare runs by row name
and docs/observability.md can document one schema for all three files.
"""
from __future__ import annotations

import json
import os
import platform
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

from repro.launch.runtime_env import enable_compile_cache

Row = Tuple[str, float, str]     # (name, us_per_call, derived)

BENCH_SCHEMA_VERSION = 2

# Every bench shares one persistent compilation cache: the directory in
# JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
enable_compile_cache(ROOT)


def timeit(fn: Callable, *args, repeat: int = 3, **kw):
    """(best seconds, result)."""
    best, out = float("inf"), None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return best, out


def emit(rows: List[Row]):
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def rate(mb: float, seconds: float) -> str:
    """MB/s as a derived-field string.  Three significant digits below
    10 MB/s: the old ``:.0f`` truncated slow sharded rows (< 0.5 MB/s on
    the 1-CPU tracked container) to a meaningless ``MBps=0``."""
    v = mb / seconds
    return f"{v:.0f}" if v >= 10 else f"{v:.3g}"


def machine_header() -> Dict:
    """Machine/config fingerprint stamped into every BENCH JSON, so a
    diff between two committed artifacts says whether the runs are even
    comparable before anyone reads a single timing row."""
    hdr: Dict = {
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
    }
    try:
        import jax
        hdr["jax_version"] = jax.__version__
        hdr["jax_x64"] = bool(jax.config.jax_enable_x64)
        devs = jax.devices()
        hdr["backend"] = jax.default_backend()
        hdr["device_kind"] = devs[0].device_kind if devs else None
        hdr["device_count"] = len(devs)
    except Exception as e:  # pragma: no cover - jax ships in this repo
        hdr["jax_version"] = f"unavailable: {type(e).__name__}"
    return hdr


def write_bench_json(path: str, bench: str, rows: List[Row],
                     config: Optional[Dict] = None) -> str:
    """Write one BENCH_*.json artifact in the stable diffable schema."""
    payload = {
        "schema": BENCH_SCHEMA_VERSION,
        "bench": bench,
        "machine": machine_header(),
        "config": config or {},
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path
