"""Host-resident vs device-resident temporal reference chain.

Measures what the ReferenceChain refactor buys (ISSUE 4): the
REF_RECONSTRUCTED chain advance of step i is on the critical path of step
i+1's encode, so keeping it on the accelerator (fused dequantize +
exception patch) instead of round-tripping through host
`reconstruct_from_indices` shortens the per-step serial section.

Rows (byte-equality of the two residencies is asserted in-process):

  chain/single/{host,device}                  TemporalCompressor, 8 steps
  chain/sharded/{host,device}_{sync,overlap}  ShardedCompressor, 2-device
                                              host mesh (subprocess)
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

import numpy as np

if __package__ in (None, ""):                      # standalone invocation
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "src"))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import Row, rate  # noqa: E402

STEPS = 8
N = 1_500_000                    # 6 MB/step f32


def _series(n=N, steps=STEPS, seed=3):
    rng = np.random.default_rng(seed)
    base = rng.normal(1.0, 0.5, n).astype(np.float32)
    out = [base]
    for t in range(steps - 1):
        nxt = (out[-1] * (1 + 0.01 * rng.standard_normal(n))
               ).astype(np.float32)
        nxt[t::4001] *= 40.0      # keep the exception patch exercised
        out.append(nxt)
    return out


def run_single() -> list:
    from repro.core import NumarckParams, compress_series

    params = NumarckParams(error_bound=1e-3)
    series = _series()
    mb = N * 4 * STEPS / (1 << 20)
    rows: list[Row] = []
    blobs = {}
    times = {}
    for chain in ("host", "device"):
        compress_series(series, params, chain=chain)   # warm jit caches
        t0 = time.perf_counter()
        blobs[chain] = compress_series(series, params, chain=chain)
        times[chain] = time.perf_counter() - t0
    for a, b in zip(blobs["host"], blobs["device"]):
        assert a.index_blocks == b.index_blocks, "residency changed bytes!"
    for chain in ("host", "device"):
        dt = times[chain]
        extra = f" speedup={times['host'] / dt:.3f}x" if chain == "device" \
            else ""
        rows.append((f"chain/single/{chain}", dt * 1e6,
                     f"MBps={rate(mb, dt)}{extra}"))
    return rows


_SHARDED_BENCH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import time
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core import NumarckParams
    from repro.distributed.pipeline import ShardedCompressor

    rng = np.random.default_rng(5)
    # Sized so the 4-config sweep (2 residencies x 2 overlap modes, each
    # warmed + timed) finishes on the small tracked machine; the point of
    # the rows is the relative speedups, not the absolute payload.
    n = 250_000
    steps = 3
    base = rng.normal(1.0, 0.5, n).astype(np.float32)
    series = [base]
    for t in range(steps - 1):
        nxt = (series[-1] * (1 + 0.01 * rng.standard_normal(n))
               ).astype(np.float32)
        nxt[t::4001] *= 40.0
        series.append(nxt)

    params = NumarckParams(error_bound=1e-3)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def run(chain, overlap):
        sc = ShardedCompressor(mesh, "data", params, use_pallas=False,
                               overlap=overlap, chain=chain)
        sc.compress_series(series)    # warm the jit caches + pools
        t0 = time.perf_counter()
        blobs = sc.compress_series(series)
        dt = time.perf_counter() - t0
        sc.close()
        return dt, blobs

    ref = None
    mb = n * 4 * steps / (1 << 20)
    for chain in ("host", "device"):
        for overlap in (False, True):
            dt, blobs = run(chain, overlap)
            if ref is None:
                ref = blobs
            assert all(a.index_blocks == b.index_blocks
                       for a, b in zip(ref, blobs)), (chain, overlap)
            mode = "overlap" if overlap else "sync"
            print(f"RESULT name={chain}_{mode} s={dt:.4f} mb={mb:.2f}")
""")


def run_sharded() -> list:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # A host-platform mesh by design: never take a chip the parent holds.
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _SHARDED_BENCH], env=env,
                         capture_output=True, text=True, timeout=1800)
    rows: list[Row] = []
    base_s = None
    for line in res.stdout.splitlines():
        if not line.startswith("RESULT "):
            continue
        kv = dict(p.split("=") for p in line.split()[1:])
        s = float(kv["s"])
        if base_s is None:
            base_s = s                      # host_sync baseline
        rows.append((f"chain/sharded/{kv['name']}", s * 1e6,
                     f"MBps={rate(float(kv['mb']), s)} "
                     f"speedup={base_s / s:.3f}x"))
    if not rows:
        rows.append(("chain/sharded", 0.0, f"FAILED rc={res.returncode}"))
    return rows


def run(smoke: bool = False) -> list:
    """``smoke`` keeps only the in-process single-device rows (the
    sharded rows need a 2-device subprocess and dominate the wall-clock);
    smoke rows are a name-identical subset of the full run's."""
    return run_single() if smoke else run_single() + run_sharded()


if __name__ == "__main__":
    from benchmarks.common import emit
    print("name,us_per_call,derived")
    emit(run())
