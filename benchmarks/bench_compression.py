"""Paper Figs. 9-12 + Tables 4/5/6: compression ratios, incompressible
ratios, and compress/decompress times for NUMARCK vs ISABELA vs ZFP vs ZLIB
on the four dataset families (synthetic analogues, DESIGN.md data layer).

Also: the sharded overlapped-streaming wall-clock comparison (paper
Sec. IV-C compute/IO overlap at rank scale) -- run in a subprocess so the
2-device host-platform mesh doesn't leak into the caller's jax config.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np

from benchmarks.common import Row, timeit
from repro.baselines import isabela, zfp_like, zlib_lossless
from repro.core import (NumarckParams, compress_step, decompress_step,
                        mean_error_rate)
from repro.data.temporal import generate_series

E = 1e-3                       # paper: error threshold 0.1%
SCALE = {"sedov": 1, "stir": 2, "asr": 2, "cmip": 2}


def run(datasets=("sedov", "stir", "asr", "cmip"),
        include_sharded: bool = True, include_chain: bool = True) -> list:
    """``include_sharded``/``include_chain`` gate the subprocess rides
    (2-device sharded stream, chain residency) so the smoke variant of
    `make bench-all` stays in-process; smoke rows remain a name-identical
    subset of the full run's rows."""
    rows: list[Row] = []
    for name in datasets:
        series = list(generate_series(name, n_iterations=3, seed=11,
                                      scale=SCALE[name]))
        prev, curr = series[1], series[2]
        nbytes = curr.nbytes

        # --- NUMARCK (top-k, auto-B) — figs 9-12 + tables 4/5/6 ---------
        p = NumarckParams(error_bound=E)
        t_c, step = timeit(compress_step, prev, curr, p, repeat=2)
        t_d, recon = timeit(decompress_step, step, prev, repeat=2)
        me = mean_error_rate(curr, recon)
        rows.append((f"fig9_12_cr_numarck_{name}", t_c * 1e6,
                     f"CR={step.compression_ratio():.2f} ME={me:.2e} "
                     f"B={step.b_bits}"))
        rows.append((f"table4_alpha_{name}", 0.0,
                     f"alpha={step.alpha*100:.2f}%"))
        rows.append((f"table5_compress_time_{name}", t_c * 1e6,
                     f"MBps={nbytes/t_c/1e6:.1f}"))
        rows.append((f"table6_decompress_time_{name}", t_d * 1e6,
                     f"MBps={nbytes/t_d/1e6:.1f}"))

        # --- ISABELA ----------------------------------------------------
        t_ci, blob_i = timeit(isabela.compress, curr, E, 1024, 32,
                              repeat=1)
        t_di, rec_i = timeit(isabela.decompress, blob_i, repeat=1)
        rows.append((f"fig9_12_cr_isabela_{name}", t_ci * 1e6,
                     f"CR={nbytes/blob_i.nbytes:.2f} "
                     f"ME={mean_error_rate(curr, rec_i):.2e}"))
        rows.append((f"table5_compress_time_isabela_{name}", t_ci * 1e6,
                     f"MBps={nbytes/t_ci/1e6:.1f}"))
        rows.append((f"table6_decompress_time_isabela_{name}",
                     t_di * 1e6, f"MBps={nbytes/t_di/1e6:.1f}"))

        # --- ZFP (abs tol = mean * E, the paper's convention) -----------
        tol = float(np.mean(np.abs(curr))) * E
        t_cz, blob_z = timeit(zfp_like.compress, curr, tol, repeat=1)
        t_dz, rec_z = timeit(zfp_like.decompress, blob_z, repeat=1)
        rows.append((f"fig9_12_cr_zfp_{name}", t_cz * 1e6,
                     f"CR={nbytes/blob_z.nbytes:.2f} "
                     f"ME={mean_error_rate(curr, rec_z):.2e}"))
        rows.append((f"table5_compress_time_zfp_{name}", t_cz * 1e6,
                     f"MBps={nbytes/t_cz/1e6:.1f}"))
        rows.append((f"table6_decompress_time_zfp_{name}", t_dz * 1e6,
                     f"MBps={nbytes/t_dz/1e6:.1f}"))

        # --- ZLIB lossless reference -------------------------------------
        t_zl, blob_l = timeit(zlib_lossless.compress, curr, repeat=1)
        rows.append((f"fig9_12_cr_zlib_{name}", t_zl * 1e6,
                     f"CR={nbytes/blob_l.nbytes:.2f} ME=0"))
    # --- robustness: NCK4 checksum-frame overhead (PR 10) ---------------
    # Unconditional so the smoke subset keeps the rows and bench-check
    # gates them against the committed artifact.
    rows.extend(run_checksum_overhead())
    if include_sharded:
        rows.extend(run_sharded_overlap())
    if include_chain:
        # host-chain vs device-chain residency (single-device and sharded,
        # overlap on/off) -- the ReferenceChain refactor, measured.
        from benchmarks import bench_chain
        rows.extend(bench_chain.run())
    return rows


def run_checksum_overhead() -> list:
    """Container write+read with the NCK4 checksum frame on vs off
    (``NCKWriter(checksums=...)``), same compressed payload both ways.
    The delta is the pure crc32 cost of the integrity layer
    (docs/robustness.md): one digest pass over the payload each way,
    clearly visible on raw container reads (no entropy decode here) and
    amortized to noise in decode-dominated workloads."""
    import tempfile

    from repro.core import compress_series
    from repro.core.container import NCKReader, NCKWriter

    rng = np.random.default_rng(23)
    n = 1 << 20                                   # 4 MB/step float32
    a = rng.normal(1.0, 0.5, n).astype(np.float32)
    b = (a * (1 + 0.01 * rng.standard_normal(n))).astype(np.float32)
    steps = compress_series([a, b], NumarckParams(error_bound=E))
    payload = float(sum(s.nbytes for s in steps))

    rows: list[Row] = []
    with tempfile.TemporaryDirectory() as d:
        for label, checksums in (("checksum_on", True),
                                 ("checksum_off", False)):
            path = os.path.join(d, f"{label}.nck")

            def write():
                w = NCKWriter(checksums=checksums)
                for i, s in enumerate(steps):
                    w.add_step(f"step{i:04d}", s)
                w.write(path)

            def read():
                r = NCKReader(path)
                return [r.read_step(nm) for nm in r.step_names()]

            t_w, _ = timeit(write, repeat=3)
            t_r, _ = timeit(read, repeat=3)
            rows.append((f"robustness/{label}", (t_w + t_r) * 1e6,
                         f"write_MBps={payload/t_w/1e6:.0f} "
                         f"read_MBps={payload/t_r/1e6:.0f}"))
    return rows


_OVERLAP_BENCH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import time
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core import NumarckParams
    from repro.distributed.pipeline import ShardedCompressor

    rng = np.random.default_rng(5)
    # Sized so both modes (each warmed + timed) finish on the small
    # tracked machine; the row's point is the overlap speedup ratio.
    n = 500_000                       # 2 MB/step f32
    steps = 4
    base = rng.normal(1.0, 0.5, n).astype(np.float32)
    series = [base]
    for _ in range(steps - 1):
        series.append((series[-1]
                       * (1 + 0.01 * rng.standard_normal(n)))
                      .astype(np.float32))

    params = NumarckParams(error_bound=1e-3)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def run(overlap):
        sc = ShardedCompressor(mesh, "data", params, use_pallas=False,
                               overlap=overlap)
        sc.compress_series(series)    # warm the jit caches + pools
        t0 = time.perf_counter()
        blobs = sc.compress_series(series)
        dt = time.perf_counter() - t0
        sc.close()
        return dt, blobs

    t_sync, b_sync = run(False)
    t_over, b_over = run(True)
    assert all(a.index_blocks == b.index_blocks
               for a, b in zip(b_sync, b_over))
    mb = n * 4 * steps / (1 << 20)
    print(f"RESULT sync_s={t_sync:.4f} overlap_s={t_over:.4f} "
          f"speedup={t_sync / max(t_over, 1e-9):.3f} mb={mb:.0f}")
""")


def run_sharded_overlap() -> list:
    """Sharded overlap=False vs overlap=True on a multi-step series under a
    host-platform 2-device mesh (byte-equality asserted in-process)."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # A host-platform mesh by design: never take a chip the parent holds.
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _OVERLAP_BENCH], env=env,
                         capture_output=True, text=True, timeout=1200)
    rows: list[Row] = []
    for line in res.stdout.splitlines():
        if line.startswith("RESULT "):
            kv = dict(p.split("=") for p in line.split()[1:])
            rows.append(("sharded_stream/sync",
                         float(kv["sync_s"]) * 1e6,
                         f"MBps={float(kv['mb'])/float(kv['sync_s']):.0f}"))
            rows.append(("sharded_stream/overlap",
                         float(kv["overlap_s"]) * 1e6,
                         f"MBps={float(kv['mb'])/float(kv['overlap_s']):.0f}"
                         f" speedup={kv['speedup']}x"))
    if not rows:
        rows.append(("sharded_stream/overlap", 0.0,
                     f"FAILED rc={res.returncode}"))
    return rows
