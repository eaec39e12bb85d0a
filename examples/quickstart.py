"""Quickstart: compress a temporal dataset with parallel NUMARCK.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import tempfile

import numpy as np

from repro.core import (NumarckParams, TemporalArchive, compress_series,
                        decompress_series, mean_error_rate)
from repro.data.temporal import generate_series
from repro.launch.runtime_env import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    enable_compile_cache(ROOT)
    # 6 snapshots of a turbulence-like field (FLASH-stir analogue)
    series = list(generate_series("stir", n_iterations=6, seed=0, scale=2))
    print(f"dataset: {len(series)} iterations x {series[0].shape} "
          f"{series[0].dtype} ({series[0].nbytes/1e6:.1f} MB each)")

    params = NumarckParams(error_bound=1e-3)      # E = 0.1%, auto-B, top-k
    steps = compress_series(series, params)

    total_in = sum(a.nbytes for a in series)
    total_out = sum(s.nbytes for s in steps)
    print(f"compression ratio: {total_in/total_out:.2f} "
          f"(deltas only: {np.mean([s.compression_ratio() for s in steps[1:]]):.2f})")
    for i, s in enumerate(steps):
        kind = "anchor" if s.is_anchor else f"B={s.b_bits} alpha={s.alpha:.3f}"
        print(f"  it{i}: {s.nbytes/1e6:6.2f} MB  {kind}")

    recon = decompress_series(steps)
    for i, (orig, rec) in enumerate(zip(series, recon)):
        assert mean_error_rate(orig, rec) <= params.error_bound * 1.01

    # write an archive + partial decompression
    path = os.path.join(tempfile.mkdtemp(), "quickstart.nck")
    TemporalArchive.write(path, "dens", steps)
    ar = TemporalArchive(path)
    window = ar.read_range("dens", 5, 1000, 1200)
    np.testing.assert_array_equal(window,
                                  recon[5].reshape(-1)[1000:1200])
    print("partial decompression of [1000:1200) at iteration 5: exact ✓")
    print(f"mean error rate (it5): "
          f"{mean_error_rate(series[5], recon[5]):.2e} <= E={params.error_bound}")


if __name__ == "__main__":
    main()
