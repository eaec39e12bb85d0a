#!/usr/bin/env python3
"""Chip smoke test: the NUMARCK compress/restore path once on a TPU.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the sharded mesh phase only

Data: one f32 field at SDRBench Hurricane ISABEL's per-field shape
(100 x 500 x 500, 100 MB per step), generated on the host from ``--seed``
by ``repro.data.temporal`` (spec "isabel"): one anchor and ``STEPS - 1``
deltas.  Generation stays out of every timing.

  A  ``compress_series`` (default ``chain="auto"``: the device-resident
     chain and its Pallas dequantize), written with ``NCKWriter`` and read
     back with ``TemporalArchive``.  Every step meets the error bound, and
     every restored step is bit-identical to the state of a
     ``chain="host"`` compressor fed the same series.
  B  ``codec="rans"``: the device entropy blobs are byte-identical to the
     host coder's (``device_entropy=False``), and ``decompress_step_device``
     restores a delta step bit-identically to the host ``decompress_step``.
  C  ``ShardedCompressor`` over a mesh of one chip with its Pallas kernels:
     blobs and centers byte-identical to phase A; the ``hist`` kernel's
     counts equal ``ref.histogram_ref``.

In every phase the ``kernels.ops`` wrappers are watched while the entry
point runs: each wrapper it called must have been called with
``use_pallas=True``, and each such wrapper must lower to a
``tpu_custom_call`` on the chip (not interpret mode, not the jnp
reference).

With ``--chips 4`` only the mesh phase runs: ``ShardedCompressor`` over
four chips (histogram psum, pmin/pmax, edge ppermute), byte-identical to
single-chip ``compress_series`` on device 0 of the same process.

Timings printed here are smoke timings of one run, not benchmark numbers.
The script exits nonzero, without a result line, when JAX finds no TPU or
when any check fails; its last line on success is one JSON object.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
VAR = "isabel"
STEPS = 4                       # one anchor and three deltas
ERROR_BOUND = 1e-3
# Pallas wrappers of the encode/decode path (kernels.ops) whose lowering
# must contain the Mosaic custom call on the chip.
KERNELS = ("change_ratio_bins", "histogram", "pack_bits", "dequantize",
           "chain_advance")
NATIVE_MARK = "tpu_custom_call"


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *args, **kw):
    """(seconds, result), blocking on any device arrays in the result."""
    import jax
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def make_series(seed: int):
    from repro.data.temporal import generate_series
    t0 = time.perf_counter()
    series = list(generate_series(VAR, n_iterations=STEPS, seed=seed))
    log(f"data: {len(series)} steps of {series[0].shape} "
        f"{series[0].dtype} ({series[0].nbytes / 1e6:.1f} MB each), "
        f"generated on the host in {time.perf_counter() - t0:.1f} s "
        "(set-up, outside every timing)")
    return series


def assert_same_steps(got, want, label: str) -> None:
    """Byte identity of everything the container persists for a step."""
    import numpy as np
    assert len(got) == len(want), (label, len(got), len(want))
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.b_bits == w.b_bits, (label, i, g.b_bits, w.b_bits)
        assert g.block_elems == w.block_elems, (label, i)
        assert g.index_blocks == w.index_blocks, (
            f"{label}: step {i} index blocks differ")
        assert g.centers.tobytes() == w.centers.tobytes(), (
            f"{label}: step {i} centers differ")
        for a, b in ((g.incomp_values, w.incomp_values),
                     (g.incomp_block_offsets, w.incomp_block_offsets)):
            assert (a is None) == (b is None), (label, i)
            if a is not None:
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), (
                    f"{label}: step {i} exception table differs")


def assert_native(lowered_text: str, what: str) -> None:
    assert NATIVE_MARK in lowered_text, (
        f"{what} did not lower to a Mosaic kernel ({NATIVE_MARK} missing): "
        "interpret mode or the jnp reference ran instead")


@contextlib.contextmanager
def kernel_calls():
    """Record ``(wrapper, use_pallas)`` for every ``kernels.ops`` wrapper
    in ``KERNELS`` that the code under the block calls or traces."""
    from repro.kernels import ops as kops
    seen = []
    saved = {name: getattr(kops, name) for name in KERNELS}

    def spy(name, fn):
        def call(*args, **kw):
            seen.append((name, kw.get("use_pallas", True)))
            return fn(*args, **kw)
        return call

    for name, fn in saved.items():
        setattr(kops, name, spy(name, fn))
    try:
        yield seen
    finally:
        for name, fn in saved.items():
            setattr(kops, name, fn)


def check_kernels_native(seen, expected, n: int, b_bits: int,
                         max_bins: int) -> None:
    """The entry point called the `expected` wrappers (recorded in `seen`
    by ``kernel_calls``) and only with ``use_pallas=True``; each wrapper it
    called, lowered that way at this phase's shapes, is a Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    called = {name for name, _ in seen}
    missing = set(expected) - called
    assert not missing, f"entry point never called {sorted(missing)}"
    jnp_calls = sorted({name for name, pallas in seen if not pallas})
    assert not jnp_calls, (
        f"entry point called {jnp_calls} with use_pallas=False")
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    k = (1 << b_bits) - 1
    cen = jax.ShapeDtypeStruct((k,), jnp.float32)
    n32 = -(-n // 32) * 32
    cases = {
        "change_ratio_bins": (lambda p, c: kops.change_ratio_bins(
            p, c, 0.0, 2 * ERROR_BOUND, max_bins=max_bins), (f32, f32)),
        "histogram": (lambda i: kops.histogram(i, max_bins=max_bins),
                      (i32,)),
        "pack_bits": (lambda i: kops.pack_bits(i, b_bits=b_bits),
                      (jax.ShapeDtypeStruct((n32,), jnp.int32),)),
        "dequantize": (lambda i, p, c: kops.dequantize(
            i, p, c, b_bits=b_bits), (i32, f32, cen)),
        "chain_advance": (lambda i, p, q, c: kops.chain_advance(
            i, p, q, c, b_bits=b_bits), (i32, f32, f32, cen)),
    }
    ran = [name for name in KERNELS if name in called]
    for name in ran:
        fn, args = cases[name]
        assert_native(jax.jit(fn).lower(*args).as_text(), name)
    log(f"  kernels the entry point ran, all with use_pallas=True, lower "
        f"to {NATIVE_MARK}: {', '.join(ran)}")


def check_error_bound(series, recon) -> None:
    """Mean error (Eq. 3) within E, and every element within its change-
    ratio bound: |D_i - R_i| <= E |R_{i-1}| (exceptions are exact)."""
    import numpy as np
    from repro.core import mean_error_rate
    np.testing.assert_array_equal(recon[0], series[0])
    worst = 0.0
    for i in range(1, len(series)):
        me = mean_error_rate(series[i], recon[i])
        assert me <= ERROR_BOUND * 1.01, (i, me)
        d = np.asarray(series[i], np.float64)
        r = np.asarray(recon[i], np.float64)
        ref = np.abs(np.asarray(recon[i - 1], np.float64))
        bound = ERROR_BOUND * 1.01 * ref
        over = np.abs(d - r) > bound
        assert not over.any(), (
            f"step {i}: {int(over.sum())} elements outside the bound")
        worst = max(worst, me)
    log(f"  error bound holds: worst mean error {worst:.3e} <= "
        f"E = {ERROR_BOUND} on every step, per element within E|R_(i-1)|")


def phase_a(series, workdir: str):
    """Single-chip main path; returns (steps, restored arrays)."""
    import numpy as np
    from repro.core import (CHAIN_HOST, NumarckParams, TemporalArchive,
                            TemporalCompressor, compress_series,
                            decompress_series)
    log("phase A: compress_series -> NCKWriter -> TemporalArchive")
    params = NumarckParams(error_bound=ERROR_BOUND)
    n = len(series)
    with kernel_calls() as seen:
        setup_s, steps = timed(compress_series, series, params)
    warm_s, again = timed(compress_series, series, params)
    assert_same_steps(again, steps, "repeat compress")
    b_bits = sorted({s.b_bits for s in steps[1:]})
    log(f"  compressed {n} steps, B = {b_bits}, compression ratio "
        f"{sum(a.nbytes for a in series) / sum(s.nbytes for s in steps):.2f}")
    # chain="auto" must have advanced the device chain (chain_advance)
    # through the Pallas dequantize.
    check_kernels_native(seen, ("chain_advance", "dequantize"),
                         series[0].size, max(b_bits), params.max_bins)

    path = os.path.join(workdir, f"{VAR}.nck")
    write_s, _ = timed(TemporalArchive.write, path, VAR, steps)
    arch = TemporalArchive(path)
    back = [arch.reader.read_step(TemporalArchive.step_name(VAR, i))
            for i in range(arch.n_iterations(VAR))]
    assert_same_steps(back, steps, "archive read-back")
    restore_cold_s, recon = timed(decompress_series, back)
    restore_s, recon2 = timed(decompress_series, back)
    for a, b in zip(recon, recon2):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(arch.read_full(VAR, n - 1), recon[-1])
    check_error_bound(series, recon)

    host = TemporalCompressor(params, chain=CHAIN_HOST)
    try:
        for i, arr in enumerate(series):
            host_step = host.add(arr)
            assert_same_steps([host_step], [steps[i]], f"host chain {i}")
            np.testing.assert_array_equal(host.reference_state(), recon[i])
    finally:
        host.close()
    log("  restored steps bit-identical to the chain='host' reference "
        "states; blobs byte-identical")
    log(f"  smoke timing (not a benchmark): set-up (compile + first run) "
        f"{setup_s:.2f} s; compress {warm_s / n:.3f} s/step; archive "
        f"write {write_s:.2f} s; restore {restore_s / n:.3f} s/step "
        f"(first restore {restore_cold_s:.2f} s)")
    log("phase A passed")
    return steps, recon


def phase_b(series, steps_a, recon):
    """Device entropy (rANS) encode and the device decode route."""
    import numpy as np
    from repro.core import NumarckParams, compress_series, decompress_step
    from repro.core import compress as comp
    from repro.kernels import rans
    log("phase B: codec='rans' device entropy and device decode")
    params = NumarckParams(error_bound=ERROR_BOUND, codec="rans")
    n = series[0].size
    with kernel_calls() as seen:
        setup_s, dev_steps = timed(compress_series, series, params)
    warm_s, _ = timed(compress_series, series, params)
    assert all(comp.device_entropy_route(params, n, s.b_bits)
               for s in dev_steps[1:]), "device entropy route not taken"
    host_steps = compress_series(
        series, dataclasses.replace(params, device_entropy=False))
    assert_same_steps(dev_steps, host_steps, "rans device vs host coder")
    log(f"  rANS blobs byte-identical to the host coder over "
        f"{len(series) - 1} delta steps")

    t = len(series) - 1
    step = dev_steps[t]
    assert comp.device_decode_route(step)
    with kernel_calls() as seen_dec:
        comp.decompress_step_device(step, recon[t - 1])    # warm-up
    check_kernels_native(seen, ("chain_advance",), n, step.b_bits,
                         params.max_bins)
    check_kernels_native(seen_dec, ("dequantize",), n, step.b_bits,
                         params.max_bins)
    dec_s, dev_out = timed(comp.decompress_step_device, step, recon[t - 1])
    saved = rans.DEVICE_MIN_BYTES
    rans.DEVICE_MIN_BYTES = float("inf")     # force the host decode route
    try:
        assert not comp.device_decode_route(step)
        host_out = decompress_step(step, recon[t - 1])
    finally:
        rans.DEVICE_MIN_BYTES = saved
    np.testing.assert_array_equal(np.asarray(dev_out), host_out)
    np.testing.assert_array_equal(host_out, recon[t])
    log(f"  decompress_step_device(step {t}) bit-identical to the host "
        "decompress_step and to phase A")
    log(f"  smoke timing (not a benchmark): set-up {setup_s:.2f} s; "
        f"compress {warm_s / len(series):.3f} s/step; device restore "
        f"{dec_s:.3f} s/step")
    log("phase B passed")


def phase_c(series, steps_a, recon, devices):
    """ShardedCompressor (Pallas kernels) on a mesh of `devices`."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import NumarckParams
    from repro.distributed.pipeline import ShardedCompressor
    from repro.kernels import ops as kops
    from repro.kernels import ref
    log(f"phase C: ShardedCompressor over a mesh of {len(devices)} "
        f"chip(s), Pallas kernels")
    params = NumarckParams(error_bound=ERROR_BOUND)
    sc = ShardedCompressor(Mesh(np.asarray(devices), ("data",)), "data",
                           params)
    try:
        with kernel_calls() as seen:
            setup_s, steps_c = timed(sc.compress_series, series)
        warm_s, _ = timed(sc.compress_series, series)
    finally:
        sc.close()
    assert_same_steps(steps_c, steps_a, "sharded vs compress_series")
    log(f"  sharded blobs, centers and exceptions byte-identical to "
        f"single-chip compress_series over {len(series)} steps")

    t = 1
    prev = jnp.asarray(recon[t - 1].reshape(-1))
    curr = jnp.asarray(series[t].reshape(-1))
    _, ids = kops.change_ratio_bins(prev, curr, steps_a[t].domain_lo,
                                    steps_a[t].bin_width,
                                    max_bins=params.max_bins)
    got = np.asarray(kops.histogram(ids, max_bins=params.max_bins))
    want = np.asarray(ref.histogram_ref(ids, max_bins=params.max_bins))
    np.testing.assert_array_equal(got, want)
    log(f"  hist kernel: {params.max_bins} bin counts equal histogram_ref "
        f"({int(got.sum())} binned elements, {int((got > 0).sum())} "
        "bins occupied)")
    # The mesh stages: analyze (change_ratio_bins, histogram), encode
    # (pack_bits) and the chain advance on the mesh (dequantize).
    check_kernels_native(seen, ("change_ratio_bins", "histogram",
                                "pack_bits", "dequantize"),
                         series[0].size, max(s.b_bits for s in steps_a[1:]),
                         params.max_bins)
    log(f"  smoke timing (not a benchmark): set-up {setup_s:.2f} s; "
        f"compress {warm_s / len(series):.3f} s/step")
    log("phase C passed")


def phase_mesh(series, devices):
    """Four-chip phase: ShardedCompressor over `devices` vs single-chip
    compress_series on device 0 of this process."""
    from repro.core import NumarckParams, compress_series, decompress_series
    log(f"mesh phase: single-chip compress_series on {devices[0]} as the "
        "reference")
    ref_steps = compress_series(series, NumarckParams(
        error_bound=ERROR_BOUND))
    phase_c(series, ref_steps, decompress_series(ref_steps), devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-C on one chip; 4: only the sharded "
                         "mesh phase over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); refusing to run on the host",
              file=sys.stderr)
        return 2
    kind, count = devices[0].device_kind, len(devices)
    log(f"device: {kind} x {count}, jax {jax.__version__}")
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {count}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.runtime_env import enable_compile_cache
    log(f"compile cache: {enable_compile_cache(ROOT)}")

    series = make_series(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(series, devices[:4])
    else:
        workdir = os.path.join(ROOT, ".smoke")
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        try:
            steps_a, recon = phase_a(series, workdir)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        phase_b(series, steps_a, recon)
        phase_c(series, steps_a, recon, devices[:1])
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
