# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness -- one bench per paper table/figure:

  bench_scaling      Table 2, Table 3, Figs. 3-8 (phases + scaling model)
  bench_compression  Figs. 9-12, Tables 4/5/6 (CR + times vs baselines)
  bench_partial      Table 7 (partial decompression linearity)
  bench_binning      Table 8, Figs. 13/14 (strategies vs DP oracle)
  bench_autob        Figs. 16/17, Table 9 (auto-B + ZLIB interaction)
  bench_kernels      kernel micro-bench (+ v5e roofline targets)

SS Roofline for the 40 (arch x shape) cells is a separate reader
(benchmarks/roofline.py) because it consumes launch/dryrun.py artifacts.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import sys

# Runnable as `python benchmarks/run.py` from the repo root: put the root
# (for `benchmarks.*`) and src (for `repro.*`) on the path -- but only
# when the packages aren't already importable (installed wheel, or
# PYTHONPATH=src), so an installed `repro` isn't shadowed by the tree.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if (importlib.util.find_spec("repro") is None
        or importlib.util.find_spec("benchmarks") is None):
    for _p in (_ROOT, os.path.join(_ROOT, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)


def _fleet_allowed() -> bool:
    """May this process start the emulated multi-process fleet?  Its
    ranks are CPU processes; on an accelerator host this process already
    holds the chip, so the measured fleet rows are skipped there (the
    model rows still run)."""
    import jax
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    print(f"# scaling/real rows skipped: this process holds the {backend} "
          "device, and the emulated CPU fleet is not started beside it")
    return False


def bench_all(out_dir: str, smoke: bool = False) -> int:
    """Write the committed perf-trajectory artifacts --
    BENCH_entropy.json, BENCH_chain.json, BENCH_compression.json,
    BENCH_scaling.json -- into `out_dir` in the stable schema of
    benchmarks.common.write_bench_json (machine/config header + named
    rows).

    ``smoke`` runs reduced, in-process variants whose rows are
    name-identical subsets of the full run's, so
    benchmarks/check_regression.py can gate a CI smoke run against the
    committed full artifacts.  Returns the number of failed benches.
    """
    from benchmarks import (bench_chain, bench_compression, bench_entropy,
                            bench_scaling)
    from benchmarks.common import emit, write_bench_json

    failed = 0
    real = _fleet_allowed()
    plan = [
        ("entropy", "BENCH_entropy.json",
         lambda: bench_entropy.run(smoke=True,
                                   sizes_mb=(bench_entropy.SMOKE_SIZES_MB
                                             if smoke else
                                             bench_entropy.FULL_SIZES_MB)),
         {"smoke": smoke}),
        ("chain", "BENCH_chain.json",
         lambda: bench_chain.run(smoke=smoke), {"smoke": smoke}),
        ("compression", "BENCH_compression.json",
         lambda: bench_compression.run(
             datasets=("sedov",) if smoke
             else ("sedov", "stir", "asr", "cmip"),
             include_sharded=not smoke, include_chain=False),
         {"smoke": smoke, "note": "chain rows live in BENCH_chain.json"}),
        ("scaling", "BENCH_scaling.json",
         lambda: bench_scaling.run(real=real, smoke=smoke),
         {"smoke": smoke, "real": real,
          "note": "scaling/real/* rows are measured emulated multi-"
                  "process runs; the rest is the paper-scale model"}),
    ]
    for bench, fname, fn, config in plan:
        path = os.path.join(out_dir, fname)
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 -- report, keep going
            print(f"{bench}_FAILED,0,{type(e).__name__}:{e}")
            import traceback
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        emit(rows)
        write_bench_json(path, bench, rows, config=config)
        print(f"# wrote {path} ({len(rows)} rows)")
    return failed


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: scaling,compression,partial,binning,"
                         "autob,kernels,chain,entropy")
    ap.add_argument("--entropy-json", default=None, metavar="PATH",
                    help="run the entropy smoke bench (device rANS vs "
                         "threaded zlib vs raw) and write the rows to "
                         "PATH (the BENCH_entropy.json CI artifact)")
    ap.add_argument("--bench-all", action="store_true",
                    help="write BENCH_entropy/chain/compression/scaling"
                         ".json into --out-dir (the committed perf "
                         "trajectory)")
    ap.add_argument("--smoke", action="store_true",
                    help="with --bench-all: reduced in-process variants "
                         "(rows are a name subset of the full run)")
    ap.add_argument("--out-dir", default=_ROOT,
                    help="destination for the BENCH_*.json artifacts "
                         "(default: repo root)")
    args = ap.parse_args()

    if args.bench_all:
        print("name,us_per_call,derived")
        sys.exit(1 if bench_all(args.out_dir, smoke=args.smoke) else 0)

    from benchmarks import (bench_autob, bench_binning, bench_chain,
                            bench_compression, bench_entropy,
                            bench_kernels, bench_partial, bench_scaling)
    benches = {
        "compression": bench_compression.run,
        "scaling": bench_scaling.run,
        "partial": bench_partial.run,
        "binning": bench_binning.run,
        "autob": bench_autob.run,
        "kernels": bench_kernels.run,
        "chain": bench_chain.run,
        "entropy": bench_entropy.run,
    }
    # "chain" rows already ride along inside bench_compression, and the
    # full "entropy" sweep has its own make target; keep both out of the
    # default sweep so `make bench` stays bounded.
    wanted = (args.only.split(",") if args.only
              else [b for b in benches if b not in ("chain", "entropy")])
    print("name,us_per_call,derived")
    from benchmarks.common import emit
    if args.entropy_json:
        rows = bench_entropy.run(smoke=True)
        emit(rows)
        bench_entropy.write_json(rows, args.entropy_json, smoke=True)
        # The smoke rows just ran; don't re-run entropy via --only, and
        # skip the default sweep entirely when only the json was asked.
        wanted = ([w for w in wanted if w != "entropy"] if args.only
                  else [])
    for name in wanted:
        try:
            emit(benches[name]())
        except Exception as e:  # noqa: BLE001
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            import traceback
            traceback.print_exc(file=sys.stderr)


if __name__ == '__main__':
    main()
