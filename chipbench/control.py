#!/usr/bin/env python3
"""Readings of the control: the plain reference at the next precision
below the configuration's (bfloat16 for float32) put in the program's
place, through the same comparison that decides ``correct``.

    python chipbench/control.py --workload <name> --seeds 1,2,3 [--units N]

A write cell's control needs no program: the traffic of ``--units``
field-steps is drawn from each seed as a run draws it, and the reference
at the lower precision compresses the same originals, made whole on one
chip.  A read cell's control decodes the archive that set-up made with
the program.  Each seed prints one JSON line of readings; the
benchmark's own runs never run this.
"""
import argparse
import json
import os
import resource
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell: dict, config: dict, traffic: dict, seed: int, units: int,
             devices, workdir: str, precision: str = "bfloat16") -> dict:
    from chipbench import harness, loadgen
    load = loadgen.make_load(config, traffic, seed, devices, workdir,
                             harness.Spans())
    try:
        if load.mode == "write":
            load.fields = [loadgen._Field(n, t=0, files={})
                           for n in config["fields"]]
            for i in range(units):
                f = i % len(load.fields)
                fd = load.fields[f]
                fd.t += 1
                load.draw_change(f, fd.t)
                if i == load.warm_fields() - 1:     # set-up's warm steps
                    load.new_block()
        else:
            load.setup()
        checks = load.control(precision)
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    return {"workload": cell["name"], "seed": seed, "precision": precision,
            "checks": {c.name: {"value": c.value, "limit": c.limit,
                                "fails": not c.ok} for c in checks}}


def placement(cell: dict, config: dict, traffic: dict):
    """(configuration, chips) of the control: a write control runs no
    program, so it makes its originals whole on one chip, the values that a
    mesh of the cell's chips makes (``chipbench/tests/test_mesh.py``)."""
    if traffic["mode"] != "write":
        return config, cell["chips"]
    return dict(config, field_partition=None), 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--units", type=int, default=16)
    ap.add_argument("--precision", default="bfloat16")
    args = ap.parse_args(argv)
    from chipbench import harness
    bench = harness.load_benchmark(ROOT)
    cell = harness.cell_of(bench, args.workload)
    traffic = harness.load_traffic(cell["traffic"])
    config, chips = placement(cell, harness.load_config(cell["config"]),
                              traffic)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"control.py: needs {chips} TPU chips", file=sys.stderr)
        return 2
    workdir = os.path.join(ROOT, ".chipbench", "control", cell["name"])
    for s in args.seeds.split(","):
        t0 = time.monotonic()
        out = readings(cell, config, traffic, int(s), args.units,
                       devices[:chips], workdir, args.precision)
        out["seconds"] = time.monotonic() - t0
        out["host_peak_rss_bytes"] = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
