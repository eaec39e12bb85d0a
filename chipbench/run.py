#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
Set-up makes the data on the device from ``--seed`` and warms every
program the window runs; the window then drives the program for
``--seconds`` seconds; afterwards the program's state is freed and what
the window produced is checked against the plain reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
window under the program's telemetry and the JAX profiler and reports its
per-layer metrics.  The last line of standard output is one JSON object;
the numbers compared, each beside its limit, are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell needs,
the run prints no result and exits with code 2.
"""
import time

START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

STATE = os.path.join(ROOT, ".chipbench")      # work files and the last trace


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def end_to_end(mode: str, units, t_start: float, setup_s: float) -> dict:
    """Rates over all the work and all the time of the window: it ends at
    the completion of its last unit."""
    window_s = units[-1].t1 - t_start
    total = sum(u.nbytes for u in units)
    out = {"setup_s": setup_s}
    if mode == "write":
        out["compress_GBps"] = total / window_s / 1e9
        out["compression_ratio"] = total / sum(u.stored for u in units)
    else:
        out["restore_GBps"] = total / window_s / 1e9
    return out


class CompileWatch:
    """Counts JAX's compile events while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event.endswith("backend_compile_duration"):
            self.events[kw.get("fun_name", "?")] += 1


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, devices, start: float = START,
             config: dict = None, traffic: dict = None):
    """Set-up, window and check of one cell; returns the result line.
    ``config`` and ``traffic`` default to the files the cell names."""
    import jax
    from chipbench import harness, loadgen, peaks, tracing

    config = config or harness.load_config(cell["config"])
    traffic = traffic or harness.load_traffic(cell["traffic"])
    spans = harness.Spans(annotate=trace)
    workdir = os.path.join(STATE, "work", cell["name"])
    load = loadgen.make_load(config, traffic, seed, devices, workdir, spans)
    log(f"data made {time.monotonic() - start:.3f} s after start")
    try:
        load.setup()
        watch = CompileWatch()
        setup_s = time.monotonic() - start
        log(f"set-up {setup_s:.3f} s")
        registry = trace_dir = None
        if trace:
            from repro.obs import telemetry
            from repro.obs import trace as _annotations  # noqa: F401
            trace_dir = os.path.join(STATE, "trace", cell["name"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            registry = telemetry.start()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        watch.active = True
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                t_start = time.perf_counter()
                units = load.window(seconds)
        finally:
            watch.active = False
            if trace:
                jax.profiler.stop_trace()
                telemetry.stop()
        window_s = units[-1].t1 - t_start
        log(f"window {window_s:.3f} s, {len(units)} units; per unit (s): "
            + json.dumps([round(u.t1 - u.t0, 4) for u in units]))
        if load.mode == "write":
            log("B, exceptions and stored bytes per unit: " + json.dumps(
                [[u.b_bits, u.exceptions, u.stored] for u in units]))
        log(f"compiles in the window: {dict(watch.events) or 0}")
        log(f"host peak RSS bytes before the check: {peak_rss()}")
        device = harness.device_info(devices)
        device["memory_peak_bytes"] = harness.memory_peak(devices)
        log(f"peak device bytes: {device['memory_peak_bytes']}")

        breakdown = None
        if trace:
            events = tracing.load_xplane(trace_dir)
            dtrace = tracing.DeviceTrace.from_events(events)
            lo = dtrace.lo

            def clock(t):
                return lo + (t - t_start) * 1e9
            snap = registry.snapshot()["spans"]
            ctx = harness.Ctx(load.mode, units, snap, spans.records, dtrace,
                              clock, peaks.peaks_for(device["kind"]), load.n,
                              load.itemsize, threading.get_ident())
            metrics = {}
            for m in harness.metrics_for(bench, cell["name"], "per_layer"):
                v = harness.metric_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = dtrace.busy_s()
            device["window_s"] = dtrace.window_s
            breakdown = {"device_ops": dtrace.top_ops(10),
                         "idle_gaps": dtrace.idle_gaps(ctx.host_spans(), 10)}
        else:
            vals = end_to_end(load.mode, units, t_start, setup_s)
            metrics = {m["name"]: {"value": vals[m["name"]],
                                   "unit": m["unit"]}
                       for m in harness.metrics_for(bench, cell["name"],
                                                    "end_to_end")}
        load.free()
        checks = load.check()
        log(f"host peak RSS bytes: {peak_rss()}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    correct = all(c.ok for c in checks)
    failed = max((c.failed for c in checks), default=0)
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r}",
              file=sys.stderr, flush=True)
    return harness.result_line(correct, len(units), failed, metrics, device,
                               checks, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    bench = harness.load_benchmark(ROOT)
    cell = harness.cell_of(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU found (JAX platform {devices[0].platform!r});"
              " refusing to run on the host", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: {cell['name']} needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.runtime_env import enable_compile_cache
    cache = enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device: {devices[0].device_kind} x {len(devices)}; cell "
        f"{cell['name']}; seed {args.seed}; compile cache {cache}")
    line = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                    devices[:cell["chips"]])
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
