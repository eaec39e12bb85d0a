"""``encode_roofline``: the bytes the sharded encode must move and its
share of the memory roofline."""
import importlib.util
import os

import pytest

from chipbench import harness, peaks, tracing
from chipbench.tracing import Event

N = 168_480_000                 # one CESM-ATM 3-D field, 26 x 1800 x 3600


def _module():
    path = os.path.join(harness.HERE, "metrics", "encode_roofline.py")
    spec = importlib.util.spec_from_file_location("encode_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_prev_and_curr_once_and_the_index_at_b_bits():
    # 674 MB each of prev and curr; 168.48 M indices at B = 5 pack to
    # 105.3 MB.  The int32 index the program also writes is not counted.
    assert _module().encode_bytes(N, 4, 5) == 2 * 673_920_000 + 105_300_000
    assert _module().encode_bytes(3, 4, 5) == 24 + 2        # 15 bits


def _ctx(dur_ns, chips=4, units=3, module="jit__encode_shard(12)"):
    ev = [Event("/host:CPU", "python", tracing.WINDOW, 0, 10**9)]
    for c in range(chips):
        plane = f"/device:TPU:{c}"
        for u in range(units):
            ev.append(Event(plane, tracing.MODULES_LINE, module,
                            1000 + u * 10**8, dur_ns))
            ev.append(Event(plane, tracing.OPS_LINE, "%fusion.1 = f32[] "
                            "fusion(%p)", 1000 + u * 10**8, dur_ns))
        ev.append(Event(plane, tracing.MODULES_LINE, "jit__analyze_shard(3)",
                        5 * 10**8, 10**6))
    trace = tracing.DeviceTrace.from_events(ev)
    us = [harness.Unit(0.0, 1.0, 4 * N, 1, 5, u, 1) for u in range(units)]
    return harness.Ctx("write", us, [], [], trace, lambda t: t,
                       peaks.peaks_for("TPU v5 lite"), N, 4, 1)


def test_share_of_the_roofline_per_chip():
    # Each chip moves a quarter of the bytes of each field-step.
    per_chip = _module().encode_bytes(N, 4, 5) / 4
    least_ns = per_chip / 819e9 * 1e9
    read = harness.metric_reader("encode_roofline")
    assert read(_ctx(int(least_ns * 10))) == pytest.approx(10.0, rel=1e-6)


def test_never_above_100_when_the_program_runs_at_the_roofline():
    per_chip = _module().encode_bytes(N, 4, 5) / 4
    at_peak = int(-(-per_chip * 1e9 // 819e9)) + 1
    got = harness.metric_reader("encode_roofline")(_ctx(at_peak))
    assert 99.0 < got <= 100.0


def test_a_trace_without_the_program_reads_nothing():
    read = harness.metric_reader("encode_roofline")
    assert read(_ctx(1000, module="jit_other(1)")) is None
