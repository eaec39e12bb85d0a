"""The reduction from a profiler trace to device metrics."""
import pytest

from chipbench import tracing
from chipbench.tracing import Event, HostSpan

def _trace():
    """Two devices; a 100 ns window; ops partly outside it."""
    ev = [Event("/host:CPU", "python", tracing.WINDOW, 100, 100)]
    for plane, ops in (("/device:TPU:0", [(90, 20), (120, 10), (125, 10),
                                          (180, 40)]),
                       ("/device:TPU:1", [(150, 10)])):
        for i, (t0, d) in enumerate(ops):
            ev.append(Event(plane, tracing.OPS_LINE, f"op{i}", t0, d))
    ev.append(Event("/device:TPU:0", "XLA Modules", "jit_f", 0, 400))
    return tracing.DeviceTrace.from_events(ev)


def test_union_merges_and_clips():
    assert tracing.union([(0, 5), (3, 8), (10, 12)], 1, 11) == [(1, 8),
                                                              (10, 11)]
    assert tracing.union([(0, 1)], 2, 3) == []


def test_gaps_are_the_window_less_the_busy_union():
    assert tracing.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6),
                                                    (7, 10)]


def test_busy_and_idle_share_of_the_window():
    t = _trace()
    assert t.window_s == pytest.approx(100e-9)
    # TPU:0 busy [100,110) [120,135) [180,200) = 45 ns; TPU:1 10 ns.
    assert t.busy_s() == pytest.approx((45 + 10) / 2 * 1e-9)
    assert t.idle_pct() == pytest.approx(72.5)


def test_op_time_found_by_name():
    t = _trace()
    assert t.op_seconds(lambda e: e.name == "op1") == pytest.approx(
        10e-9 / 2)
    # Program time from the program line: jit_f spans the whole window.
    assert t.program_seconds("jit_f") == pytest.approx(100e-9 / 2)
    assert t.program_seconds("other") == 0.0
    assert t.top_ops(1)[0][0] in ("op3", "op0")


def test_idle_gaps_named_by_the_innermost_open_span():
    t = _trace()
    spans = [HostSpan("bench.add", 100, 200, -1),
             HostSpan("finalize", 135, 180, 0),
             HostSpan("finalize.entropy", 140, 170, 1)]
    g = t.idle_gaps(spans, 3)
    assert g[0] == ["finalize.entropy", pytest.approx(45e-9)]
    assert [name for name, _ in g] == ["finalize.entropy", "bench.add"]
    assert tracing.span_at(spans, 99) == "(no span)"


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError, match="no 'chipbench.window'"):
        tracing.window([Event("/device:TPU:0", tracing.OPS_LINE, "x", 0, 1)])


class _Span:
    """A program span as ``repro.obs.telemetry`` records it."""

    def __init__(self, name, t0, t1, depth=0, tid=1):
        self.name, self.t0, self.t1 = name, t0, t1
        self.depth, self.tid = depth, tid

    @property
    def duration(self):
        return self.t1 - self.t0


@pytest.mark.parametrize("mode,cell", [("write", "isabel.write"),
                                       ("read", "isabel.read")])
def test_every_metric_of_a_cell_reads_a_number(mode, cell):
    from chipbench import harness, peaks
    n = 1000
    ev = [Event("/host:CPU", "python", tracing.WINDOW, 0, 10_000)]
    for i, mod in enumerate(("jit__analyze(7)", "jit_chain_advance(9)")):
        ev.append(Event("/device:TPU:0", tracing.MODULES_LINE, mod,
                        1000 + 2000 * i, 500))
        ev.append(Event("/device:TPU:0", tracing.OPS_LINE, "fusion.1",
                        1000 + 2000 * i, 500, mod.split("(")[0]))
    trace = tracing.DeviceTrace.from_events(ev)
    spans = [_Span(name, 1e-6, 2e-6) for name in (
        "encode.analyze", "encode.index", "finalize", "nck.write",
        "decode.entropy", "decode.dequant")]
    units = [harness.Unit(0.0, 5e-6, 4 * n, 400, 5, 0, 1)]
    bench = [harness.BenchSpan("bench.add", 0.0, 4e-6, 1)]
    ctx = harness.Ctx(mode, units, spans, bench, trace, lambda t: t * 1e9,
                      peaks.peaks_for("TPU v5 lite"), n, 4, 1)
    b = harness.load_benchmark()
    for m in harness.metrics_for(b, cell, "per_layer"):
        v = harness.metric_reader(m["name"])(ctx)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100.0, (m["name"], v)


def test_driver_self_time_leaves_out_the_set_up_step():
    from chipbench import harness
    units = [harness.Unit(10.0, 14.0, 4, 1, 5, 0, 2)]
    bench = [harness.BenchSpan("bench.add", 1.0, 5.0, 1),     # set-up
             harness.BenchSpan("bench.add", 10.0, 13.0, 1)]
    spans = [_Span("encode.analyze", 10.5, 11.0),
             _Span("finalize.task", 11.0, 12.5),
             _Span("finalize", 11.0, 12.5, depth=1)]
    ctx = harness.Ctx("write", units, spans, bench, None, lambda t: t, {},
                      1, 4, 1)
    assert ctx.self_ms("bench.add") == pytest.approx(1000.0)
