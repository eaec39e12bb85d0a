"""Every per-layer metric of ``cesm3d.write.4chip`` reads a number from
what its traced run holds: the sharded driver's spans, and a trace of
four chips whose programs and ops carry the names the TPU v5e compiler
gives the sharded stages (``jit__encode_shard``; ``%pmin.7 = f32[]{:T(128)}
all-reduce(...)``, from their HLO compiled for a described ``v5e:2x2``)."""
import pytest

from chipbench import harness, peaks, tracing
from chipbench.tracing import Event

CELL = "cesm3d.write.4chip"
N = 26 * 1800 * 3600
PROGRAMS = {   # program: (its ops, at its start, each 1 ms)
    "jit__relayout(1)": ["%all-gather.5 = f32[26,1800,3600]{2,0,1:T(8,128)} "
                         "all-gather(%copy.1), channel_id=1",
                         "%multiply_dynamic-slice_fusion = f32[42120000]{0} "
                         "fusion(%partition-id, %all-gather.5)"],
    "jit__analyze_shard(2)": ["%pmin.7 = f32[]{:T(128)} all-reduce("
                              "%get-tuple-element.274), channel_id=1",
                              "%psum.15 = s32[65536]{0:T(1024)S(1)} all-reduce("
                              "%bitcast.20), channel_id=1"],
    "jit__encode_shard(3)": ["%collective-permute-start = (s32[1677696]{0:T(1024)}"
                             ", s32[1677696]{0:T(1024)}, u32[]{:S(2)}, "
                             "u32[]{:S(2)}) collective-permute-start(%slice.1843)",
                             "%fusion.12 = s32[1677696]{0:T(1024)} fusion("
                             "%collective-permute-done)"],
    "jit__gather_shard(4)": ["%psum.7 = s32[1048576]{0:T(1024)S(1)} all-reduce("
                             "%bitcast-convert_select_fusion), channel_id=1"],
    "jit__advance_shard(5)": ["%fusion.3 = f32[42120000]{0} fusion(%p.1)"],
}
SPANS = ["encode.relayout", "encode.analyze", "encode.index",
         "encode.exceptions", "encode.pack_fetch", "encode.idx_fetch",
         "finalize", "finalize.exceptions", "finalize.entropy",
         "entropy.compress", "nck.write"]


class _Span:
    def __init__(self, name, t0, t1, depth=0, tid=1):
        self.name, self.t0, self.t1 = name, t0, t1
        self.depth, self.tid = depth, tid

    @property
    def duration(self):
        return self.t1 - self.t0


def _ctx():
    ms = 1_000_000
    ev = [Event("/host:CPU", "python", tracing.WINDOW, 0, 100 * ms)]
    for c in range(4):
        plane = f"/device:TPU:{c}"
        for i, (prog, ops) in enumerate(PROGRAMS.items()):
            t0 = (10 + 10 * i) * ms
            ev.append(Event(plane, tracing.MODULES_LINE, prog, t0,
                            len(ops) * ms))
            for j, op in enumerate(ops):
                ev.append(Event(plane, tracing.OPS_LINE, op, t0 + j * ms, ms,
                                prog.split("(")[0]))
    trace = tracing.DeviceTrace.from_events(ev)
    spans = [_Span(name, 0.01 + 0.001 * i, 0.0115 + 0.001 * i)
             for i, name in enumerate(SPANS)]
    units = [harness.Unit(0.0, 0.09, 4 * N, N // 12, 5, 0, 1, 821000)]
    bench = [harness.BenchSpan("bench.add", 0.005, 0.08, 1)]
    return harness.Ctx("write", units, spans, bench, trace,
                       lambda t: t * 1e9, peaks.peaks_for("TPU v5 lite"), N,
                       4, 1)


@pytest.mark.parametrize("metric", [
    m["name"] for m in harness.metrics_for(harness.load_benchmark(), CELL,
                                           "per_layer")])
def test_every_metric_of_the_cell_reads_a_number(metric):
    unit = {m["name"]: m["unit"]
            for m in harness.load_benchmark()["per_layer"]}[metric]
    v = harness.metric_reader(metric)(_ctx())
    assert v is not None and v > 0, metric
    if unit == "%":
        assert v <= 100.0, (metric, v)


def test_collectives_are_the_relayout_analyze_edge_and_gather_ops():
    # Per chip: the all-gather, pmin, psum, collective-permute-start and
    # the gather's psum, 1 ms each; not the fusions that take their
    # results.
    assert harness.metric_reader("collective_ms")(_ctx()) == pytest.approx(
        5.0)
