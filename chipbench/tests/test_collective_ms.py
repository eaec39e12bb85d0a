"""``collective_ms``: which ops of a device trace are collectives, and
their busy time per field-step."""
import pytest

from chipbench import harness, tracing
from chipbench.tracing import Event

# Op names as the trace gives them: the HLO instruction as text.  The
# first five are instructions of the sharded stages as the TPU v5e
# compiler emits them for a described v5e:2x2 (the gather's and the
# analyze stage's all-reduces, named after the psum or pmin they lower,
# the relayout's all-gather, the edge exchange's collective-permute);
# the rest are the other forms of the same opcodes.
COLLECTIVES = [
    "%psum.7 = s32[1048576]{0:T(1024)S(1)} all-reduce("
    "%bitcast-convert_select_fusion), channel_id=1, "
    "replica_groups={{0,1,2,3}}",
    "%pmin.7 = f32[]{:T(128)} all-reduce(%get-tuple-element.274), "
    "channel_id=1",
    "%all-gather.5 = f32[26,1800,3600]{2,0,1:T(8,128)} all-gather(%copy.1), "
    "channel_id=1, dimensions={1}",
    "%collective-permute-start = (s32[1677696]{0:T(1024)}, "
    "s32[1677696]{0:T(1024)}, u32[]{:S(2)}, u32[]{:S(2)}) "
    "collective-permute-start(%slice.1843), channel_id=1",
    "%collective-permute-done = s32[1677696]{0:T(1024)} "
    "collective-permute-done(%collective-permute-start)",
    "%all-reduce.3 = f32[] all-reduce(f32[] %x), to_apply=%region_0.1",
    "%collective-permute-start.1 = (s32[5120]{0}, s32[5120]{0}) "
    "collective-permute-start(s32[5120]{0} %slice.2)",
    "%all-to-all.2 = f32[4,1024]{1,0} all-to-all(%p)",
    "%all-reduce-start = f32[4]{0:T(256)} all-reduce-start(%y)",
    "all-reduce.1",
]
NOT_COLLECTIVES = [
    "%fusion.7 = f32[] fusion(%all-reduce.3), kind=kLoop",
    "%fusion.7 = f32[] fusion(f32[] %all-reduce.3), kind=kLoop",
    "%fusion.1 = s32[65536]{0:T(1024)S(1)} fusion(s32[25000000]{0:T(1024)} "
    "%param), kind=kLoop",
    "%copy.4 = f32[42120000]{0} copy(%all-gather)",
    "%while.1 = (u32[], f32[8192]{0}) while(%tuple), "
    "metadata={op_name=\"jit(_gather_shard)/shard_map/psum\"}",
]


def _read():
    return harness.metric_reader("collective_ms")


def _module():
    import importlib.util
    import os
    path = os.path.join(harness.HERE, "metrics", "collective_ms.py")
    spec = importlib.util.spec_from_file_location("collective_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", COLLECTIVES)
def test_a_collective_opcode_is_matched(name):
    assert _module().is_collective(name)


@pytest.mark.parametrize("name", NOT_COLLECTIVES)
def test_an_op_that_only_names_a_collective_is_not(name):
    assert not _module().is_collective(name)


def _ctx(mode="write"):
    """Two chips, a window of [100, 300) ns, two field-steps."""
    ev = [Event("/host:CPU", "python", tracing.WINDOW, 100, 200)]
    ops = {"/device:TPU:0": [(COLLECTIVES[0], 90, 30),     # 20 in window
                             (COLLECTIVES[3], 150, 20),    # [150, 170)
                             (COLLECTIVES[4], 160, 20),    # [160, 180)
                             (NOT_COLLECTIVES[0], 170, 50)],
           "/device:TPU:1": [(COLLECTIVES[2], 290, 40)]}  # 10 in window
    for plane, rows in ops.items():
        for name, t0, d in rows:
            ev.append(Event(plane, tracing.OPS_LINE, name, t0, d))
    trace = tracing.DeviceTrace.from_events(ev)
    units = [harness.Unit(0.0, 1.0, 4, 1, 5, 0, 1),
             harness.Unit(1.0, 2.0, 4, 1, 5, 1, 1)]
    return harness.Ctx(mode, units, [], [], trace, lambda t: t, {}, 1, 4, 1)


def test_busy_union_per_chip_averaged_per_field_step():
    # TPU:0: [100, 120) and the union [150, 180): 50 ns; TPU:1: 10 ns.
    # Mean over the chips 30 ns, over two field-steps 15 ns.
    assert _read()(_ctx()) == pytest.approx(15e-6)


def test_reads_nothing_on_a_read_cell_or_without_a_trace():
    assert _read()(_ctx("read")) is None
    ctx = _ctx()
    ctx.trace = None
    assert _read()(ctx) is None
