"""Readers of the program spans that run on the entropy pool's threads."""
import pytest

from chipbench import harness


class _Span:
    """A program span as ``repro.obs.telemetry`` records it."""

    def __init__(self, name, t0, t1, depth=0, tid=1):
        self.name, self.t0, self.t1 = name, t0, t1
        self.depth, self.tid = depth, tid

    @property
    def duration(self):
        return self.t1 - self.t0


@pytest.mark.parametrize("name,span", [("decode_inflate_ms", "decode.inflate"),
                                       ("decode_unpack_ms", "decode.unpack")])
def test_pool_thread_metrics_sum_every_thread(name, span):
    # Two restored steps; three pool threads overlap in time, so the sum is
    # thread-seconds, more than the 1 s of wall time they cover.
    units = [harness.Unit(0.0, 1.0, 4, 0, 0, 0, 0),
             harness.Unit(1.0, 2.0, 4, 0, 0, 0, 1)]
    spans = [_Span(span, 0.0, 1.0, depth=0, tid=11),
             _Span(span, 0.0, 0.5, depth=0, tid=12),
             _Span(span, 0.25, 1.0, depth=0, tid=13),
             _Span("decode.entropy", 0.0, 1.0, depth=0, tid=1),
             _Span("other", 0.0, 9.0, depth=0, tid=12)]
    ctx = harness.Ctx("read", units, spans, [], None, lambda t: t, {}, 1, 4,
                      1)
    assert harness.metric_reader(name)(ctx) == pytest.approx(
        1e3 * (1.0 + 0.5 + 0.75) / 2)


@pytest.mark.parametrize("name", ["decode_inflate_ms", "decode_unpack_ms",
                                  "decode_upload_ms", "nck_read_ms",
                                  "host_pack_ms", "entropy_code_ms"])
def test_a_span_the_program_lacks_reads_nothing(name):
    # The parent commit has none of these spans: the reader returns None
    # and the result line leaves the metric out.
    units = [harness.Unit(0.0, 1.0, 4, 0, 0, 0, 0)]
    for mode in ("write", "read"):
        ctx = harness.Ctx(mode, units, [_Span("finalize", 0.0, 1.0)], [],
                          None, lambda t: t, {}, 1, 4, 1)
        assert harness.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name,mode,span", [
    ("host_pack_ms", "write", "finalize.pack"),
    ("entropy_code_ms", "write", "entropy.compress"),
    ("decode_inflate_ms", "read", "decode.inflate"),
    ("decode_unpack_ms", "read", "decode.unpack"),
    ("decode_upload_ms", "read", "decode.upload"),
    ("nck_read_ms", "read", "nck.read")])
def test_each_new_metric_reads_its_span_in_its_own_cell(name, mode, span):
    # Two units; the span nests in an older one, as in the program.
    units = [harness.Unit(0.0, 1.0, 4, 0, 0, 0, 0),
             harness.Unit(1.0, 2.0, 4, 0, 0, 0, 1)]
    spans = [_Span("finalize", 0.0, 1.0), _Span(span, 0.1, 0.4, depth=1),
             _Span(span, 1.2, 1.3, depth=1)]
    other = "read" if mode == "write" else "write"
    read = harness.metric_reader(name)
    ctx = harness.Ctx(mode, units, spans, [], None, lambda t: t, {}, 1, 4, 1)
    assert read(ctx) == pytest.approx(1e3 * (0.3 + 0.1) / 2)
    ctx = harness.Ctx(other, units, spans, [], None, lambda t: t, {}, 1, 4, 1)
    assert read(ctx) is None
