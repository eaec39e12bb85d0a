"""``relayout_ms``: span ``encode.relayout`` per field-step."""
import pytest

from chipbench import harness
from chipbench.tests.test_span_metrics import _Span


def _ctx(mode, spans):
    units = [harness.Unit(0.0, 1.0, 4, 1, 5, 0, 1),
             harness.Unit(1.0, 2.0, 4, 1, 5, 1, 1),
             harness.Unit(2.0, 3.0, 4, 1, 5, 2, 1)]
    return harness.Ctx(mode, units, spans, [], None, lambda t: t, {}, 1, 4,
                       1)


def test_span_time_per_field_step():
    spans = [_Span("encode.relayout", 0.1, 0.13),
             _Span("encode.relayout", 1.1, 1.12),
             _Span("encode.relayout", 2.1, 2.11),
             _Span("encode.analyze", 0.2, 0.9)]
    got = harness.metric_reader("relayout_ms")(_ctx("write", spans))
    assert got == pytest.approx(1e3 * (0.03 + 0.02 + 0.01) / 3)


def test_a_program_without_the_span_reads_nothing():
    # The host route (and the parent commit) has no relayout span.
    spans = [_Span("encode.analyze", 0.2, 0.9)]
    read = harness.metric_reader("relayout_ms")
    assert read(_ctx("write", spans)) is None
    assert read(_ctx("read", [_Span("encode.relayout", 0, 1)])) is None
