"""Telemetry layer (src/repro/obs): span semantics, disabled-path cost,
Chrome-trace export, per-step rollup stability and the overlap/queue
metrics -- plus the invariant the whole subsystem hangs on: telemetry
NEVER changes pipeline outputs (blobs byte-identical on vs off)."""
import json
import os
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import (NumarckParams, compress_series,
                        decompress_series)
from repro.core import entropy
from repro.core.overlap import FinalizeQueue, _attach_context
from repro.core.pipeline import StepMeta
from repro.obs import report, telemetry, trace
from repro.obs.report import STEP_TELEMETRY_KEYS

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

P = NumarckParams(error_bound=1e-3, max_bins=1024, block_bytes=512)


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Tests must never leak an enabled registry into each other."""
    telemetry.stop()
    yield
    telemetry.stop()


def _series(n_steps=4, n=4096, seed=0):
    rng = np.random.default_rng(seed)
    out = [rng.normal(size=n).astype(np.float32)]
    for _ in range(n_steps - 1):
        out.append(out[-1]
                   + rng.normal(scale=1e-4, size=n).astype(np.float32))
    return out


def _blob_sig(steps):
    """Everything that lands in the NCK container, as comparable bytes."""
    return [(s.b_bits, s.codec, tuple(s.block_codecs or ()),
             tuple(s.index_blocks),
             b"" if s.incomp_values is None else s.incomp_values.tobytes())
            for s in steps]


# ---------------------------------------------------------------- spans

def test_span_nesting_depth_and_attrs():
    with telemetry.capture() as reg:
        with telemetry.span("a", step=1) as sa:
            with telemetry.span("b"):
                with telemetry.span("c") as sc:
                    sc.set(late=42)
            sa.set(bytes_out=7)
    recs = {r.name: r for r in reg.spans}
    assert [recs[n].depth for n in "abc"] == [0, 1, 2]
    # children close before (and inside) their parent
    assert recs["a"].t0 <= recs["b"].t0 <= recs["c"].t0
    assert recs["c"].t1 <= recs["b"].t1 <= recs["a"].t1
    # late-set attributes are recorded
    assert recs["a"].attrs == {"step": 1, "bytes_out": 7}
    assert recs["c"].attrs == {"late": 42}
    assert all(r.duration >= 0.0 for r in reg.spans)


def test_span_stack_is_thread_local():
    """Nesting depth is per thread: a worker span opened while the main
    thread holds a span open starts at depth 0 on its own lane."""
    with telemetry.capture() as reg:
        def worker():
            with telemetry.span("w.outer"):
                with telemetry.span("w.inner"):
                    pass
        with telemetry.span("main.outer"):
            t = threading.Thread(target=worker, name="obs-worker")
            t.start()
            t.join()
    recs = {r.name: r for r in reg.spans}
    assert recs["w.outer"].depth == 0
    assert recs["w.inner"].depth == 1
    assert recs["main.outer"].depth == 0
    assert recs["w.outer"].tid != recs["main.outer"].tid
    assert recs["w.inner"].tname == "obs-worker"


def test_span_error_recorded_and_propagates():
    with telemetry.capture() as reg:
        with pytest.raises(ValueError, match="boom"):
            with telemetry.span("failing"):
                raise ValueError("boom")
        # the stack unwound: a follow-up span is back at depth 0
        with telemetry.span("after"):
            pass
    recs = {r.name: r for r in reg.spans}
    assert recs["failing"].error == "ValueError: boom"
    assert recs["after"].depth == 0
    assert report.rollup(reg)["spans"]["failing"]["errors"] == 1


def test_capture_scoping():
    assert not telemetry.enabled()
    with telemetry.capture() as reg:
        assert telemetry.enabled() and telemetry.active() is reg
    assert not telemetry.enabled()
    assert telemetry.stop() is None


# ------------------------------------------------------- disabled path

def test_disabled_returns_shared_noop():
    assert not telemetry.enabled()
    assert telemetry.span("x") is telemetry.NOOP_SPAN
    assert telemetry.span("y", k=1) is telemetry.NOOP_SPAN
    assert telemetry.NOOP_SPAN.set(a=1) is telemetry.NOOP_SPAN
    assert telemetry.NOOP_SPAN.duration == 0.0
    # counters/gauges/hists fall through without touching a registry
    telemetry.counter("n"), telemetry.gauge("g", 1.0), telemetry.histo("h", 1.0)


def test_disabled_overhead_is_negligible():
    """The instrumentation left in the hot paths must cost ~nothing while
    disabled: per-callsite cost far under a percent of one small step."""
    assert not telemetry.enabled()
    N = 20_000

    def loop():
        t0 = time.perf_counter()
        for _ in range(N):
            with telemetry.span("hot"):
                pass
            telemetry.counter("hot.n")
            telemetry.gauge("hot.g", 1.0)
        return (time.perf_counter() - t0) / (3 * N)

    per_call = min(loop() for _ in range(3))         # best-of-3 vs noise
    series = _series()
    compress_series(series, P)                       # warm the jit caches
    t0 = time.perf_counter()
    steps = compress_series(series, P)
    step_s = (time.perf_counter() - t0) / len(series)
    assert steps[-1].meta.get("telemetry") is None   # really disabled
    # ~a dozen callsites per step; assert 100x that against 5% of a step
    assert 100 * per_call < 0.05 * step_s, (
        f"disabled telemetry too hot: {per_call * 1e9:.0f}ns/call vs "
        f"{step_s * 1e3:.2f}ms/step")


# ------------------------------------------- outputs must never change

def test_blobs_byte_identical_telemetry_on_off():
    series = _series()
    base = compress_series(series, P)
    with telemetry.capture():
        on = compress_series(series, P)
        on_overlap = compress_series(series, P, overlap=True)
    assert _blob_sig(on) == _blob_sig(base)
    assert _blob_sig(on_overlap) == _blob_sig(base)
    # and the instrumented steps reconstruct to exactly the same arrays
    for a, b in zip(decompress_series(on), decompress_series(base)):
        assert np.array_equal(a, b)


# ------------------------------------------------- per-step rollup

def test_step_telemetry_canonical_keys_across_overlap_modes():
    series = _series()
    with telemetry.capture():
        serial = compress_series(series, P, overlap=False)
        overlap = compress_series(series, P, overlap=True)
    for steps in (serial, overlap):
        for st in steps:
            tele = st.meta["telemetry"]
            assert tuple(tele) == STEP_TELEMETRY_KEYS
            assert tele["bytes_in"] > 0 and tele["bytes_out"] > 0
            assert tele["finalize_s"] >= 0.0
    # anchors carry the same key set as delta steps
    assert serial[0].is_anchor and not serial[1].is_anchor
    # the non-timing fields are deterministic across modes
    for a, b in zip(serial, overlap):
        ta, tb = a.meta["telemetry"], b.meta["telemetry"]
        for k in ("bytes_in", "bytes_out", "entropy_ratio", "codec",
                  "device_entropy"):
            assert ta[k] == tb[k]


def test_sharded_driver_same_telemetry_shape_and_blobs():
    """Single-device vs sharded (1-shard mesh in-process): identical
    canonical telemetry keys, byte-identical blobs, on or off."""
    import jax
    from jax.sharding import Mesh
    from repro.distributed.pipeline import ShardedCompressor

    series = _series(n_steps=3)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    base = compress_series(series, P)
    sc = ShardedCompressor(mesh, "data", P, use_pallas=False)
    off = sc.compress_series(series)
    assert _blob_sig(off) == _blob_sig(base)
    with telemetry.capture():
        on = sc.compress_series(series)
        single = compress_series(series, P)
    assert _blob_sig(on) == _blob_sig(base)
    for st_s, st_d in zip(single, on):
        ts, td = st_s.meta["telemetry"], st_d.meta["telemetry"]
        assert tuple(ts) == tuple(td) == STEP_TELEMETRY_KEYS
    roll_s = report.series_rollup(single)
    roll_d = report.series_rollup(on)
    for k in ("steps", "bytes_in", "bytes_out", "codecs"):
        assert roll_s[k] == roll_d[k]
    sc.close()


def test_series_rollup():
    series = _series()
    with telemetry.capture():
        steps = compress_series(series, P)
    roll = report.series_rollup(steps)
    assert roll["steps"] == len(series)
    assert roll["steps_without_telemetry"] == 0
    # bytes_in is entropy-stage input (anchor raw bytes + packed index
    # bytes per delta step), so it sits between one step's raw size and
    # the whole series' raw size for this well-binned series
    raw = sum(a.nbytes for a in series)
    assert series[0].nbytes <= roll["bytes_in"] <= raw
    assert 0 < roll["bytes_out"] < roll["bytes_in"]
    assert roll["entropy_ratio_mean"] > 1.0
    assert sum(roll["codecs"].values()) == len(series)
    assert all(v >= 0.0 for v in roll["totals"].values())
    # steps compressed with telemetry off are counted, not invented
    plain = compress_series(series, P)
    roll2 = report.series_rollup(plain)
    assert roll2["steps"] == 0
    assert roll2["steps_without_telemetry"] == len(series)


def test_rollup_aggregates():
    series = _series()
    with telemetry.capture() as reg:
        compress_series(series, P)
    roll = report.rollup(reg)
    for name in ("finalize", "finalize.entropy", "finalize.anchor",
                 "encode.analyze", "encode.index", "entropy.compress"):
        assert name in roll["spans"], sorted(roll["spans"])
    fin = roll["spans"]["finalize"]
    assert fin["count"] == len(series) - 1          # anchor has its own span
    assert fin["total_s"] >= fin["max_s"] >= fin["mean_s"] >= 0.0
    assert any(k.startswith("entropy.bytes_in.") for k in roll["counters"])


# -------------------------------------------------------- chrome trace

def test_chrome_trace_json_valid_with_pool_lanes(tmp_path):
    rng = np.random.default_rng(1)
    with telemetry.capture() as reg:
        compress_series(_series(), P, overlap=True)
        # drive the shared entropy pool directly: > _MIN_PARALLEL_BYTES
        raws = [rng.integers(0, 8, 1 << 19, dtype=np.uint8).tobytes()
                for _ in range(8)]
        entropy.compress_blocks(raws, codec="zlib", parallel=True)
    path = trace.write_chrome_trace(str(tmp_path / "trace.json"), reg)
    with open(path) as f:
        doc = json.load(f)                           # valid JSON
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no span events"
    for e in xs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        json.dumps(e["args"])                        # attrs all jsonable
    lanes = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert any(n.startswith("finalize") for n in lanes), lanes
    assert any(n.startswith("entropy") for n in lanes), lanes
    assert any(n.startswith("MainThread") for n in lanes), lanes
    # the FinalizeQueue depth gauge exports as counter events
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert "finalize.depth" in counters
    assert doc["otherData"]["counters"]


# ------------------------------------------------ overlap queue metrics

def test_finalize_queue_metrics():
    with telemetry.capture() as reg:
        q = FinalizeQueue(True, name="qq", max_in_flight=1)
        for _ in range(3):
            q.submit(time.sleep, 0.02, label="napping")
        q.close()
    roll = report.rollup(reg)
    assert roll["hists"]["qq.queue_wait_s"]["count"] == 3
    assert roll["gauges"]["qq.depth"]["max"] == 1.0
    assert roll["counters"]["qq.stall_s"] > 0.0      # bound forced a stall
    assert roll["spans"]["qq.task"]["count"] == 3
    assert roll["spans"]["qq.flush"]["count"] >= 1


@pytest.mark.parametrize("overlap", [False, True])
def test_finalize_queue_exception_context(overlap):
    def explode(i):
        raise ValueError(f"bad step data {i}")

    q = FinalizeQueue(overlap, name="shard-finalize")
    with telemetry.capture() as reg:
        f = q.submit(explode, 7, label="finalize step 7")
        # original message stays a prefix: match= keeps working
        with pytest.raises(ValueError, match="^bad step data 7") as ei:
            if overlap:
                q.flush()
            else:
                f.result()
        q.close()
    # the worker/stage/step context rides in the message ...
    assert "[shard-finalize worker: finalize step 7]" in str(ei.value)
    assert ei.value.args[0].startswith("bad step data 7")
    # ... and the failure is recorded on the task span
    assert report.rollup(reg)["spans"]["shard-finalize.task"]["errors"] == 1


def test_exception_context_attached_once():
    e = ValueError("boom")
    _attach_context(e, "finalize", "finalize step 2")
    _attach_context(e, "finalize", "finalize step 2")   # resurfaced future
    assert str(e).count("[finalize worker: finalize step 2]") == 1


# ------------------------------------------------ zlib_ratio deprecation

def test_zlib_ratio_alias_warns_once():
    series = _series(n_steps=2)
    steps = compress_series(series, P)
    meta = steps[1].meta
    assert isinstance(meta, StepMeta)
    StepMeta._warned = False                 # order-independence
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert meta["zlib_ratio"] == meta["entropy_ratio"]
        assert meta.get("zlib_ratio") == meta["entropy_ratio"]
        steps[1].meta.get("zlib_ratio")      # and again via another read
    deps = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1                    # once per process, not per read
    assert "entropy_ratio" in str(deps[0].message)
    # non-alias reads never warn
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        meta["entropy_ratio"], meta.get("entropy_codec")


# ------------------------------------------------ perf regression gate

def test_check_regression_compare():
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    from benchmarks.check_regression import compare, parse_cr

    def row(name, us, derived=""):
        return {"name": name, "us_per_call": us, "derived": derived}

    tracked = {"enc": row("enc", 1000.0, "CR=4.00"),
               "tiny": row("tiny", 20.0)}
    # identical -> pass
    assert compare(tracked, dict(tracked), 0.5, 0.05, 100.0) == []
    # +40% under a +50% tolerance -> pass; +120% -> fail
    assert compare(tracked, {"enc": row("enc", 1400.0, "CR=4.00")},
                   0.5, 0.05, 100.0) == []
    probs = compare(tracked, {"enc": row("enc", 2200.0, "CR=4.00")},
                    0.5, 0.05, 100.0)
    assert len(probs) == 1 and "enc" in probs[0]
    # sub-min_us rows are noise: never timing-gated
    assert compare(tracked, {"tiny": row("tiny", 900.0)},
                   0.5, 0.05, 100.0) == []
    # CR regressions fail even when timing is fine
    probs = compare(tracked, {"enc": row("enc", 1000.0, "CR=3.00")},
                    0.5, 0.05, 100.0)
    assert len(probs) == 1 and "CR=3.00" in probs[0]
    # a bench that failed to run fails the gate outright
    probs = compare(tracked, {"x_FAILED": row("x_FAILED", 0.0, "boom")},
                    0.5, 0.05, 100.0)
    assert len(probs) == 1 and "failed" in probs[0]
    assert parse_cr("CR=2.50 n=3") == 2.5 and parse_cr("") is None
