"""The comparison that decides ``correct``: the plain reference agrees
with the program where it should, and the control (the reference at
bfloat16 in the program's place) fails it."""
import numpy as np
import pytest

import jax
from chipbench import control, data, harness, reference as ref
from chipbench.tests._cells import run_tiny, tiny
from repro.core import NumarckParams, TemporalCompressor
from repro.core.container import NCKWriter
from repro.core.compress import decompress_step


def _series(steps=4, shape=(8, 64, 64)):
    s = harness.load_config("isabel")["stats"]
    gen = data.FieldGen(shape, "float32", s)
    key = data.seed_key(11)
    pool = [gen.change(key, j) for j in range(2)]
    return [np.asarray(a) for a in gen.series(key, 0, pool,
                                               [0, 1, 0, 1][:steps - 1])]


def test_reference_chain_equals_the_programs_files(tmp_path):
    series = _series()
    comp = TemporalCompressor(NumarckParams(error_bound=1e-3))
    prog_prev = ref_prev = series[0]
    comp.add(series[0])
    for t, curr in enumerate(series[1:], start=1):
        step = comp.add(curr)
        path = str(tmp_path / f"s{t}.nck")
        w = NCKWriter()
        w.add_step("s", step)
        w.write(path)
        got, idx, b_bits = ref.decode(ref.NCKFile(path), "s", prog_prev)
        np.testing.assert_array_equal(got, decompress_step(step, prog_prev))
        want, ridx, rb = ref.compress_step(ref_prev, curr, 1e-3)
        assert rb == b_bits == step.b_bits
        np.testing.assert_array_equal(ridx, idx)
        np.testing.assert_array_equal(want, got)
        prog_prev, ref_prev = got, want
    comp.close()


def test_unpack_reads_lsb_first_fields():
    vals = np.array([5, 0, 31, 17, 2, 9, 30, 1, 16], np.int64)
    bits = ((vals[:, None] >> np.arange(5)) & 1).astype(np.uint8)
    stream = np.packbits(bits.reshape(-1), bitorder="little").tobytes()
    np.testing.assert_array_equal(ref.unpack(stream, 9, 5), vals)
    vals12 = np.array([4095, 0, 1234, 77], np.int64)
    bits = ((vals12[:, None] >> np.arange(12)) & 1).astype(np.uint8)
    stream = np.packbits(bits.reshape(-1), bitorder="little").tobytes()
    np.testing.assert_array_equal(ref.unpack(stream, 4, 12), vals12)


@pytest.mark.parametrize("cell", ["isabel.write", "isabel.read"])
def test_program_passes_at_a_small_size(cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", ["isabel.write", "isabel.read"])
def test_control_fails_and_the_reference_itself_passes(cell, tmp_path):
    bench, c, config, traffic = tiny(cell)
    devs = jax.devices()[:1]
    low = control.readings(c, config, traffic, 5, 6, devs, str(tmp_path))
    assert any(v["fails"] for v in low["checks"].values()), low
    same = control.readings(c, config, traffic, 5, 6, devs, str(tmp_path),
                            precision="float32")
    assert not any(v["fails"] for v in same["checks"].values()), same


def test_ulp_distance_counts_floats_between_two_values():
    from chipbench.loadgen import mismatch, ulp_distance
    a = np.array([1.0, -1.0, 0.0, 2.0], np.float32)
    b = np.nextafter(a, np.float32(np.inf))
    np.testing.assert_array_equal(ulp_distance(a, b), [1, 1, 1, 1])
    np.testing.assert_array_equal(ulp_distance(np.float32([-0.0]),
                                               np.float32([0.0])), [0])
    c = np.array([1.0, 1.0], np.float32)
    d = np.array([1.0 + 2e-3, 1.0 + 64 * 2**-23], np.float32)
    assert mismatch(c, d, 64) == 0.5      # a bin away counts, 64 ulps not
    assert mismatch(c, d, 0) == 1.0
