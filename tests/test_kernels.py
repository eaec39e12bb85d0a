"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bitpack, change_ratio, dequant, hist, ref

RNG = np.random.default_rng(1234)


def _temporal_pair(n, dtype, zero_frac=0.01, inf_frac=0.001):
    prev = RNG.normal(1.0, 0.7, n).astype(dtype)
    nz = RNG.random(n) < zero_frac
    prev[nz] = 0.0
    curr = (prev * (1 + 0.02 * RNG.standard_normal(n))).astype(dtype)
    bad = RNG.random(n) < inf_frac
    curr[bad] = np.inf
    return prev, curr


@pytest.mark.parametrize("n", [1, 100, 1024, 4097, 300_000])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_change_ratio_kernel(n, dtype):
    prev, curr = _temporal_pair(n, dtype)
    lo, w, m = -0.128, 0.002, 2048
    r_k, id_k = change_ratio.change_ratio_bins(
        jnp.asarray(prev, jnp.float32), jnp.asarray(curr, jnp.float32),
        lo, w, max_bins=m, interpret=True)
    r_r, id_r = ref.change_ratio_bins_ref(prev, curr, lo, w, max_bins=m)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(id_k), np.asarray(id_r))


@pytest.mark.parametrize("block_rows", [8, 256])
def test_change_ratio_kernel_block_shapes(block_rows):
    prev, curr = _temporal_pair(50_000, np.float32)
    r_k, id_k = change_ratio.change_ratio_bins(
        jnp.asarray(prev), jnp.asarray(curr), -0.064, 0.001, max_bins=1024,
        block_rows=block_rows, interpret=True)
    r_r, id_r = ref.change_ratio_bins_ref(prev, curr, -0.064, 0.001,
                                          max_bins=1024)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(id_k), np.asarray(id_r))


@pytest.mark.parametrize("b_bits", list(range(1, 17)) + [24])
def test_bitpack_kernel_all_widths(b_bits):
    n = 32 * 123
    idx = RNG.integers(0, 1 << b_bits, n).astype(np.int32)
    w_k = np.asarray(bitpack.pack_bits(jnp.asarray(idx), b_bits=b_bits,
                                       interpret=True))
    w_r = ref.pack_bits_ref(idx, b_bits=b_bits)
    np.testing.assert_array_equal(w_k, w_r)


@pytest.mark.parametrize("n_groups", [1, 7, 513, 4096])
def test_bitpack_kernel_sizes(n_groups):
    b = 11
    idx = RNG.integers(0, 1 << b, 32 * n_groups).astype(np.int32)
    w_k = np.asarray(bitpack.pack_bits(jnp.asarray(idx), b_bits=b,
                                       interpret=True))
    np.testing.assert_array_equal(w_k, ref.pack_bits_ref(idx, b_bits=b))


@pytest.mark.parametrize("b_bits", [2, 5, 8, 13, 16])
@pytest.mark.parametrize("n", [17, 2048, 100_001])
def test_dequant_kernel(b_bits, n):
    """The byte-table lookup is exact: bit-identical to the jnp oracle and
    the gather lowering, marker lanes included."""
    k = (1 << b_bits) - 1
    centers = RNG.uniform(-0.1, 0.1, k).astype(np.float32)
    centers[: min(k, 4)] = [0.0, -0.0, 1e-30, -3.5e-3][: min(k, 4)]
    idx = RNG.integers(0, k + 1, n).astype(np.int32)
    prev = RNG.normal(1, 0.5, n).astype(np.float32)
    out_k = np.asarray(dequant.dequantize(
        jnp.asarray(idx), jnp.asarray(prev), jnp.asarray(centers),
        b_bits=b_bits, interpret=True))
    out_r = np.asarray(ref.dequantize_ref(idx, prev, centers, b_bits=b_bits))
    out_j = np.asarray(dequant.dequantize_jnp(idx, prev, centers,
                                              b_bits=b_bits))
    np.testing.assert_array_equal(out_k, out_r)
    np.testing.assert_array_equal(out_k, out_j)


@pytest.mark.parametrize("max_bins", [1024, 4096, 65536])
@pytest.mark.parametrize("n", [100, 65_537])
def test_hist_kernel(max_bins, n):
    ids = RNG.integers(-1, max_bins, n).astype(np.int32)
    h_k = np.asarray(hist.histogram(jnp.asarray(ids), max_bins=max_bins,
                                    interpret=True))
    h_r = np.asarray(ref.histogram_ref(ids, max_bins=max_bins))
    np.testing.assert_array_equal(h_k, h_r)
    assert h_k.sum() == (ids >= 0).sum()


@pytest.mark.parametrize("block_rows", [8, 64])
def test_hist_kernel_many_tiles_all_chunks(block_rows):
    """Several element tiles accumulate into one resident output block:
    every one of the 64 1024-bin chunks is hit, and one hot bin collects
    counts from every tile."""
    max_bins = 65536
    n = 4 * 64 * 1024 + 777
    ids = RNG.integers(-1, max_bins, n).astype(np.int32)
    ids[::3] = 40_000                        # hot bin, in every tile
    ids[:64] = np.arange(64) * 1024 + 1023   # last bin of every chunk
    h_k = np.asarray(hist.histogram(jnp.asarray(ids), max_bins=max_bins,
                                    block_rows=block_rows, interpret=True))
    h_r = np.asarray(ref.histogram_ref(ids, max_bins=max_bins))
    np.testing.assert_array_equal(h_k, h_r)
    assert (h_k.reshape(64, 1024).sum(axis=1) > 0).all()
    assert h_k[40_000] == (ids == 40_000).sum() > 64 * 1024


def test_pack_matches_core_packing_bytes():
    """Kernel uint32 words viewed as bytes == core.packing byte stream."""
    from repro.core import packing
    b = 13
    idx = RNG.integers(0, 1 << b, 32 * 64).astype(np.int32)
    words = np.asarray(bitpack.pack_bits(jnp.asarray(idx), b_bits=b,
                                         interpret=True))
    byts = words.view("<u4").tobytes()
    expect = packing.pack_indices_np(idx, b).tobytes()
    assert byts[: len(expect)] == expect
