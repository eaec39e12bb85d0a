"""Ahead-of-time compiles for a TPU v5e chip at a real field width.

No chip is needed: JAX describes a ``v5e:2x2`` topology and the TPU
compiler (Mosaic for the Pallas kernels) compiles for it, so a kernel the
chip would refuse fails here.  Shapes are one Hurricane ISABEL field
(100 x 500 x 500 f32 = 25 M elements).  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
pytest-xdist workers each import every test file.  All such compiles
live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack, change_ratio, dequant, hist
from repro.kernels import ops as kops

N_ISABEL = 100 * 500 * 500
MAX_BINS = 1 << 16
MOSAIC = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with JAX's persistent compilation cache off:
    an executable for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def test_change_ratio_bins_compiles(one_chip):
    f32 = _spec((N_ISABEL,), jnp.float32, one_chip)
    fn = jax.jit(lambda p, c: change_ratio.change_ratio_bins(
        p, c, -0.05, 2e-3, max_bins=MAX_BINS))
    assert MOSAIC in _compile_text(fn, f32, f32)


def test_histogram_compiles(one_chip):
    ids = _spec((N_ISABEL,), jnp.int32, one_chip)
    fn = jax.jit(lambda i: hist.histogram(i, max_bins=MAX_BINS))
    assert MOSAIC in _compile_text(fn, ids)


@pytest.mark.parametrize("b_bits", [8, 16])
def test_pack_bits_compiles(one_chip, b_bits):
    idx = _spec((N_ISABEL,), jnp.int32, one_chip)
    fn = jax.jit(lambda i: bitpack.pack_bits(i, b_bits=b_bits))
    assert MOSAIC in _compile_text(fn, idx)


@pytest.mark.parametrize("b_bits", [8, 16])
def test_dequantize_compiles(one_chip, b_bits):
    idx = _spec((N_ISABEL,), jnp.int32, one_chip)
    prev = _spec((N_ISABEL,), jnp.float32, one_chip)
    centers = _spec(((1 << b_bits) - 1,), jnp.float32, one_chip)
    fn = jax.jit(lambda i, p, c: dequant.dequantize(i, p, c,
                                                    b_bits=b_bits))
    assert MOSAIC in _compile_text(fn, idx, prev, centers)


def test_chain_advance_compiles(one_chip, monkeypatch):
    # The wrappers pick interpret mode from jax.default_backend(), which
    # is the CPU here; compile the lowering the chip would run.
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    b_bits = 8
    idx = _spec((N_ISABEL,), jnp.int32, one_chip)
    f32 = _spec((N_ISABEL,), jnp.float32, one_chip)
    centers = _spec(((1 << b_bits) - 1,), jnp.float32, one_chip)
    text = kops.chain_advance.lower(idx, f32, f32, centers, b_bits=b_bits,
                                    use_pallas=True).compile().as_text()
    assert MOSAIC in text


def test_single_device_analyze_compiles(one_chip):
    from repro.core.compress import _analyze
    f32 = _spec((N_ISABEL,), jnp.float32, one_chip)
    compiled = _analyze.lower(f32, f32, np.float32(1e-3), MAX_BINS, 16,
                              4).compile()
    assert compiled.memory_analysis() is not None
