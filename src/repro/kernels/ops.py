"""Public jit'd wrappers over the Pallas kernels.

On TPU the compiled kernels run natively; everywhere else (this CPU
container, unit tests) they execute in interpret mode, which runs the same
kernel bodies element-faithfully.  `use_pallas=False` falls back to the
pure-jnp oracles -- the distributed pipeline exposes this so the dry-run can
compare both lowerings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitpack, change_ratio, dequant, hist, ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def change_ratio_bins(prev, curr, domain_lo, width, *, max_bins,
                      use_pallas: bool = True):
    if not use_pallas:
        return ref.change_ratio_bins_ref(prev, curr, domain_lo, width,
                                         max_bins=max_bins)
    return change_ratio.change_ratio_bins(prev, curr, domain_lo, width,
                                          max_bins=max_bins,
                                          interpret=_interpret())


def pack_bits(idx, *, b_bits, use_pallas: bool = True):
    if not use_pallas:
        return ref.pack_bits_ref(idx, b_bits=b_bits)
    return bitpack.pack_bits(idx, b_bits=b_bits, interpret=_interpret())


def dequantize(idx, prev, centers, *, b_bits, use_pallas: bool = True):
    # The Pallas byte-table kernel is f32-only; other dtypes (the f64
    # chain under jax_enable_x64) take the dtype-preserving gather path,
    # which is bit-identical for f32 anyway.
    if not use_pallas or jnp.asarray(prev).dtype != jnp.float32:
        return dequant.dequantize_jnp(idx, prev, centers, b_bits=b_bits)
    return dequant.dequantize(idx, prev, centers, b_bits=b_bits,
                              interpret=_interpret())


def histogram(bin_ids, *, max_bins, use_pallas: bool = True):
    if not use_pallas:
        return ref.histogram_ref(bin_ids, max_bins=max_bins)
    return hist.histogram(bin_ids, max_bins=max_bins,
                          interpret=_interpret())


def patch_exceptions(recon, idx, exc_values, *, b_bits):
    """Device-side exception scatter (see kernels.dequant)."""
    return dequant.patch_exceptions(recon, idx, exc_values, b_bits=b_bits)


def exception_positions(idx, n, marker, block_elems):
    """Device-side incompressible compaction, left on the device.

    Returns (per-block marker counts (nblocks,) int64 on the host, the
    ascending marker positions on the device padded with ``n`` to the next
    power of two of their count, and that count ``k``).  The padding keeps
    the jit cache bounded (<= log2(n) entries) across steps with varying
    exception counts.  ``k == 0`` gives no positions (None).
    """
    flat = jnp.asarray(idx).reshape(-1)[:n]
    mask = flat == marker
    nblocks = -(-n // block_elems)
    padded = jnp.pad(mask, (0, nblocks * block_elems - n))
    counts = np.asarray(
        padded.reshape(nblocks, block_elems).sum(axis=1,
                                                 dtype=jnp.int32)
    ).astype(np.int64)
    k = int(counts.sum())
    if k == 0:
        return counts, None, 0
    size = min(1 << (k - 1).bit_length(), n)
    (pos,) = jnp.nonzero(mask, size=size, fill_value=n)
    return counts, pos, k


def exception_compact(idx, n, marker, block_elems):
    """Device-side incompressible compaction for the encode stage.

    Returns (per-block marker counts (nblocks,) int64, ascending marker
    positions (k,) int64) computed on device -- the host finalize gathers
    the k exception values by position instead of re-scanning the full
    index table with a boolean mask (see ``exception_positions``).
    """
    counts, pos, k = exception_positions(idx, n, marker, block_elems)
    if k == 0:
        return counts, np.zeros(0, np.int64)
    return counts, np.asarray(pos)[:k].astype(np.int64)


def chain_advance_core(idx, prev, curr, centers, *, b_bits,
                       use_pallas: bool = True):
    """Unjitted REF_RECONSTRUCTED chain-advance body:

        R_i = prev * (1 + centers[idx]);  R_i[idx == marker] = curr[...]

    The exception patch comes straight from `curr` (the values the
    finalize stage will compact into the exception table), so the result
    is bit-identical to reconstructing from the finalized blob.  The one
    home of the marker-patch semantics: the jitted single-device
    `chain_advance` and the sharded `_advance_shard` stage both call it.
    """
    recon = dequantize(idx, prev, centers, b_bits=b_bits,
                       use_pallas=use_pallas)
    marker = (1 << b_bits) - 1
    return jnp.where(jnp.asarray(idx) == marker,
                     jnp.asarray(curr).astype(recon.dtype), recon)


@functools.partial(jax.jit, static_argnames=("b_bits", "use_pallas"))
def chain_advance(idx, prev, curr, centers, *, b_bits,
                  use_pallas: bool = True):
    """Fused device chain advance (jitted `chain_advance_core`)."""
    return chain_advance_core(idx, prev, curr, centers, b_bits=b_bits,
                              use_pallas=use_pallas)


__all__ = ["change_ratio_bins", "pack_bits", "dequantize",
           "patch_exceptions", "exception_positions", "exception_compact",
           "chain_advance",
           "chain_advance_core", "histogram"]
