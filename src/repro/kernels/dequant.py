"""Pallas TPU kernel: fused decompression (unpack-free dequantize).

    out = prev * (1 + centers[idx])                 (corrected Eq. 4)

TPU adaptation: TPUs have no fast VMEM gather, so the codebook lookup
centers[idx] is a **two-level one-hot lookup**.  The padded codebook is
laid out as a (LO, HI) table with centers[h * LO + l] at [l, h].  For one
1024-lane row of indices, one MXU matmul of the table against the one-hot
of ``idx // LO`` (HI, 1024) yields every candidate column (LO, 1024); a
compare against ``idx % LO`` then selects one sublane per lane.

The lookup must be exact: the chain promises bit-identity with the host
NumPy reconstruction.  A float matmul may round the centers on the MXU, so
the table holds the four *bytes* of each center's bit pattern as bf16
(integers 0..255 are exact in bf16).  With one nonzero product per output
the f32 accumulation is exact, and the bytes reassemble to the center's
bits with integer shifts -- no arithmetic ever touches the value itself.

Incompressible lanes (idx == 2^B - 1) are produced as 0 by the raw kernel;
`patch_exceptions` scatters the exception table back over them **on
device** (one `.at[].set`), so full reconstruction never has to leave the
accelerator.  `dequantize_jnp` is the dtype-preserving gather path used
for float64 chains (under jax_enable_x64) and off the TPU; for float32 it
is bit-identical to the Pallas kernel (exact lookup, then the same IEEE
f32 ``prev * (1 + c)`` in both lowerings).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANE = 1024
DEFAULT_BLOCK_ROWS = 64
_HI_ALIGN = 16          # bf16 sublane tile


def _split(k: int):
    """(LO, HI): a codebook of k centers as LO sublanes x HI columns.

    LO ~ sqrt(k) balances the one-hot build (HI compares per element)
    against the byte selects (4 * LO per element); HI pads to the bf16
    sublane tile.  Indices at or past k (the marker) find an all-zero
    one-hot column or a zero table entry, i.e. center 0, as in
    `dequantize_jnp`'s zero-padded LUT."""
    log_k = max(1, (k - 1).bit_length())
    lo_w = min(128, max(8, 1 << -(-log_k // 2)))
    hi_w = max(1, -(-k // lo_w))
    return lo_w, -(-hi_w // _HI_ALIGN) * _HI_ALIGN


def _byte_table(centers):
    """(4 * LO, HI) bf16: byte j of bits(centers[h * LO + l]) at
    [j * LO + l, h]; entries past the codebook are 0."""
    k = centers.shape[0]
    lo_w, hi_w = _split(k)
    c = jnp.zeros((lo_w * hi_w,), jnp.float32).at[:k].set(
        centers.astype(jnp.float32))
    bits = lax.bitcast_convert_type(c, jnp.uint32).reshape(hi_w, lo_w).T
    planes = [(bits >> (8 * j)) & 0xFF for j in range(4)]
    return jnp.concatenate(planes, axis=0).astype(jnp.bfloat16)


def _kernel(idx_ref, prev_ref, tab_ref, out_ref, *, marker, lo_w, hi_w):
    tab = tab_ref[...]                              # (4 * LO, HI) bf16
    hi_iota = lax.broadcasted_iota(jnp.int32, (hi_w, LANE), 0)
    lo_iota = lax.broadcasted_iota(jnp.int32, (lo_w, LANE), 0)
    shift = lo_w.bit_length() - 1

    @pl.loop(0, idx_ref.shape[0])
    def _row(r):
        idx = idx_ref[pl.ds(r, 1), :]               # (1, LANE) int32
        onehot = (hi_iota == (idx >> shift)).astype(jnp.bfloat16)
        cols = jnp.dot(tab, onehot, preferred_element_type=jnp.float32)
        pick = lo_iota == (idx & (lo_w - 1))
        bits = jnp.zeros_like(idx)
        for j in range(4):                          # static: 4 bytes
            byte = jnp.sum(jnp.where(pick, cols[j * lo_w:(j + 1) * lo_w],
                                     0.0), axis=0, keepdims=True)
            bits = bits | (byte.astype(jnp.int32) << (8 * j))
        c = lax.bitcast_convert_type(bits, jnp.float32)
        out = prev_ref[pl.ds(r, 1), :] * (1.0 + c)
        out_ref[pl.ds(r, 1), :] = jnp.where(idx == marker, 0.0, out)


@functools.partial(jax.jit,
                   static_argnames=("b_bits", "block_rows", "interpret"))
def dequantize(idx: jax.Array, prev: jax.Array, centers: jax.Array, *,
               b_bits: int, block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: bool = False):
    """(n,) i32 idx, (n,) f32 prev, (k,) f32 centers -> (n,) f32 recon.

    Incompressible positions (idx == 2^B - 1) return 0.0; patch them from
    the exception table afterwards.
    """
    n = idx.shape[0]
    marker = (1 << b_bits) - 1
    lo_w, hi_w = _split(centers.shape[0])
    tab = _byte_table(centers)

    rows = pl.cdiv(n, LANE)
    rows_pad = pl.cdiv(rows, block_rows) * block_rows
    pad = rows_pad * LANE - n
    # Pad with the marker so padded lanes don't contribute NaNs.
    idx2 = jnp.pad(idx, (0, pad), constant_values=marker).reshape(rows_pad,
                                                                  LANE)
    prev2 = jnp.pad(prev.astype(jnp.float32), (0, pad)).reshape(rows_pad,
                                                                LANE)
    grid = (rows_pad // block_rows,)
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, marker=marker, lo_w=lo_w, hi_w=hi_w),
        grid=grid,
        in_specs=[blk, blk, pl.BlockSpec(tab.shape, lambda i: (0, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANE), jnp.float32),
        interpret=interpret,
    )(idx2, prev2, tab)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("b_bits",))
def dequantize_jnp(idx: jax.Array, prev: jax.Array, centers: jax.Array, *,
                   b_bits: int):
    """Dtype-preserving gather dequantize (no Pallas).

    Arithmetic runs in `prev.dtype` -- the float64 chain path under
    jax_enable_x64 -- and for float32 inputs is bit-identical to the
    Pallas byte-table kernel.  Marker lanes return 0 like `dequantize`.
    """
    idx = jnp.asarray(idx)
    prev = jnp.asarray(prev)
    marker = (1 << b_bits) - 1
    lut = jnp.zeros((marker + 1,), prev.dtype)
    lut = lut.at[: centers.shape[0]].set(centers.astype(prev.dtype))
    comp = prev * (1 + lut[jnp.clip(idx, 0, marker)])
    return jnp.where(idx == marker, jnp.zeros((), prev.dtype), comp)


@functools.partial(jax.jit, static_argnames=("b_bits",))
def patch_exceptions(recon: jax.Array, idx: jax.Array,
                     exc_values: jax.Array, *, b_bits: int):
    """Scatter the compacted exception table over the marker lanes on
    device: one segment-wise ``.at[].set`` replaces the host boolean-mask
    scatter the dequantize kernel used to punt to.

    The exception table is compacted in stream order, which equals the
    per-block offset-table order (blocks partition the stream), so a
    single global scatter patches every block's segment at once; ranged
    readers slice the table by the offset table first and pass the slice.
    ``exc_values`` may be padded past the true marker count -- surplus
    positions resolve to ``idx.size`` and are dropped by the scatter.
    """
    marker = (1 << b_bits) - 1
    m = exc_values.shape[0]
    if m == 0:
        return recon
    pos = jnp.flatnonzero(idx == marker, size=m, fill_value=idx.shape[0])
    return recon.at[pos].set(exc_values.astype(recon.dtype), mode="drop")


__all__ = ["dequantize", "dequantize_jnp", "patch_exceptions"]
