"""Pallas TPU kernel: candidate-bin histogram (phase 2 counting pass).

TPU adaptation: there is no atomic scatter-add on TPU, so the histogram
is a **two-level one-hot contraction on the MXU**.  A bin id splits as
``id = hi * 256 + lo``; for one 1024-lane row of ids, the one-hots of hi
(HI, 1024) and lo (256, 1024) contract over the lanes into a (HI, 256)
count tile -- every bin of the row at once.  One-hot entries are exact in
bf16 and a tile's counts stay far below 2^24, so the f32 accumulation is
exact; each element tile then adds its counts to the int32 output.

The output block is the same for every grid step, so it stays resident in
VMEM and is written back once at the end (the accumulation axis is the
only, sequential, grid axis).  Invalid elements carry bin_id == -1, whose
hi part (-1) matches no one-hot row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 1024
DEFAULT_BLOCK_ROWS = 64
BIN_CHUNK = 1024        # max_bins granularity
LO_BINS = 256           # bins per one-hot row of the lo part
_HI_ALIGN = 16          # bf16 sublane tile


def _kernel(id_ref, out_ref, *, hi_w):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hi_iota = lax.broadcasted_iota(jnp.int32, (hi_w, LANE), 0)
    lo_iota = lax.broadcasted_iota(jnp.int32, (LO_BINS, LANE), 0)

    def row(r, acc):
        ids = id_ref[pl.ds(r, 1), :]                # (1, LANE) int32
        hi = (hi_iota == (ids >> 8)).astype(jnp.bfloat16)
        lo = (lo_iota == (ids & (LO_BINS - 1))).astype(jnp.bfloat16)
        return acc + lax.dot_general(
            hi, lo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = lax.fori_loop(0, id_ref.shape[0], row,
                        jnp.zeros((hi_w, LO_BINS), jnp.float32))
    out_ref[...] += acc.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("max_bins", "block_rows", "interpret"))
def histogram(bin_ids: jax.Array, *, max_bins: int,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: bool = False):
    """(n,) int32 in [-1, max_bins) -> (max_bins,) int32 counts."""
    assert max_bins % BIN_CHUNK == 0, "max_bins must be a multiple of 1024"
    n = bin_ids.shape[0]
    rows = pl.cdiv(n, LANE)
    rows_pad = pl.cdiv(rows, block_rows) * block_rows
    ids2 = jnp.pad(bin_ids, (0, rows_pad * LANE - n),
                   constant_values=-1).reshape(rows_pad, LANE)
    hi_w = pl.cdiv(max_bins // LO_BINS, _HI_ALIGN) * _HI_ALIGN
    out = pl.pallas_call(
        functools.partial(_kernel, hi_w=hi_w),
        grid=(rows_pad // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((hi_w, LO_BINS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((hi_w, LO_BINS), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ids2)
    return out.reshape(-1)[:max_bins]


__all__ = ["histogram"]
