"""Pallas TPU kernel: B-bit index packing (paper "bits packing" sub-phase).

Packs groups of 32 B-bit indices into B uint32 words of the little-endian
bitstream (layout identical to core.packing).  The MPI implementation
bit-copies "the B least significant bits of the integer to the corresponding
index table entry" one element at a time; on TPU we unroll the 32 static
element positions per word-group, so each tile is pure vector shifts/ors --
no scalar loop, no gather.

TPU adaptation: the tile is **lane-dense**.  The wrapper transposes the
(groups, 32) index table to (32, groups), so each of the 32 element
positions is one sublane row with the word-groups along the lanes; the
kernel ORs shifted rows into B output rows of (B, groups) words, which the
wrapper transposes back.  (A (rows, 32) -> (rows, B) tile keeps 32 or B
lanes per vreg and packed wrong words on a v5e chip, although interpret
mode agreed with the oracle.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GROUP = 32              # indices per word-group (32*B bits = B words)
DEFAULT_BLOCK_COLS = 2048


def _kernel(idx_ref, out_ref, *, b_bits):
    mask = jnp.uint32((1 << b_bits) - 1)
    words = [jnp.zeros((1, idx_ref.shape[1]), jnp.uint32)
             for _ in range(b_bits)]
    for j in range(GROUP):                      # static unroll
        v = idx_ref[j:j + 1, :].astype(jnp.uint32) & mask
        bit0 = j * b_bits
        w, s = bit0 // 32, bit0 % 32
        words[w] = words[w] | (v << s)
        if s + b_bits > 32:                      # spills into the next word
            words[w + 1] = words[w + 1] | (v >> (32 - s))
    for w in range(b_bits):
        out_ref[w:w + 1, :] = words[w]


@functools.partial(jax.jit,
                   static_argnames=("b_bits", "block_cols", "interpret"))
def pack_bits(idx: jax.Array, *, b_bits: int,
              block_cols: int = DEFAULT_BLOCK_COLS, interpret: bool = False):
    """(n,) int32 (n % 32 == 0) -> (n//32*B,) uint32 words."""
    n = idx.shape[0]
    assert n % GROUP == 0, "pad to a multiple of 32 first"
    groups = n // GROUP
    cols = pl.cdiv(groups, block_cols) * block_cols
    idx_t = jnp.pad(idx, (0, (cols - groups) * GROUP)).reshape(cols,
                                                               GROUP).T
    out = pl.pallas_call(
        functools.partial(_kernel, b_bits=b_bits),
        grid=(cols // block_cols,),
        in_specs=[pl.BlockSpec((GROUP, block_cols), lambda i: (0, i))],
        out_specs=pl.BlockSpec((b_bits, block_cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b_bits, cols), jnp.uint32),
        interpret=interpret,
    )(idx_t)
    return out.T.reshape(-1)[: groups * b_bits]


__all__ = ["pack_bits", "GROUP"]
