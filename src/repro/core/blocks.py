"""Index-table blocking + per-block entropy coding (paper Sec. IV-C).

The index table is split into fixed-element-count blocks, each entropy-
coded independently so that partial decompression only decodes the
overlapped blocks.  Two offset tables accompany the blocks (paper Fig. 2):
  * index_table_offset        -- byte offset of each coded block
  * incompressible_table_offset -- number of incompressible elements before
                                   each block (locates exceptions)

Packing and entropy coding themselves live in the shared stage modules
(``core.pipeline``, ``core.entropy``); this module keeps the thin
block-level API the decompressors and baselines use.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from repro.core import entropy, packing
from repro.core import pipeline as pipe
from repro.obs import telemetry


def block_slices(n: int, block_elems: int) -> List[Tuple[int, int]]:
    return pipe.block_slices(n, block_elems)


def deflate_blocks(idx: np.ndarray, b_bits: int, block_elems: int,
                   level: int = 6, codec: str = entropy.DEFAULT_CODEC,
                   parallel: bool = True):
    """Pack + entropy-code each block.
    Returns (blocks, raw_sizes, incomp_offsets).

    incomp_offsets[i] = number of incompressible markers (== 2**B - 1) in
    blocks [0, i) -- the exclusive prefix the decompressor needs.
    """
    raws = pipe.pack_blocks_host(idx, b_bits, block_elems)
    blocks = entropy.compress_blocks(raws, codec=codec, level=level,
                                     parallel=parallel)
    raw_sizes = np.asarray([len(r) for r in raws], np.int64)
    marker = (1 << b_bits) - 1
    incomp_offsets = pipe.exception_offsets(
        np.asarray(idx).reshape(-1) == marker, block_elems)
    return blocks, raw_sizes, incomp_offsets


def inflate_block(blob: bytes, n_elems: int, b_bits: int,
                  codec: str = entropy.DEFAULT_CODEC) -> np.ndarray:
    with telemetry.span("decode.inflate"):
        packed = np.frombuffer(entropy.decompress_block(blob, codec),
                               dtype=np.uint8)
    with telemetry.span("decode.unpack"):
        return packing.unpack_indices_np(packed, n_elems, b_bits)


def zlib_ratio(blocks: List[bytes], raw_sizes: np.ndarray) -> float:
    """Average entropy compression ratio of the index table (paper
    Table 9).  Name kept from the zlib-only days for compatibility."""
    return pipe.entropy_ratio(blocks, raw_sizes)


__all__ = ["block_slices", "deflate_blocks", "inflate_block", "zlib_ratio"]
