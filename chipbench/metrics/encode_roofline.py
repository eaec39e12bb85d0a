"""The sharded encode program's share of the memory roofline: the least
time to read ``prev`` and ``curr`` once and write the index packed at each
unit's B bits, at the chip's peak bandwidth, over the device time of the
program (``_encode_shard``: index, edge exchange, bit-pack), one per
field-step, each chip doing its share.  The int32 index the program also
writes is not counted: a program that stops writing it shows a gain."""
from chipbench import roofline


def encode_bytes(n: int, itemsize: int, b_bits: int) -> int:
    """One read of ``prev`` and of ``curr``, one write of the packed index."""
    return 2 * n * itemsize + -(-n * b_bits // 8)


def read(ctx):
    if ctx.mode != "write" or ctx.trace is None or not ctx.units:
        return None
    secs = ctx.trace.program_seconds("encode_shard")
    chips = len(ctx.trace.ops)
    nbytes = sum(encode_bytes(ctx.n, ctx.itemsize, u.b_bits)
                 for u in ctx.units)
    return roofline.roofline_pct(nbytes / chips, secs,
                                 ctx.peaks["hbm_bytes_per_s"])
