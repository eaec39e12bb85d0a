"""The chain advance's share of the memory roofline: the least time to
read the B-bit index and ``prev`` and write the reconstruction once at
the chip's peak bandwidth, over the device time of the chain-advance
program (the Pallas dequantize and the exception patch:
``chain_advance``), one per field-step."""
from chipbench import roofline


def read(ctx):
    if ctx.mode != "write" or ctx.trace is None or not ctx.units:
        return None
    secs = ctx.trace.program_seconds("advance")
    chips = len(ctx.trace.ops)
    nbytes = sum(roofline.dequant_bytes(ctx.n, ctx.itemsize, u.b_bits)
                 for u in ctx.units)
    return roofline.roofline_pct(nbytes / chips, secs,
                                 ctx.peaks["hbm_bytes_per_s"])
