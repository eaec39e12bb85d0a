"""Analyze's share of the memory roofline: the least time to read
``prev`` and ``curr`` once at the chip's peak bandwidth, over the device
time of the analyze program (``_analyze``), one run per field-step."""
from chipbench import roofline


def read(ctx):
    if ctx.mode != "write" or ctx.trace is None or not ctx.units:
        return None
    secs = ctx.trace.program_seconds("analyze")
    chips = len(ctx.trace.ops)
    nbytes = roofline.analyze_bytes(ctx.n, ctx.itemsize) * len(ctx.units)
    return roofline.roofline_pct(nbytes / chips, secs,
                                 ctx.peaks["hbm_bytes_per_s"])
