"""Dequantize and exception patch per restored step: spans
``decode.dequant`` and ``decode.patch``."""


def read(ctx):
    if ctx.mode != "read":
        return None
    return ctx.per_unit_ms(["decode.dequant", "decode.patch"])
