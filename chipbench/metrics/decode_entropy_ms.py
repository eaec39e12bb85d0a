"""Entropy decode per restored step: span ``decode.entropy``."""


def read(ctx):
    if ctx.mode != "read":
        return None
    return ctx.per_unit_ms(["decode.entropy"])
