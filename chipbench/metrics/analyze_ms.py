"""Analyze stage per field-step: span ``encode.analyze``."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.per_unit_ms(["encode.analyze"])
