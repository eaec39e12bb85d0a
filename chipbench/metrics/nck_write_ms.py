"""NCK container write per field-step, fsync included: span ``nck.write``."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.per_unit_ms(["nck.write"])
