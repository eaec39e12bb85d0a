"""Re-layout of a field the mesh holds into the encode stages' flat
layout, per field-step: span ``encode.relayout`` (one jitted program;
across chips a collective).  A program that copies the field through the
host instead has no such span and reads nothing."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.per_unit_ms(["encode.relayout"])
