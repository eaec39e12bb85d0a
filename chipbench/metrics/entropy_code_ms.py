"""Entropy coding of the packed index per field-step: span
``entropy.compress`` (the pooled codec, inside ``finalize.entropy``)."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.per_unit_ms(["entropy.compress"])
