"""Entropy decode of the index blocks per restored step: span
``decode.inflate``, summed over the entropy pool's threads (thread-ms,
not wall time)."""


def read(ctx):
    if ctx.mode != "read":
        return None
    return ctx.per_unit_ms(["decode.inflate"])
