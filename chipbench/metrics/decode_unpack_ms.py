"""B-bit unpack of the inflated index blocks per restored step: span
``decode.unpack``, summed over the entropy pool's threads (thread-ms, not
wall time)."""


def read(ctx):
    if ctx.mode != "read":
        return None
    return ctx.per_unit_ms(["decode.unpack"])
