"""Host bit-pack of the B-bit index per field-step: span ``finalize.pack``
(``pipeline.pack_blocks_host``, inside ``finalize.entropy``)."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.per_unit_ms(["finalize.pack"])
