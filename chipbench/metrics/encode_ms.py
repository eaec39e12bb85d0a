"""Index, exceptions and pack per field-step: spans ``encode.index``,
``encode.exceptions``, ``encode.pack_fetch`` and ``encode.idx_fetch``."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.per_unit_ms(["encode.index", "encode.exceptions",
                            "encode.pack_fetch", "encode.idx_fetch"])
