"""Index upload per restored step: span ``decode.upload`` (int32 staging,
``device_put`` and its wait, inside ``decode.entropy``)."""


def read(ctx):
    if ctx.mode != "read":
        return None
    return ctx.per_unit_ms(["decode.upload"])
