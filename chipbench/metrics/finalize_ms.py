"""Host finalize per field-step (exceptions, entropy coding, assembly):
span ``finalize``."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.per_unit_ms(["finalize"])
