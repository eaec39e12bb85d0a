"""NCK container read per restored step: span ``nck.read``
(``NCKReader.read_step``: sections read and digests checked)."""


def read(ctx):
    if ctx.mode != "read":
        return None
    return ctx.per_unit_ms(["nck.read"])
