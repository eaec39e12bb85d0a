"""Driver time per field-step: the benchmark's span around the
compressor's ``add`` less the program's stage spans inside it (input
fetch, padding, uploads, dispatch)."""


def read(ctx):
    if ctx.mode != "write":
        return None
    return ctx.self_ms("bench.add")
