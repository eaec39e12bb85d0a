"""Share of the read window in which no op ran on the device, mean over
the chips (device trace)."""


def read(ctx):
    if ctx.mode != "read" or ctx.trace is None:
        return None
    return ctx.trace.idle_pct()
