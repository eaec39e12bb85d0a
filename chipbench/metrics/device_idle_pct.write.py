"""Share of the write window in which no op ran on the device, mean over
the chips (device trace)."""


def read(ctx):
    if ctx.mode != "write" or ctx.trace is None:
        return None
    return ctx.trace.idle_pct()
