"""Share of the traced window in which no op ran on the device, while
counting jobs ran back to back (mean over the chips)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
