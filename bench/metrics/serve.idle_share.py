"""Share of the traced window in which no op ran on the device, while the
open loop served lookups (mean over the chips)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
