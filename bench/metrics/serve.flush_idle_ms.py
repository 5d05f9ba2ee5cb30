"""Device idle time inside the service's flushes, per flush: the traced
window's time in which no op ran on the device and the program's
`serve.flush` span was open, per chip, over the number of flushes."""

from bench import scopes


def read(ctx):
    layers = scopes.of(ctx)
    if layers is None:
        return None
    flushes = len(layers.span_s("serve.flush"))
    if not flushes:
        return None
    return 1e3 * layers.idle_within_s("serve.flush") / flushes
