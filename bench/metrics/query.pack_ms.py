"""Median host time of the query service's pack step per flush: the
program's `query.pack` span (`query.pack_queries` on the device and the
read of the packed words back to the host), over the window's flushes."""

from bench import scopes


def read(ctx):
    return scopes.span_median_ms(ctx, "query.pack")
