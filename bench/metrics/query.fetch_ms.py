"""Median host time of the query service's fetch step per flush: the
program's `query.fetch` span (the answers and the five stat scalars read
back to the host, which waits for the lookup to end), over the window's
flushes."""

from bench import scopes


def read(ctx):
    return scopes.span_median_ms(ctx, "query.fetch")
