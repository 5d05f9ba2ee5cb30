"""Median host time of the query service's put step per flush: the
program's `query.put` span (padding the packed words to the pow2 batch and
copying them to the device), over the window's flushes."""

from bench import scopes


def read(ctx):
    return scopes.span_median_ms(ctx, "query.put")
