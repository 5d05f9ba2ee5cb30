"""Median host wall time of one `QueryService.flush()` in the window: from
the call to the answers on the host, for a batch of whatever was due."""

import numpy as np


def read(ctx):
    flush_s = ctx.counters.get("flush_s")
    if flush_s is None or len(flush_s) == 0:
        return None
    return 1e3 * float(np.median(flush_s))
