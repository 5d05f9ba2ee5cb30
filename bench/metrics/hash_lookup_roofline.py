"""The query lookup kernel's share of its HBM roofline.

Bytes per live query: its 4 B key read, its 4 B count written, and one
8 B slot (key and count) read: 16 B. Live queries are every k-mer word of
every request the window served (padding slots of the pow2 batch are not
work). The bound is HBM bandwidth. Time is the summed device time of the
`hash_lookup` kernel's events.
"""

BYTES_PER_QUERY = 16


def lookup_bytes(queries: int) -> int:
    return BYTES_PER_QUERY * int(queries)


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s("hash_lookup")
    if t <= 0 or not ctx.counters.get("live_queries"):
        return None
    least = lookup_bytes(ctx.counters["live_queries"]) / ctx.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least / t
