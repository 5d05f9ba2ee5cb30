"""The owner partition's and L3's plan kernel's share of its HBM roofline.

`make_partition_plan` runs twice per plan: a histogram pass that reads a
tile of bucket ids and writes the per-tile histogram rows, then a
positions pass that reads the ids and the histogram rows and writes each
item's position. Its bytes are the operands read and the results written
by each call, counted from the shapes in the call's HLO text in the trace
(tiles as the kernel lays them out, padding included). The kernel does no
arithmetic to speak of, so HBM bandwidth bounds it. Time is the summed
device time of its events.
"""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s("make_partition_plan")
    if t <= 0:
        return None
    least = ctx.trace.kernel_io_bytes("make_partition_plan") / ctx.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least / t
