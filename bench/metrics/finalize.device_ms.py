"""Device time of the finalize executable per counting job: the runs of the
module named after `KmerCounter.finalize`'s jitted body (`local_finalize`)
in the traced window, divided by the jobs that ran there."""

MODULE = "local_finalize"


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("jobs"):
        return None
    calls = ctx.trace.module_count(MODULE)
    if calls == 0:
        return None
    per_device = ctx.trace.module_time_s(MODULE) / ctx.trace.n_devices
    return 1e3 * per_device / ctx.counters["jobs"]
