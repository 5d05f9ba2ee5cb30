"""Device time of L3 compression per counting job: the ops under the `l3`
named scope (each chunk's local radix sort and accumulate, split into
NORMAL and HEAVY lanes) in the update executable `local_update`, per chip,
over the window's jobs."""

from bench import scopes


def read(ctx):
    return scopes.per_job_scope_ms(ctx, "l3")
