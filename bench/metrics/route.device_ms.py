"""Device time of the owner route per counting job: the ops under the
`route` named scope (owners, partition plan, scatter into tiles and the
exchange) in the update executable `local_update`, per chip, over the
window's jobs. The query executable's `route` scope is not counted."""

from bench import scopes


def read(ctx):
    return scopes.per_job_scope_ms(ctx, "route")
