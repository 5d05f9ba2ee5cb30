"""Device time of the all-to-all exchange per counting job: the ops under
the `exchange` named scope (each route's `all_to_all` and the layout ops
XLA makes for it, nested inside `route`) in the update executable
`local_update`, per chip, over the window's jobs. On one chip the
one-participant collective compiles to nothing, and this reads nothing."""

from bench import scopes


def read(ctx):
    return scopes.per_job_scope_ms(ctx, "exchange")
