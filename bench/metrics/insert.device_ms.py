"""Device time of the receiver insert per counting job: the ops under the
`insert` named scope (decoding the received tiles and `store_insert`,
the `hash_insert` kernel among them) in the update executable
`local_update`, per chip, over the window's jobs."""

from bench import scopes


def read(ctx):
    return scopes.per_job_scope_ms(ctx, "insert")
