"""Device time of the extraction layer per counting job: the ops under the
`extract` named scope (parsing the reads and extracting canonical k-mers)
in the update executable `local_update`, per chip, over the window's jobs.
"""

from bench import scopes


def read(ctx):
    return scopes.per_job_scope_ms(ctx, "extract")
