"""The one `shard_map` entry point of the SPMD drivers.

Every driver calls `shard_map(f, mesh=..., in_specs=..., out_specs=...)`;
varying-manual-axes checking is always disabled (the k-mer drivers return
unreduced per-shard results on purpose).
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
