"""One persistent JAX compilation cache per checkout, for the entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
here overrides it. Otherwise `enable()` points the cache at `.jax_cache/`
at the root of the checkout (listed in .gitignore): a fixed path, because
the cache directory is part of what a cached entry is found by, so a temp-,
pid- or time-based directory would never hit.

Only entry points call `enable()` (chip_smoke.py, `kc_serve`,
`benchmarks/run.py`); importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
