"""Serial k-mer counting (paper Algorithm 1) -- the correctness oracle.

Single-device: parse reads into packed k-mers, sort, accumulate. Every other
algorithm in this package must produce exactly this histogram.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding
from repro.core.sort import AccumResult, accumulate


class KCStats(NamedTuple):
    total_kmers: jax.Array   # () int64-ish: number of k-mer instances counted


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def count_kmers_serial(reads: jax.Array, k: int, canonical: bool = False,
                       bits_per_symbol: int = 2) -> AccumResult:
    """reads: (n_reads, m) symbol codes -> AccumResult over all k-mers."""
    kmers = encoding.extract_kmers(reads, k, bits_per_symbol)
    if canonical:
        kmers = encoding.canonical(kmers, k)
    return accumulate(jnp.sort(kmers),
                      sentinel_val=int(jnp.iinfo(kmers.dtype).max))


def count_kmers_python(reads_np, k: int) -> dict:
    """Pure-Python oracle (collections.Counter) for tests; codes input."""
    from collections import Counter

    c: Counter = Counter()
    for row in reads_np:
        word = 0
        mask = (1 << (2 * k)) - 1
        for j, base in enumerate(row.tolist()):
            word = ((word << 2) | int(base)) & mask
            if j >= k - 1:
                c[word] += 1
    return dict(c)


def count_kmers_numpy(reads_np, k: int, canonical: bool = False):
    """Vectorized host oracle: (sorted unique packed words, int64 counts).

    NumPy only -- no JAX, no kernels -- so it checks the device path at
    sizes the Python loop above cannot reach (tens of millions of k-mer
    instances). Words pack first base highest, as `count_kmers_python`
    does; `canonical` keeps min(word, reverse complement) with the
    complement of code c being 3 - c.
    """
    codes = np.asarray(reads_np)
    n_pos = codes.shape[1] - k + 1
    dt = np.uint32 if 2 * k <= 30 else np.uint64
    fwd = np.zeros((codes.shape[0], n_pos), dt)
    rc = np.zeros_like(fwd)
    for j in range(k):
        base = codes[:, j:j + n_pos].astype(dt)
        fwd = (fwd << dt(2)) | base
        if canonical:
            rc |= (dt(3) - base) << dt(2 * j)
    words = np.minimum(fwd, rc) if canonical else fwd
    uniq, counts = np.unique(words.ravel(), return_counts=True)
    return uniq, counts.astype(np.int64)
