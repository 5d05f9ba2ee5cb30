"""The benchmark's own data and plain reference: reads, queries and counts.

Everything here is NumPy and imports nothing of the program under test,
so the reference that decides `correct` shares no code with what it
checks. Reads are 2-bit codes (A=0, C=1, G=2, T=3); a k-mer packs its first
base highest, two bits a base, into a uint32 word (k <= 15); a canonical
k-mer is min(word, reverse complement) with the complement of c being 3-c.

Every array is made from the run's `--seed` through `np.random.SeedSequence`
streams, so one seed gives the same reads and queries on every machine.
"""

from __future__ import annotations

import numpy as np

# Substreams of one seed: genome, reads, query reads, arrival order.
GENOME, READS, QUERIES, ARRIVALS = range(4)

# Rows of reads the reference packs at once: bounds its host memory at a
# few hundred MiB whatever the read count.
_BLOCK_READS = 1 << 16


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one substream of `seed` (any non-negative int)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def genome(cfg: dict, seed: int) -> np.ndarray:
    """Uniform random genome of `genome_bases` codes."""
    return rng(seed, GENOME).integers(0, 4, size=cfg["genome_bases"],
                                      dtype=np.uint8)


def sample_reads(gen: np.ndarray, n_reads: int, read_len: int,
                 error_rate: float, g: np.random.Generator) -> np.ndarray:
    """(n_reads, read_len) uint8 codes at uniform offsets, each base
    substituted with probability `error_rate`."""
    starts = g.integers(0, gen.size - read_len + 1, size=n_reads)
    reads = gen[starts[:, None] + np.arange(read_len)[None, :]]
    if error_rate > 0:
        flips = g.random(reads.shape) < error_rate
        shift = g.integers(1, 4, reads.shape, dtype=np.uint8)
        reads = np.where(flips, (reads + shift) % 4, reads).astype(np.uint8)
    return reads


def forward_words(reads: np.ndarray, k: int) -> np.ndarray:
    """(n, read_len - k + 1) uint32 forward-strand k-mer words."""
    n_pos = reads.shape[1] - k + 1
    words = np.zeros((reads.shape[0], n_pos), np.uint32)
    for j in range(k):
        words = (words << np.uint32(2)) | reads[:, j:j + n_pos]
    return words


def revcomp(words: np.ndarray, k: int) -> np.ndarray:
    """Reverse complement of packed k-mer words."""
    out = np.zeros_like(words)
    w = words.copy()
    three = words.dtype.type(3)
    two = words.dtype.type(2)
    for _ in range(k):
        out = (out << two) | (three - (w & three))
        w = w >> two
    return out


def canonical(words: np.ndarray, k: int) -> np.ndarray:
    return np.minimum(words, revcomp(words, k))


def count_kmers(reads: np.ndarray, k: int, canonical_: bool):
    """Reference histogram: (sorted unique words, int64 counts), packed in
    blocks of reads and merged."""
    parts = []
    for lo in range(0, reads.shape[0], _BLOCK_READS):
        w = forward_words(reads[lo:lo + _BLOCK_READS], k).ravel()
        parts.append(canonical(w, k) if canonical_ else w)
    uniq, counts = np.unique(np.concatenate(parts), return_counts=True)
    return uniq, counts.astype(np.int64)


def lookup(hist, words: np.ndarray, k: int, canonical_: bool) -> np.ndarray:
    """Reference answer for each query word: the count of its (canonical)
    k-mer in `hist`, 0 where absent."""
    uniq, counts = hist
    q = canonical(words, k) if canonical_ else words
    i = np.clip(np.searchsorted(uniq, q), 0, uniq.size - 1)
    return np.where(uniq[i] == q, counts[i], 0).astype(np.int64)


def histogram_mismatch(got, want) -> int:
    """Entries on which two histograms disagree: k-mers of either side that
    are missing from the other, plus shared k-mers whose counts differ."""
    gu, gc = got
    wu, wc = want
    if gu.size and np.any(gu[1:] <= gu[:-1]):
        # a k-mer reported twice (e.g. by two owners) is a fault in itself
        order = np.argsort(gu, kind="stable")
        gu, gc = gu[order], gc[order]
        dup = int(np.count_nonzero(gu[1:] == gu[:-1]))
        keep = np.concatenate([[True], gu[1:] != gu[:-1]])
        return dup + histogram_mismatch((gu[keep], gc[keep]), want)
    shared, gi, wi = np.intersect1d(gu, wu, assume_unique=True,
                                    return_indices=True)
    only = (gu.size - shared.size) + (wu.size - shared.size)
    return int(only + np.count_nonzero(gc[gi] != wc[wi]))


class Sketch:
    """The control's store: a one-row count-min sketch of 2**bits counters,
    i.e. the exact store with each key replaced by a `bits`-bit hash. A
    query answers the total of every k-mer that shares its counter, so
    distinct k-mers that collide merge and misses can read as hits."""

    def __init__(self, hist, bits: int):
        uniq, counts = hist
        self.bits = bits
        self.table = np.bincount(_fingerprint(uniq, bits), weights=counts,
                                 minlength=1 << bits).astype(np.int64)

    def counts(self, words: np.ndarray, k: int,
               canonical_: bool) -> np.ndarray:
        q = canonical(words, k) if canonical_ else words
        return self.table[_fingerprint(q, self.bits)]


def _fingerprint(words: np.ndarray, bits: int) -> np.ndarray:
    """`bits`-bit multiplicative hash of uint32 words (Knuth's constant)."""
    h = words.astype(np.uint64) * np.uint64(0x9E3779B1)
    return ((h & np.uint64(0xFFFFFFFF)) >> np.uint64(32 - bits)).astype(
        np.int64)
