"""The benchmark's reference, traffic arithmetic, peaks and byte counts."""

import collections
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from bench import data, workload

BENCH = Path(__file__).resolve().parent
SMALL = {"genome_bases": 2048, "n_reads": 96, "read_len": 40}


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _python_counts(reads, k, canonical):
    """Loop-by-loop oracle of the NumPy reference."""
    c = collections.Counter()
    for row in reads.tolist():
        for i in range(len(row) - k + 1):
            w = 0
            for b in row[i:i + k]:
                w = (w << 2) | b
            if canonical:
                rc = 0
                for b in reversed(row[i:i + k]):
                    rc = (rc << 2) | (3 - b)
                w = min(w, rc)
            c[w] += 1
    return c


@pytest.mark.parametrize("k", [5, 11, 15])
@pytest.mark.parametrize("canonical", [False, True])
def test_reference_counts_match_a_python_loop(k, canonical):
    gen = data.genome(SMALL, 7)
    reads = data.sample_reads(gen, 64, 30, 0.02, data.rng(7, data.READS))
    uniq, counts = data.count_kmers(reads, k, canonical)
    assert dict(zip(uniq.tolist(), counts.tolist())) == _python_counts(
        reads, k, canonical)


def test_reference_blocks_merge_exactly(monkeypatch):
    gen = data.genome(SMALL, 3)
    reads = data.sample_reads(gen, 100, 40, 0.0, data.rng(3, data.READS))
    whole = data.count_kmers(reads, 15, True)
    monkeypatch.setattr(data, "_BLOCK_READS", 7)
    blocked = data.count_kmers(reads, 15, True)
    assert all(np.array_equal(a, b) for a, b in zip(whole, blocked))


def test_same_seed_same_data_and_other_seed_other_data():
    a = data.sample_reads(data.genome(SMALL, 2**31 + 9), 10, 40, 0.01,
                          data.rng(2**31 + 9, data.READS))
    b = data.sample_reads(data.genome(SMALL, 2**31 + 9), 10, 40, 0.01,
                          data.rng(2**31 + 9, data.READS))
    c = data.sample_reads(data.genome(SMALL, 5), 10, 40, 0.01,
                          data.rng(5, data.READS))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_reads_carry_substitutions_at_the_error_rate():
    gen = data.genome(SMALL, 1)
    clean = data.sample_reads(gen, 2000, 150, 0.0, data.rng(1, data.READS))
    noisy = data.sample_reads(gen, 2000, 150, 0.01, data.rng(1, data.READS))
    assert noisy.dtype == np.uint8 and noisy.max() <= 3
    # the same offsets; each substitution changes the base
    assert 0.009 <= np.mean(noisy != clean) <= 0.011


def test_revcomp_is_an_involution_and_canonical_is_strand_free():
    w = np.random.default_rng(0).integers(0, 1 << 30, 1000).astype(np.uint32)
    assert np.array_equal(data.revcomp(data.revcomp(w, 15), 15), w)
    assert np.array_equal(data.canonical(w, 15),
                          data.canonical(data.revcomp(w, 15), 15))


def test_lookup_answers_counts_and_misses():
    hist = (np.array([3, 9, 20], np.uint32), np.array([2, 5, 1], np.int64))
    got = data.lookup(hist, np.array([9, 4, 20, 3, 100], np.uint32), 15,
                      False)
    assert got.tolist() == [5, 0, 1, 2, 0]


@pytest.mark.parametrize("got,want,n", [
    (([1, 2, 3], [1, 1, 1]), ([1, 2, 3], [1, 1, 1]), 0),
    (([1, 2, 3], [1, 2, 1]), ([1, 2, 3], [1, 1, 1]), 1),
    (([1, 3], [1, 1]), ([1, 2, 3], [1, 1, 1]), 1),
    (([1, 2, 3, 4], [1, 1, 1, 1]), ([1, 2, 3], [1, 1, 1]), 1),
    (([1, 2, 2, 3], [1, 1, 1, 1]), ([1, 2, 3], [1, 2, 1]), 2),
    (([], []), ([1], [1]), 1),
])
def test_histogram_mismatch_counts_every_disagreement(got, want, n):
    as_np = lambda h: (np.asarray(h[0], np.uint32),  # noqa: E731
                       np.asarray(h[1], np.int64))
    assert data.histogram_mismatch(as_np(got), as_np(want)) == n


def test_sketch_control_breaks_exactness():
    gen = data.genome(dict(SMALL, genome_bases=1 << 14), 4)
    reads = data.sample_reads(gen, 2000, 60, 0.0, data.rng(4, data.READS))
    hist = data.count_kmers(reads, 15, True)
    # ~2 counters per distinct k-mer, as 2**24 give the cells' ~9.5 M
    bits = int(np.ceil(np.log2(2 * hist[0].size)))
    sk = data.Sketch(hist, bits)
    assert data.histogram_mismatch(
        (hist[0], sk.counts(hist[0], 15, False)), hist) > 0
    assert np.all(sk.counts(hist[0], 15, False) >= hist[1])


def test_arrivals_offer_the_same_gaps_to_every_seed():
    a = workload.arrivals(500.0, 4.0, 1)
    b = workload.arrivals(500.0, 4.0, 2**31 + 17)
    assert a.size == b.size == 2000
    assert np.all(np.diff(a) >= 0) and a[0] == 0.0
    ga = np.sort(np.diff(a))
    gb = np.sort(np.diff(b))
    assert not np.array_equal(np.diff(a), np.diff(b))
    # the same multiset of gaps, bar the one each order puts first
    assert abs(ga.sum() - gb.sum()) < ga.max()
    assert 3.8 < a[-1] < 4.2


@pytest.mark.parametrize("q,want", [(50, 5.0), (99, 10.0), (100, 10.0),
                                    (10, 1.0)])
def test_percentile_is_nearest_rank(q, want):
    lat = np.arange(1.0, 11.0)
    assert workload.percentile(lat, q) == want


def test_failed_requests_miss_the_tail():
    lat = np.concatenate([np.ones(98), [np.inf, np.inf]])
    assert workload.percentile(lat, 50) == 1.0
    assert workload.percentile(lat, 99) == np.inf


def test_open_loop_times_from_due_and_counts_failures():
    class Slow:
        """A system whose every flush takes 50 ms; request 3 fails."""

        def __init__(self):
            self.pending = []
            self.seen = 0

        def submit(self, reqs):
            self.pending += reqs

        def flush(self):
            import time
            time.sleep(0.05)
            out = []
            for r in self.pending:
                out.append(RuntimeError("refused") if self.seen == 3
                           else r * 0)
                self.seen += 1
            self.pending = []
            return out

    due = np.array([0.0, 0.01, 0.02, 0.2, 0.21])
    reqs = np.ones((5, 4), np.uint32)
    out = workload.run_serve(Slow(), reqs, due, max_requests=2)
    lat = out["lat"]
    assert out["failed"].tolist() == [False, False, False, True, False]
    assert np.isinf(lat[3])
    # request 2 waited for request 0's flush, then its own: from its due
    # time, not from when the loop got to it
    assert lat[2] >= 0.05 + 0.05 - 0.02 - 1e-3
    assert out["sizes"].max() <= 2
    assert all(lat[i] >= 0.05 - 1e-3 for i in (0, 1, 2, 4))


def test_instances_of_a_job():
    assert workload.instances({"n_reads": 204800, "read_len": 150,
                               "k": 15}) == 27_852_800


def test_insert_bytes_are_24_per_item():
    assert _metric("hash_insert_roofline").insert_bytes(1000) == 24_000


def test_lookup_bytes_are_16_per_query():
    assert _metric("hash_lookup_roofline").lookup_bytes(136) == 2176


def test_peaks_table_knows_v5e_and_refuses_others():
    from bench import run
    p = run.load_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        run.load_peaks("TPU v9 imaginary")
    assert "Google Cloud" in json.loads(
        (BENCH / "peaks.json").read_text())["source"]


def test_roofline_readers_return_nothing_without_a_trace():
    from types import SimpleNamespace
    ctx = SimpleNamespace(trace=None, counters={}, peaks={})
    for f in (BENCH / "metrics").glob("*.py"):
        assert _metric(f.stem).read(ctx) is None, f.stem
