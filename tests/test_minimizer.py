"""Minimizer-routed super-k-mer transport (ISSUE 4 acceptance).

- Property suite (hypothesis / the deterministic shim): every length-w
  window selects the true minimum; super-k-mer segmentation covers every
  k-mer of every read exactly once -- duplicates, read boundaries, repeated
  minimizer values (capped runs) and reverse-complement canonicalization
  included.
- Canonical orientation: minimizer values are strand-invariant, so a read
  and its reverse complement route every k-mer to the same owner.
- End-to-end: `transport_impl='superkmer'` == the `'kmer'` oracle == the
  serial count across {1d, 2d} x {packed, dual} x {stream, stacked} and at
  k=31/uint64 (subprocess, x64), with measurably fewer wire bytes.
- The default superkmer path lowers with zero HLO sort ops.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh

from repro.core import encoding, fabsp, minimizer, serial
from repro.data import genome
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def reads():
    spec = genome.ReadSetSpec(genome_bases=2048, n_reads=128, read_len=60,
                              heavy_hitter_frac=0.3, seed=17)
    return jnp.asarray(genome.sample_reads(spec))


@pytest.fixture(scope="module")
def mesh1d():
    return Mesh(np.array(jax.devices()[:1]), ("pe",))


@pytest.fixture(scope="module")
def mesh2d():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("row", "col"))


def _merge(res):
    out = {}
    nsh = res.num_unique.shape[0]
    L = res.unique.shape[0] // nsh
    u = np.asarray(res.unique).reshape(nsh, L)
    c = np.asarray(res.counts).reshape(nsh, L)
    nu = np.asarray(res.num_unique)
    for s in range(nsh):
        for i in range(nu[s]):
            out[int(u[s, i])] = int(c[s, i])
    return out


def _decode_histogram(reads_arr, k, m, canonical=False):
    """Segment + re-extract on one device: the transport round-trip."""
    sk = minimizer.segment_superkmers(reads_arr, k, m, canonical=canonical)
    kmers, counts = minimizer.superkmer_to_kmers(sk.words, sk.lengths, k, m,
                                                 canonical=canonical)
    out = {}
    for x, c in zip(np.asarray(kmers), np.asarray(counts)):
        if c:
            out[int(x)] = out.get(int(x), 0) + int(c)
    return out, sk


def _serial_dict(reads_arr, k, canonical=False):
    ser = serial.count_kmers_serial(reads_arr, k, canonical=canonical)
    n = int(ser.num_unique)
    return {int(u): int(c) for u, c in zip(ser.unique[:n], ser.counts[:n])}


# --- property: sliding-window minimum ----------------------------------------


@settings(max_examples=25, deadline=None)
@given(n_pos=st.integers(4, 700), window=st.integers(1, 48),
       seed=st.integers(0, 10_000))
def test_sliding_min_selects_true_window_minimum(n_pos, window, seed):
    window = min(window, n_pos)
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.integers(0, 1 << 30, size=(3, n_pos),
                                    dtype=np.uint32))
    got = np.asarray(ops.sliding_min(vals, window))
    ref_out = np.asarray(ref.sliding_min_ref(vals, window))
    v = np.asarray(vals)
    assert got.shape == (3, n_pos - window + 1)
    for p in range(got.shape[1]):           # every window: the true minimum
        true = v[:, p:p + window].min(axis=1)
        assert (got[:, p] == true).all()
    assert (got == ref_out).all()


def test_sliding_min_kernel_matches_ref_across_tilings():
    rng = np.random.default_rng(7)
    for (rows, n, w, tile) in [(8, 96, 5, 16), (8, 1030, 11, 512),
                               (1, 50, 50, 8), (16, 257, 31, 32)]:
        vals = jnp.asarray(rng.integers(0, 1 << 30, size=(rows, n),
                                        dtype=np.uint32))
        from repro.kernels.minimizer import sliding_min_pallas
        got = sliding_min_pallas(vals, w, block_rows=1, tile=tile,
                                 interpret=True)
        assert (np.asarray(got)
                == np.asarray(ref.sliding_min_ref(vals, w))).all()


# --- property: segmentation covers every k-mer exactly once ------------------


@settings(max_examples=12, deadline=None)
@given(k=st.integers(5, 15), m=st.integers(3, 9),
       heavy=st.booleans(), seed=st.integers(0, 1000))
def test_superkmers_cover_every_kmer_exactly_once(k, m, heavy, seed):
    m = min(m, k)
    spec = genome.ReadSetSpec(genome_bases=512, n_reads=24,
                              read_len=max(2 * k, 30),
                              heavy_hitter_frac=0.5 if heavy else 0.0,
                              seed=seed)
    reads_arr = jnp.asarray(genome.sample_reads(spec))
    got, sk = _decode_histogram(reads_arr, k, m)
    assert got == serial.count_kmers_python(np.asarray(reads_arr), k)
    # instance conservation: run lengths partition the k-mer positions
    lens = np.asarray(sk.lengths)
    assert int(lens.sum()) == reads_arr.shape[0] \
        * (reads_arr.shape[1] - k + 1)
    assert int(lens.max()) <= minimizer.window_size(k, m)


def test_superkmers_cover_poly_a_capped_runs():
    """A constant minimizer value (poly-A) must split at the w-k-mer cap
    instead of overflowing the fixed-width slot."""
    k, m = 13, 7
    reads_arr = jnp.zeros((4, 60), jnp.uint8)
    got, sk = _decode_histogram(reads_arr, k, m)
    assert got == serial.count_kmers_python(np.asarray(reads_arr), k)
    assert int(np.asarray(sk.lengths).max()) == minimizer.window_size(k, m)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000))
def test_superkmers_canonical_strand_invariant(seed):
    """Canonical mode: a read and its reverse complement select identical
    minimizer values per k-mer (so every k-mer copy routes to one owner)
    and decode to the same canonical histogram."""
    k, m = 13, 7
    rng = np.random.default_rng(seed)
    fwd = rng.integers(0, 4, size=(16, 50), dtype=np.uint8)
    rev = (3 - fwd)[:, ::-1].copy()
    mz_f = np.asarray(minimizer.window_minimizers(
        jnp.asarray(fwd), k, m, canonical=True))
    mz_r = np.asarray(minimizer.window_minimizers(
        jnp.asarray(rev), k, m, canonical=True))
    assert (mz_f == mz_r[:, ::-1]).all()
    hist_f, _ = _decode_histogram(jnp.asarray(fwd), k, m, canonical=True)
    hist_r, _ = _decode_histogram(jnp.asarray(rev), k, m, canonical=True)
    assert hist_f == hist_r
    assert hist_f == _serial_dict(jnp.asarray(fwd), k, canonical=True)


# --- end-to-end: superkmer == kmer oracle across the parity grid -------------


@pytest.mark.parametrize("receiver", ["stream", "stacked"])
@pytest.mark.parametrize("l3_mode", ["packed", "dual"])
@pytest.mark.parametrize("topology", ["1d", "2d"])
def test_superkmer_matches_kmer_and_serial(reads, mesh1d, mesh2d, topology,
                                           l3_mode, receiver):
    k = 9 if l3_mode == "packed" else 13
    # w = k - m + 1 must be large enough that the overlap saving beats the
    # slot+header overhead: w=5 at k=9, w=7 at k=13.
    m = 5 if k == 9 else 7
    mesh = mesh1d if topology == "1d" else mesh2d
    axes = ("pe",) if topology == "1d" else ("row", "col")
    results, stats = {}, {}
    for transport in ("kmer", "superkmer"):
        cfg = fabsp.DAKCConfig(k=k, chunk_reads=32, l3_mode=l3_mode,
                               topology=topology, receiver_impl=receiver,
                               transport_impl=transport, minimizer_len=m)
        res, st_ = fabsp.count_kmers(reads, mesh, cfg, axes)
        assert int(st_.overflow) == 0 and int(st_.store_overflow) == 0
        results[transport], stats[transport] = _merge(res), st_
    assert results["superkmer"] == results["kmer"]
    assert results["superkmer"] == _serial_dict(reads, k)
    # the point of the transport: strictly fewer wire bytes
    assert int(stats["superkmer"].wire_bytes) \
        < int(stats["kmer"].wire_bytes)


def test_superkmer_canonical_end_to_end(reads, mesh1d):
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, canonical=True,
                           transport_impl="superkmer")
    res, st_ = fabsp.count_kmers(reads, mesh1d, cfg)
    assert int(st_.overflow) == 0
    assert _merge(res) == _serial_dict(reads, 13, canonical=True)


def test_superkmer_kmer_counter_incremental(mesh1d):
    s1 = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=60,
                            seed=1)
    s2 = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=60,
                            seed=2)
    r1 = jnp.asarray(genome.sample_reads(s1))
    r2 = jnp.asarray(genome.sample_reads(s2))
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, transport_impl="superkmer")
    counter = fabsp.KmerCounter(mesh1d, cfg)
    counter.update(r1)
    counter.update(r2)
    res, _ = counter.finalize()
    res_one, _ = fabsp.count_kmers(jnp.concatenate([r1, r2]), mesh1d, cfg)
    assert _merge(res) == _merge(res_one)


def test_superkmer_k31_uint64_subprocess():
    """k=31 (uint64 words, x64): superkmer == kmer == serial, and the
    super-k-mer stream is smaller than the dual-format k-mer stream."""
    code = r"""
import os
os.environ["JAX_ENABLE_X64"] = "1"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import fabsp, serial
from repro.data import genome

spec = genome.ReadSetSpec(genome_bases=1024, n_reads=32, read_len=64, seed=9)
reads = jnp.asarray(genome.sample_reads(spec))
mesh = Mesh(np.array(jax.devices()[:1]), ("pe",))
def merge(res):
    out = {}
    nsh = res.num_unique.shape[0]; L = res.unique.shape[0] // nsh
    u = np.asarray(res.unique).reshape(nsh, L)
    c = np.asarray(res.counts).reshape(nsh, L)
    for s in range(nsh):
        for i in range(np.asarray(res.num_unique)[s]):
            out[int(u[s, i])] = int(c[s, i])
    return out
got, wire = {}, {}
for transport in ("kmer", "superkmer"):
    cfg = fabsp.DAKCConfig(k=31, chunk_reads=16, minimizer_len=15,
                           transport_impl=transport)
    res, st = fabsp.count_kmers(reads, mesh, cfg)
    assert int(st.overflow) == 0 and int(st.store_overflow) == 0
    got[transport] = merge(res)
    wire[transport] = int(st.wire_bytes)
assert got["superkmer"] == got["kmer"]
ser = serial.count_kmers_serial(reads, 31)
n = int(ser.num_unique)
oracle = {int(u): int(c) for u, c in zip(ser.unique[:n], ser.counts[:n])}
assert got["superkmer"] == oracle
assert wire["superkmer"] < wire["kmer"], wire
print("OK wire=%r" % (wire,))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


# --- config validation and lowering ------------------------------------------


def test_superkmer_config_validation():
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, transport_impl="superkmer", minimizer_len=14)
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, transport_impl="superkmer", minimizer_len=0)
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, transport_impl="superkmer", topology="2d",
                         route2d_impl="perhop")
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, transport_impl="msp")
    # perhop stays legal for the kmer transport, and superkmer+1d ignores it
    fabsp.DAKCConfig(k=13, topology="2d", route2d_impl="perhop")
    fabsp.DAKCConfig(k=13, transport_impl="superkmer",
                     route2d_impl="perhop")


@pytest.mark.parametrize("topology", ["1d", "2d"])
def test_superkmer_path_has_no_hlo_sort(mesh1d, mesh2d, topology):
    import re

    mesh = mesh1d if topology == "1d" else mesh2d
    axes = ("pe",) if topology == "1d" else ("row", "col")
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, canonical=True,
                           topology=topology, transport_impl="superkmer")
    fabsp.clear_executable_cache()
    fn = fabsp._counting_executable(cfg, mesh, axes, (64, 60), "uint8",
                                    cfg.slack, store_cap=512)
    txt = fn.lower(jax.ShapeDtypeStruct((64, 60), jnp.uint8)).as_text()
    fabsp.clear_executable_cache()
    n_sorts = len(re.findall(r"stablehlo\.sort|\bsort\(|sort\.[0-9]", txt))
    assert n_sorts == 0, f"sort op leaked into the superkmer {topology} path"


# --- wire accounting ---------------------------------------------------------


def test_superkmer_wire_bytes_exact(reads, mesh1d):
    """wire_bytes counts the packed super-k-mer stream exactly: slots *
    (payload words + the int32 length header) summed over chunks."""
    k, m = 13, 7
    cfg = fabsp.DAKCConfig(k=k, chunk_reads=32, minimizer_len=m,
                           transport_impl="superkmer")
    _, st_ = fabsp.count_kmers(reads, mesh1d, cfg)
    mode, cap_sk, _ = fabsp._plan_caps(cfg, 1, tuple(reads.shape), cfg.slack)
    assert mode == "superkmer"
    n_chunks = reads.shape[0] // 32
    assert int(st_.wire_bytes) == n_chunks * cap_sk \
        * minimizer.slot_bytes(k, m)
