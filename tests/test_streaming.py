"""Streaming receiver acceptance (ISSUE 3).

- `receiver_impl='stream'` == `'stacked'` oracle as sorted (kmer, count)
  sets across {1d, 2d} x {packed, dual} x {canonical on/off}, and both
  match the serial oracle.
- The stream path's traced receive buffer does NOT scale with n_chunks
  (jaxpr aval accounting); the stacked oracle's does (sanity).
- Incremental API: two KmerCounter.update() batches == one concatenated
  count_kmers call; store growth (rehash rounds) preserves counts.
- Overflow rounds: adversarial skew (L3 off) triggers slack doubling on a
  real 8-PE mesh, returns exact counts, and repeats hit the executable
  cache; an undersized count store triggers capacity-doubling rehash
  rounds with the same cache discipline.
- Wire accounting: the int32-pair wire_bytes is exact and equal across
  receiver impls.
"""

import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import encoding, fabsp, serial
from repro.data import genome


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


def _serial_dict(reads, k, canonical=False):
    raw = serial.count_kmers_python(np.asarray(reads), k)
    if not canonical:
        return raw
    out = {}
    for km, c in raw.items():
        can = int(encoding.canonical(jnp.asarray([km], jnp.uint32), k)[0])
        out[can] = out.get(can, 0) + c
    return out


# --- stream == stacked across the full wire-format / topology grid ----------


@pytest.mark.parametrize("canonical", [False, True])
@pytest.mark.parametrize("l3_mode", ["packed", "dual"])
@pytest.mark.parametrize("topology", ["1d", "2d"])
def test_stream_matches_stacked_and_serial(reads, mesh1d, mesh2d, topology,
                                           l3_mode, canonical):
    k = 9 if l3_mode == "packed" else 13
    mesh = mesh1d if topology == "1d" else mesh2d
    axes = ("pe",) if topology == "1d" else ("row", "col")
    results, stats = {}, {}
    for recv in ("stream", "stacked"):
        cfg = fabsp.DAKCConfig(k=k, chunk_reads=32, l3_mode=l3_mode,
                               topology=topology, canonical=canonical,
                               receiver_impl=recv)
        res, st = fabsp.count_kmers(reads, mesh, cfg, axes)
        assert int(st.overflow) == 0 and int(st.store_overflow) == 0
        results[recv], stats[recv] = _merge(res), st
    assert results["stream"] == results["stacked"]
    assert results["stream"] == _serial_dict(reads, k, canonical)
    # identical routing => identical wire accounting, exactly
    assert int(stats["stream"].sent_words) == int(stats["stacked"].sent_words)
    assert int(stats["stream"].wire_bytes) == int(stats["stacked"].wire_bytes)


# --- receive buffer does not scale with n_chunks (jaxpr accounting) ----------


def _iter_avals(params_or_jaxpr, out):
    eqns = getattr(params_or_jaxpr, "eqns", None)
    if eqns is None:
        return
    for eqn in eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(aval)
        for p in eqn.params.values():
            for sub in _subjaxprs(p):
                _iter_avals(sub, out)


def _subjaxprs(p):
    if hasattr(p, "jaxpr"):           # ClosedJaxpr
        yield p.jaxpr
    elif hasattr(p, "eqns"):          # Jaxpr
        yield p
    elif isinstance(p, (list, tuple)):
        for x in p:
            yield from _subjaxprs(x)


def _max_word_aval_elems(cfg, mesh, n_reads):
    """Largest uint32 (k-mer word) intermediate in the traced count path."""
    fabsp.clear_executable_cache()
    fn = fabsp._counting_executable(cfg, mesh, ("pe",), (n_reads, 44),
                                    "uint8", cfg.slack)
    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((n_reads, 44), jnp.uint8))
    avals = []
    _iter_avals(jaxpr.jaxpr, avals)
    fabsp.clear_executable_cache()
    elems = [int(np.prod(a.shape)) for a in avals
             if getattr(a, "dtype", None) == jnp.uint32 and a.shape]
    assert elems, "no word-dtype intermediates found"
    return max(elems)


def test_stream_receive_buffer_independent_of_n_chunks(mesh1d):
    base = dict(k=13, chunk_reads=32, use_l3=False, store_capacity=2048)
    small, big = 128, 512                    # 4 vs 16 chunks
    stream = fabsp.DAKCConfig(receiver_impl="stream", **base)
    stacked = fabsp.DAKCConfig(receiver_impl="stacked", **base)
    s_small = _max_word_aval_elems(stream, mesh1d, small)
    s_big = _max_word_aval_elems(stream, mesh1d, big)
    k_small = _max_word_aval_elems(stacked, mesh1d, small)
    k_big = _max_word_aval_elems(stacked, mesh1d, big)
    # stacked receive buffer stacks per chunk: grows with the chunk count
    assert k_big >= 2 * k_small
    # stream receive memory is the store + one in-flight tile: flat
    assert s_big == s_small
    assert s_small < k_small


# --- incremental API ---------------------------------------------------------


def test_kmer_counter_two_updates_equal_one_call(mesh1d):
    s1 = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=60,
                            seed=1)
    s2 = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=60,
                            seed=2)
    r1 = jnp.asarray(genome.sample_reads(s1))
    r2 = jnp.asarray(genome.sample_reads(s2))
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, l3_mode="dual")
    counter = fabsp.KmerCounter(mesh1d, cfg)
    counter.update(r1)
    counter.update(r2)
    res, agg = counter.finalize()
    res_one, st_one = fabsp.count_kmers(jnp.concatenate([r1, r2]), mesh1d,
                                        cfg)
    assert _merge(res) == _merge(res_one)
    assert int(agg.raw_kmers) == int(st_one.raw_kmers)
    assert int(agg.sent_words) == int(st_one.sent_words)
    assert int(agg.wire_bytes) == int(st_one.wire_bytes)


def test_insert_group_counts_reach_the_stats(reads, mesh1d):
    """The insert's group counts ride the scan to `DAKCStats`: a 4,096-slot
    store (32 rows) takes the grouped path, every item that reaches it lies
    in a counted group, the one-call and incremental paths agree, and the
    stacked receiver, which inserts nothing, counts none."""
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, use_l3=False,
                           store_capacity=4096)
    _, st = fabsp.count_kmers(reads, mesh1d, cfg)
    groups, fallbacks = int(st.insert_groups), int(st.insert_fallbacks)
    sent = int(st.sent_words)
    assert sent / 8 <= groups <= sent and 0 < fallbacks < groups
    up = fabsp.KmerCounter(mesh1d, cfg).update(reads)
    assert (int(up.insert_groups), int(up.insert_fallbacks)) == (
        groups, fallbacks)
    _, stacked = fabsp.count_kmers(
        reads, mesh1d, dataclasses.replace(cfg, receiver_impl="stacked"))
    assert int(stacked.insert_groups) == int(stacked.insert_fallbacks) == 0


def test_kmer_counter_grows_undersized_store(mesh1d):
    spec = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=60,
                              seed=3)
    r = jnp.asarray(genome.sample_reads(spec))
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, use_l3=False,
                           store_capacity=64)
    counter = fabsp.KmerCounter(mesh1d, cfg)
    counter.update(r)
    assert counter.store_capacity > 64          # rehash rounds fired
    res, _ = counter.finalize()
    assert _merge(res) == _serial_dict(r, 13)
    # the store keeps accepting updates after finalize
    counter.update(r)
    res2, _ = counter.finalize()
    assert _merge(res2) == {k: 2 * v for k, v in _serial_dict(r, 13).items()}


def test_kmer_counter_requires_stream():
    with pytest.raises(ValueError):
        fabsp.KmerCounter(Mesh(np.array(jax.devices()[:1]), ("pe",)),
                          fabsp.DAKCConfig(k=13, receiver_impl="stacked"))


def test_degenerate_store_sizing_rejected():
    """A 0-slot store would make the capacity-doubling rehash a no-op loop;
    the config rejects it (and non-positive store slack) up front."""
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, store_capacity=0)
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, store_slack=0.0)
    fabsp.DAKCConfig(k=13, store_capacity=1)    # minimal but legal


# --- two-pass store sizing ----------------------------------------------------


def test_sampled_store_sizing_tracks_distinct_not_instances(mesh1d):
    """Deep coverage of a SMALL genome: the distinct set saturates, so the
    two-pass sample estimate must size the store far below the
    instance-count bound -- and still count exactly (a rehash round absorbs
    any under-estimate)."""
    spec = genome.ReadSetSpec(genome_bases=512, n_reads=512, read_len=60,
                              seed=21)                  # ~60x coverage
    reads = jnp.asarray(genome.sample_reads(spec))
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32)        # sizing='sample'
    sampled = fabsp._resolve_store_capacity(reads, cfg, 1)
    bound = fabsp._default_store_capacity(cfg, tuple(reads.shape), 1)
    assert sampled < bound // 4, (sampled, bound)
    # quantized (power of two) so near-identical batches share one
    # executable-cache entry despite the data-dependent estimate
    assert sampled & (sampled - 1) == 0
    # the saturated estimate still covers the true distinct count
    res, stats = fabsp.count_kmers(reads, mesh1d, cfg)
    assert int(stats.store_overflow) == 0
    assert _merge(res) == _serial_dict(reads, 13)


def test_sampled_store_sizing_override_and_oracle():
    """Explicit store_capacity wins over sampling; store_sizing='bound'
    restores the shape-only instance bound; unknown values are rejected."""
    spec = genome.ReadSetSpec(genome_bases=512, n_reads=128, read_len=60,
                              seed=22)
    reads = jnp.asarray(genome.sample_reads(spec))
    cfg_pin = fabsp.DAKCConfig(k=13, chunk_reads=32, store_capacity=777)
    assert fabsp._resolve_store_capacity(reads, cfg_pin, 1) == 777
    cfg_bound = fabsp.DAKCConfig(k=13, chunk_reads=32, store_sizing="bound")
    assert fabsp._resolve_store_capacity(reads, cfg_bound, 1) \
        == fabsp._default_store_capacity(cfg_bound, tuple(reads.shape), 1)
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, store_sizing="guess")


def test_sampled_store_sizing_fully_distinct_sample_falls_back():
    """A sample with no duplicate k-mers carries no saturation information:
    the estimator must fall back to the instance-count bound rather than
    extrapolate from nothing."""
    n_reads, read_len, k = 64, 24, 11
    rng = np.random.default_rng(23)
    while True:                       # draw until the sample is all-distinct
        reads = rng.integers(0, 4, (n_reads, read_len), dtype=np.uint8)
        words = np.asarray(encoding.extract_kmers(jnp.asarray(reads[:32]),
                                                  k))
        if np.unique(words).size == words.size:
            break
    cfg = fabsp.DAKCConfig(k=k, chunk_reads=32)
    got = fabsp._resolve_store_capacity(jnp.asarray(reads), cfg, 1)
    assert got == fabsp._default_store_capacity(cfg, reads.shape, 1)


# --- overflow rounds: store rehash + executable cache ------------------------


def test_store_rehash_round_exact_and_cached(mesh1d):
    """An undersized store must double (rehash rounds) until the batch fits,
    deliver exact counts, and a repeat call must re-trace nothing."""
    spec = genome.ReadSetSpec(genome_bases=512, n_reads=64, read_len=52,
                              seed=5)
    reads = jnp.asarray(genome.sample_reads(spec))
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, use_l3=False,
                           store_capacity=64)
    traces = [0]
    orig = fabsp._local_count

    def counting(*args, **kwargs):
        traces[0] += 1
        return orig(*args, **kwargs)

    fabsp.clear_executable_cache()
    fabsp._local_count = counting
    try:
        res, stats = fabsp.count_kmers(reads, mesh1d, cfg)
        assert _merge(res) == _serial_dict(reads, 13)
        assert int(stats.store_overflow) == 0   # final round fits
        rounds = traces[0]
        assert rounds >= 2, "undersized store should have forced a rehash"
        res2, _ = fabsp.count_kmers(reads, mesh1d, cfg)
        assert traces[0] == rounds, "rehash-round shapes re-traced"
        assert _merge(res2) == _serial_dict(reads, 13)
    finally:
        fabsp._local_count = orig
        fabsp.clear_executable_cache()


def test_route_overflow_slack_doubling_8pe_subprocess():
    """Adversarial skew (all-A reads, L3 off) on a REAL 8-PE mesh: every
    k-mer hashes to one owner, so per-destination capacity overflows at
    slack 1.01; the overflow round must double slack until counts are
    exact, and a repeat call must hit the executable cache."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import fabsp, serial

reads = np.zeros((128, 40), dtype=np.uint8)   # all-A: one k-mer repeated
mesh = Mesh(np.array(jax.devices()), ("pe",))
cfg = fabsp.DAKCConfig(k=13, chunk_reads=16, use_l3=False, slack=1.01)
traces = [0]
orig = fabsp._local_count
def counting(*a, **k):
    traces[0] += 1
    return orig(*a, **k)
fabsp._local_count = counting
res, stats = fabsp.count_kmers(jnp.asarray(reads), mesh, cfg)
rounds = traces[0]
assert rounds >= 2, f"skew did not trigger the overflow round ({rounds})"
assert int(stats.overflow) == 0
got = {}
nsh = res.num_unique.shape[0]; L = res.unique.shape[0] // nsh
u = np.asarray(res.unique).reshape(nsh, L)
c = np.asarray(res.counts).reshape(nsh, L)
for s in range(nsh):
    for i in range(np.asarray(res.num_unique)[s]):
        got[int(u[s, i])] = int(c[s, i])
assert got == serial.count_kmers_python(reads, 13), "wrong counts after retry"
fabsp.count_kmers(jnp.asarray(reads), mesh, cfg)
assert traces[0] == rounds, "overflow-round shapes re-traced on repeat"
print("OK rounds=%d" % rounds)
"""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_stream_k31_uint64_subprocess():
    """The paper's k=31 regime (uint64 words, 'dual' wire format, x64 mode):
    stream == stacked == the raw-word oracle. Fresh process for x64."""
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
got = {}
for recv in ("stream", "stacked"):
    cfg = fabsp.DAKCConfig(k=31, chunk_reads=16, receiver_impl=recv)
    res, st = fabsp.count_kmers(reads, mesh, cfg)
    assert int(st.overflow) == 0 and int(st.store_overflow) == 0
    got[recv] = merge(res)
assert got["stream"] == got["stacked"]
ser = serial.count_kmers_serial(reads, 31)
n = int(ser.num_unique)
oracle = {int(u): int(c) for u, c in zip(ser.unique[:n], ser.counts[:n])}
assert got["stream"] == oracle
print("OK distinct=%d" % len(oracle))
"""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


# --- wire accounting ---------------------------------------------------------


def test_wire_bytes_exact_int(reads, mesh1d):
    """wire_bytes is an exact integer: n identical chunks move exactly n
    times one chunk's padded bytes (the float32 accumulator lost this past
    ~2**24 bytes)."""
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, use_l3=False)
    _, st = fabsp.count_kmers(reads, mesh1d, cfg)
    n_chunks = reads.shape[0] // 32
    mode, cap_n, _ = fabsp._plan_caps(cfg, 1, tuple(reads.shape), cfg.slack)
    assert mode == "none"
    word_b = jnp.iinfo(encoding.kmer_dtype(13)).bits // 8
    assert int(st.wire_bytes) == n_chunks * cap_n * word_b
    assert isinstance(int(st.wire_bytes), int)
