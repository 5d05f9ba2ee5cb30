"""Routing conformance suite (ISSUE 5): one lane-list engine for every
transport.

- Property tests (hypothesis / the deterministic shim) on
  `aggregation.route_tiles`, the pre-collective stage every route shares:
  for arbitrary lane sets and owner maps, every destination row holds
  exactly the stream-order prefix of its owner's valid elements, zipped
  lane tuples survive the bucketing, radix == argsort bit-identically, and
  conservation (routed + dropped == valid) holds.
- Compact hop-2 slicing: `route_lanes` with `hop2_capacity` forwards
  exactly each bucket row's first hop2_capacity slots (lanes stay aligned)
  and charges the hop-1 fill histogram for the slice, sender-side.
- Parity grid {1d, 2d} x {kmer, superkmer} x {stream, stacked} x
  {compact, padded}: histograms identical to the serial oracle everywhere
  (the pre-refactor semantics), wire bytes equal across receivers, and the
  compact hop 2 never moves more bytes than the padded oracle.
- Exact per-lane wire-byte model: `DAKCStats.wire_bytes` ==
  caps x per-slot lane widths for every wire format and topology -- the
  single-source-of-truth accounting regression (the old `_route` /
  `_route_sk` duplicates each carried their own header-width conventions).
- Zero HLO sort ops on the default (compact included) lowering.
- Adversarial skew x compact hop 2 on a REAL 8-PE mesh (subprocess): all
  mass on one owner forces the padded fallback AND slack rounds, counts
  stay exact, and a repeat call hits the executable cache.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import aggregation, compat, encoding, fabsp, minimizer, serial
from repro.data import genome

SENT32 = int(np.iinfo(np.uint32).max)


@pytest.fixture(scope="module")
def reads():
    spec = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=60,
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


def _serial_dict(reads, k):
    return serial.count_kmers_python(np.asarray(reads), k)


# --- property: route_tiles conformance ---------------------------------------


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), num_pes=st.integers(1, 8),
       capacity=st.integers(4, 64), n_word=st.integers(1, 3),
       n_i32=st.integers(0, 2))
def test_route_tiles_conformance(seed, num_pes, capacity, n_word, n_i32):
    """Arbitrary lane sets: destination rows are exactly the stream-order
    prefix of each owner's valid elements, per lane, with lanes zipped."""
    rng = np.random.default_rng(seed)
    n = 96
    lanes = tuple(
        [jnp.asarray(rng.integers(0, 1 << 20, n, dtype=np.uint32))
         for _ in range(n_word)]
        + [jnp.asarray(rng.integers(1, 1 << 10, n, dtype=np.int32))
           for _ in range(n_i32)])
    kinds = ("word",) * n_word + ("i32",) * n_i32
    owners = jnp.asarray(rng.integers(0, num_pes, n, dtype=np.int32))
    valid = jnp.asarray(rng.random(n) < 0.85)
    tiles, fill, overflow = aggregation.route_tiles(
        lanes, kinds, owners, valid, num_pes, capacity)
    o_tiles, o_fill, o_ovf = aggregation.route_tiles(
        lanes, kinds, owners, valid, num_pes, capacity, impl="argsort")
    for t, ot in zip(tiles, o_tiles):       # radix == argsort, bit-for-bit
        assert (np.asarray(t) == np.asarray(ot)).all()
    assert (np.asarray(fill) == np.asarray(o_fill)).all()
    assert int(overflow) == int(o_ovf)
    # conservation: routed + dropped == valid
    assert int(fill.sum()) + int(overflow) == int(valid.sum())
    # every row is the stream-order prefix of its owner's zipped tuples
    lanes_np = [np.asarray(x) for x in lanes]
    own = np.asarray(owners)
    val = np.asarray(valid)
    f = np.asarray(fill)
    for p in range(num_pes):
        want = [tuple(int(lane[i]) for lane in lanes_np)
                for i in range(n) if val[i] and own[i] == p][:capacity]
        got = [tuple(int(np.asarray(t)[p, j]) for t in tiles)
               for j in range(f[p])]
        assert got == want, f"owner {p}"
        # tail padding: sentinel on word lanes, zero on i32 lanes
        for t, kind in zip(tiles, kinds):
            tail = np.asarray(t)[p, f[p]:]
            assert (tail == (SENT32 if kind == "word" else 0)).all()
    # the per-slot byte model every transport's wire stat derives from
    assert aggregation.lane_wire_bytes(lanes, kinds) == 4 * len(lanes)


def test_route_tiles_and_route_lanes_validation():
    w = jnp.zeros((8,), jnp.uint32)
    i = jnp.zeros((8,), jnp.int32)
    owners = jnp.zeros((8,), jnp.int32)
    valid = jnp.ones((8,), bool)
    with pytest.raises(ValueError):     # unknown lane kind
        aggregation.route_tiles((w,), ("float",), owners, valid, 2, 4)
    with pytest.raises(ValueError):     # lanes/kinds mismatch
        aggregation.route_tiles((w, i), ("word",), owners, valid, 2, 4)
    with pytest.raises(ValueError):     # plan= is radix-only
        aggregation.route_tiles((w,), ("word",), owners, valid, 2, 4,
                                plan="x", impl="argsort")
    with pytest.raises(ValueError):     # compact hop 2 is oneplan-only
        aggregation.route_lanes((w,), ("word",), owners, valid, num_pes=4,
                                capacity=4, axis_names=("row", "col"),
                                grid=(2, 2), route2d="perhop",
                                hop2_capacity=2,
                                rederive_owners=lambda x: owners)
    with pytest.raises(ValueError):     # ... and the 1d route has no hop 2
        aggregation.route_lanes((w,), ("word",), owners, valid, num_pes=4,
                                capacity=4, axis_names=("pe",), grid=None,
                                hop2_capacity=2)
    with pytest.raises(ValueError):     # perhop re-plans from a word lane
        aggregation.route_lanes((w,), ("word",), owners, valid, num_pes=4,
                                capacity=4, axis_names=("row", "col"),
                                grid=(2, 2), route2d="perhop")
    with pytest.raises(ValueError):     # config: perhop has no compact seam
        fabsp.DAKCConfig(k=13, topology="2d", route2d_impl="perhop",
                         hop2_impl="compact")
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=13, hop2_impl="sliced")
    # legal: compact is ignored off the 2d oneplan route
    fabsp.DAKCConfig(k=13, hop2_impl="compact")
    fabsp.DAKCConfig(k=13, topology="2d", hop2_impl="compact")
    # empty reads degrade to the shape bound instead of dividing by zero
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32)
    empty = jnp.zeros((0, 40), jnp.uint8)
    assert fabsp._chunk_valid_estimate(empty, cfg, "dual", (0, 40)) \
        == fabsp._chunk_valid_estimate(None, cfg, "dual", (0, 40))


# --- compact hop-2 slicing (direct route_lanes, degenerate 1-PE 2d mesh) -----


def test_route_lanes_compact_hop2_slices_prefix(mesh2d):
    """hop2_capacity forwards exactly each bucket row's first cap2 slots
    (lanes aligned), and hop2_dropped charges the hop-1 fill for the rest."""
    n, cap, cap2 = 24, 32, 8
    words = jnp.arange(100, 100 + n, dtype=jnp.uint32)
    tags = jnp.arange(1, n + 1, dtype=jnp.int32)

    def body(w, t):
        rr = aggregation.route_lanes(
            (w, t), ("word", "i32"), jnp.zeros((n,), jnp.int32),
            jnp.ones((n,), bool), num_pes=1, capacity=cap,
            axis_names=("row", "col"), grid=(1, 1), hop2_capacity=cap2)
        return rr.lanes, rr.sent_valid, rr.wire_bytes, rr.hop2_dropped

    fn = jax.jit(compat.shard_map(
        body, mesh=mesh2d, in_specs=(P(), P()),
        out_specs=((P(), P()), P(), P(), P())))
    (rw, rt), sent, wire, h2 = fn(words, tags)
    assert rw.shape == (cap2,) and rt.shape == (cap2,)
    # the first cap2 elements in stream order survive, zipped
    assert np.asarray(rw).tolist() == list(range(100, 100 + cap2))
    assert np.asarray(rt).tolist() == list(range(1, cap2 + 1))
    assert int(h2) == n - cap2              # fill 24, compact 8
    assert int(sent) == n + cap2            # hop 1 full fill + hop 2 slice
    assert int(wire) == (cap + cap2) * (4 + 4)   # word + i32 lane widths


# --- parity grid: {1d,2d} x {kmer,superkmer} x {stream,stacked} x
#     {compact,padded} ----------------------------------------------------


@pytest.mark.parametrize("hop2", ["padded", "compact"])
@pytest.mark.parametrize("receiver", ["stream", "stacked"])
@pytest.mark.parametrize("transport", ["kmer", "superkmer"])
@pytest.mark.parametrize("topology", ["1d", "2d"])
def test_routing_parity_grid(reads, mesh1d, mesh2d, topology, transport,
                             receiver, hop2):
    """Histograms identical to the serial oracle across the full transport
    grid; wire accounting equal across receivers; the compact hop 2 never
    moves more bytes than the padded oracle (strictly fewer where L3
    compression leaves the tile under-occupied)."""
    k = 13
    mesh = mesh1d if topology == "1d" else mesh2d
    axes = ("pe",) if topology == "1d" else ("row", "col")
    cfg = fabsp.DAKCConfig(k=k, chunk_reads=32, l3_mode="dual",
                           topology=topology, transport_impl=transport,
                           minimizer_len=7, receiver_impl=receiver,
                           hop2_impl=hop2)
    res, st_ = fabsp.count_kmers(reads, mesh, cfg, axes)
    assert int(st_.overflow) == 0 and int(st_.store_overflow) == 0
    assert int(st_.hop2_dropped) == 0
    assert _merge(res) == _serial_dict(reads, k)
    if topology == "2d" and hop2 == "compact":
        padded, st_p = fabsp.count_kmers(
            reads, mesh, fabsp.DAKCConfig(
                k=k, chunk_reads=32, l3_mode="dual", topology=topology,
                transport_impl=transport, minimizer_len=7,
                receiver_impl=receiver), axes)
        assert _merge(padded) == _merge(res)
        assert int(st_.wire_bytes) <= int(st_p.wire_bytes)
        if transport == "kmer":     # dual L3 leaves the tile under-occupied
            assert int(st_.wire_bytes) < int(st_p.wire_bytes)


def test_parity_stream_equals_stacked_wire(reads, mesh2d):
    """Identical routing => identical wire accounting, exactly, compact
    included (stream and stacked receivers share one route)."""
    for hop2 in ("padded", "compact"):
        stats = {}
        for recv in ("stream", "stacked"):
            cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, topology="2d",
                                   receiver_impl=recv, hop2_impl=hop2)
            _, st_ = fabsp.count_kmers(reads, mesh2d, cfg, ("row", "col"))
            stats[recv] = st_
        assert int(stats["stream"].wire_bytes) \
            == int(stats["stacked"].wire_bytes), hop2
        assert int(stats["stream"].sent_words) \
            == int(stats["stacked"].sent_words), hop2


# --- exact per-lane wire-byte model ------------------------------------------


def _expected_wire(cfg, reads, num_pes, hop2_caps):
    """The analytic per-lane model: caps x per-slot widths, exactly what
    aggregation.lane_wire_bytes makes every transport charge."""
    n_chunks = reads.shape[0] // cfg.chunk_reads
    mode, cap_n, cap_h = fabsp._plan_caps(cfg, num_pes, tuple(reads.shape),
                                          cfg.slack)
    word_b = jnp.iinfo(encoding.kmer_dtype(cfg.k, cfg.bits_per_symbol)).bits \
        // 8
    two_hop = cfg.topology == "2d"
    c2n, c2h = hop2_caps if hop2_caps else (cap_n, cap_h)
    if mode == "superkmer":
        slot_b = minimizer.slot_bytes(cfg.k, cfg.minimizer_len,
                                      cfg.bits_per_symbol)
        per_chunk = num_pes * (cap_n + (c2n if two_hop else 0)) * slot_b
        return n_chunks * per_chunk
    if mode == "dual":
        per_chunk = num_pes * (cap_n + (c2n if two_hop else 0)) * word_b \
            + num_pes * (cap_h + (c2h if two_hop else 0)) * (word_b + 4)
        return n_chunks * per_chunk
    return n_chunks * num_pes * (cap_n + (c2n if two_hop else 0)) * word_b


@pytest.mark.parametrize("hop2", ["padded", "compact"])
@pytest.mark.parametrize("transport,l3_mode", [("kmer", "dual"),
                                               ("kmer", "packed"),
                                               ("superkmer", "auto")])
@pytest.mark.parametrize("topology", ["1d", "2d"])
def test_wire_bytes_match_per_lane_model(reads, mesh1d, mesh2d, topology,
                                         transport, l3_mode, hop2):
    """Regression for the single-source-of-truth byte accounting: the old
    `_route`/`_route_sk` duplicates each converted slots->bytes with their
    own header-width conventions; route_lanes charges every lane once, and
    the stat must equal the analytic model bit-for-bit -- dual HEAVY pairs
    (word + int32 count) and super-k-mer headers included."""
    k = 9 if l3_mode == "packed" else 13
    mesh = mesh1d if topology == "1d" else mesh2d
    axes = ("pe",) if topology == "1d" else ("row", "col")
    cfg = fabsp.DAKCConfig(k=k, chunk_reads=32, l3_mode=l3_mode,
                           topology=topology, transport_impl=transport,
                           minimizer_len=5 if k == 9 else 7,
                           hop2_impl=hop2)
    _, st_ = fabsp.count_kmers(reads, mesh, cfg, axes)
    assert int(st_.overflow) == 0 and int(st_.hop2_dropped) == 0
    hop2_caps = fabsp._resolve_hop2_caps(reads, cfg, 1, tuple(reads.shape),
                                         cfg.slack)
    assert int(st_.wire_bytes) == _expected_wire(cfg, reads, 1, hop2_caps)


# --- zero HLO sort ops on the default lowering, compact included -------------


@pytest.mark.parametrize("transport", ["kmer", "superkmer"])
def test_compact_hop2_path_has_no_hlo_sort(mesh2d, transport):
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=32, topology="2d",
                           transport_impl=transport, hop2_impl="compact")
    fabsp.clear_executable_cache()
    fn = fabsp._counting_executable(cfg, mesh2d, ("row", "col"), (64, 60),
                                    "uint8", cfg.slack, store_cap=512,
                                    hop2_caps=(64, 32))
    txt = fn.lower(jax.ShapeDtypeStruct((64, 60), jnp.uint8)).as_text()
    fabsp.clear_executable_cache()
    n_sorts = len(re.findall(r"stablehlo\.sort|\bsort\(|sort\.[0-9]", txt))
    assert n_sorts == 0, f"sort op leaked into the compact {transport} path"


# --- adversarial skew x compact hop 2 on a real 8-PE mesh --------------------


def test_compact_hop2_skew_padded_fallback_8pe_subprocess():
    """All mass on one owner (all-A reads, superkmer transport: every run
    shares the poly-A minimizer) on a REAL (2, 4) mesh: the measured
    compact tile cannot hold the single hot bucket, so the round must fall
    back to the padded hop 2 AND double the routing slack, deliver exact
    counts, and a repeat call must re-trace nothing."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import fabsp, serial

reads = np.zeros((512, 40), dtype=np.uint8)   # all-A: one minimizer value
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("row", "col"))
# chunk_reads=64 puts the hop-1 capacity (96) above the compact floor
# (64), so the hot bucket's fill both overflows hop 1 AND misses the
# compact hop-2 tile -- the two-capacity interplay under test.
cfg = fabsp.DAKCConfig(k=13, chunk_reads=64, topology="2d",
                       transport_impl="superkmer", minimizer_len=7,
                       hop2_impl="compact")
rounds = []
orig = fabsp._counting_executable
def spy(cfg_, mesh_, axes_, shape_, dtype_, slack_, store_cap=None,
        hop2_caps=None, **kw):
    rounds.append((slack_, hop2_caps))
    return orig(cfg_, mesh_, axes_, shape_, dtype_, slack_,
                store_cap=store_cap, hop2_caps=hop2_caps, **kw)
fabsp._counting_executable = spy
traces = [0]
orig_local = fabsp._local_count
def counting(*a, **k):
    traces[0] += 1
    return orig_local(*a, **k)
fabsp._local_count = counting
res, stats = fabsp.count_kmers(jnp.asarray(reads), mesh, cfg,
                               ("row", "col"))
n_rounds = len(rounds)
assert n_rounds >= 2, f"skew did not trigger the overflow round ({rounds})"
assert rounds[0][1] is not None, "round 1 should try the compact tile"
assert any(h is None for _, h in rounds[1:]), \
    f"no padded fallback round in {rounds}"
assert max(s for s, _ in rounds) > cfg.slack, f"no slack round in {rounds}"
assert int(stats.overflow) == 0 and int(stats.hop2_dropped) == 0
got = {}
nsh = res.num_unique.shape[0]; L = res.unique.shape[0] // nsh
u = np.asarray(res.unique).reshape(nsh, L)
c = np.asarray(res.counts).reshape(nsh, L)
for s in range(nsh):
    for i in range(np.asarray(res.num_unique)[s]):
        got[int(u[s, i])] = int(c[s, i])
assert got == serial.count_kmers_python(reads, 13), "wrong counts after retry"
n_traces = traces[0]
assert n_traces == n_rounds, (n_traces, n_rounds)
fabsp.count_kmers(jnp.asarray(reads), mesh, cfg, ("row", "col"))
assert traces[0] == n_traces, "retry shapes re-traced on repeat"
print("OK rounds=%d" % n_rounds)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
