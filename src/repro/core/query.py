"""Online k-mer query path: the aggregation protocol run in reverse.

The sharded CountStore that `fabsp.KmerCounter` builds is a serving index
the moment counting stops: every PE holds the committed (key, count) table
for its disjoint slice of k-mer space, so answering "how many times did
this k-mer occur" is a routed batched probe --

1. **Pack.** Query k-mers are packed/canonicalized with the SAME encoding
   the counting path used (`encoding.pack_kmers` / `encoding.canonical`),
   so a query word is bit-identical to the stored word it asks about.
2. **Forward hop.** One `aggregation.route_lanes` call sends each query
   word to its owner PE -- the identical ownership function counting used
   (`fabsp._ownership_keys` + `owner.owner_pe`, minimizer-keyed under the
   superkmer transport). A 1-based query-id `'i32'` lane rides beside the
   word lane; id 0 is indistinguishable from the zero-padded tile slots,
   so ids start at 1 and padding never aliases a live query.
3. **Probe.** Each PE probes its committed store shard in place with the
   read-only lookup kernel (`ops.hash_lookup`, kernels/hash_table.py) --
   same home-slot hash, same linear probe walk as the insert path, count
   0 is a definitive miss. Nothing is written: queries compose with a
   live counter.
4. **Return hop.** A second `route_lanes` call ships (qid, count) pairs
   back to the PE that asked (owner = (qid-1) // n_local, the inverse of
   the id assignment), and each PE scatters its answers into request
   order via (qid-1) % n_local. The concatenated per-PE outputs ARE the
   request-ordered count vector.

Overflow cannot happen, by construction rather than by retry: both hops
route with per-destination capacity = n_local (the per-PE padded query
slot count). A sender only HAS n_local items in total, so no forward
bucket can exceed n_local; and the return hop's bucket for source PE s
holds only queries s itself sent here, again <= n_local. Any query
distribution -- including every query hitting one owner -- routes cleanly
in a single deterministic execution, with no RetryController in the loop.
That is what makes the path servable: a query never rehashes, never
doubles slack, never retraces once its shape bucket is compiled.

Shape bucketing: the per-PE slot count n_local is the pow2 ceiling of
nq / P, and the jitted shard_map executable is memoized in
`fabsp._EXEC_CACHE` keyed on (cfg, mesh, n_local, store capacity) -- a
serving stream of arbitrary batch sizes compiles one executable per pow2
bucket and store generation, then reuses it forever. `KmerCounter.count /
contains` is the user-facing wrapper; `launch/kc_serve.py` is the
multi-tenant harness on top.

Spilled-bin tier (`query_spilled_counts`): a counter whose spill tier is
engaged keeps most of its counts in disk bins, with only a vestigial
in-core store; probing that store alone would silently undercount.
Instead the query runs in two stages. Stage 1 is the ordinary routed
probe above, against the snapshot's (vestigial) store. Stage 2 groups
the queries per disk bin by their bin key -- `spill.bin_of` of the same
ownership key the WRITER binned by (the third hash family), so a query
word lands in exactly the bin holding its records -- folds each touched
bin on demand through the counter's elastic fold (`_fold_pairs`, the
same engine the drain uses) into a sharded bin shard, probes it with the
same read-only lookup executable, and adds the residuals into the
request-ordered answer. Folded shards live in a byte-bounded LRU
(`BinShardCache`, budget `DAKCConfig.query_bin_cache_bytes`) keyed by
the snapshot's segment list, so steady-state serving re-probes cached
shards and a new store generation naturally invalidates; an evicted bin
just re-folds on its next touch. Bins partition k-mer space, so
vestigial + residual IS the exact count. The typed `QueryUnavailable`
survives only under the opt-in strict mode `spill_query='refuse'` (a
harness that would rather 503 than pay fold latency on the read path).

Generation pinning: `KmerCounter.count` passes the epoch-pinned
`countstore.StoreSnapshot` -- store arrays AND the spill manifest view
frozen at the last batch commit -- so both stages answer from one
committed generation even while an update, rehash, or spill replay is
in flight.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import aggregation, compat, countstore, encoding, fabsp, spill
from repro.core.owner import owner_pe
from repro.kernels import ops


class QueryUnavailable(RuntimeError):
    """The counter declines to serve: its committed generation has an
    engaged spill tier and the config opted out of the spilled-bin query
    tier's on-demand folds (`spill_query='refuse'`). Typed so a serving
    harness can 503 the tenant instead of paying fold latency."""


class QueryStats(NamedTuple):
    """Host-side stats of one `query_counts` batch."""
    n_queries: int      # live queries in the batch (pre-padding)
    n_hits: int         # queries with count > 0
    wire_bytes: int     # exact padded bytes both hops moved (global)
    probe_sum: int      # total probe steps across all live queries
    probe_max: int      # deepest single probe walk
    n_local: int        # per-PE padded slot count (the shape bucket)
    batch_fill: float   # n_queries / (n_local * P) -- padding waste
    bins_probed: int = 0  # spilled-bin stage: distinct disk bins probed
    bin_folds: int = 0    # ... of which needed an on-demand fold (cache
                          # misses; 0 on a warm cache or in-core store)

    @property
    def probe_avg(self) -> float:
        return self.probe_sum / max(1, self.n_queries)


class BinShardCache:
    """Byte-bounded LRU of materialized spill-bin shards.

    One entry per disk bin: the sharded (keys, counts) store that bin's
    records folded into, costing `P * cap * (key + int32)` bytes of
    device memory. Entries are keyed by the bin id and VERSIONED by the
    snapshot's segment-file tuple, so a later spill commit (new segments
    in the bin) misses cleanly instead of serving a stale shard.
    Eviction is LRU past `budget_bytes`, always keeping the newest entry
    (a budget smaller than one shard still serves -- every touch just
    re-folds). Counters (`hits`/`misses`/`evictions`) feed the serving
    stats and the eviction tests.
    """

    def __init__(self, budget_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self._entries = {}   # bin -> (version, keys, counts, nbytes)
        self._order = []     # LRU order, oldest first
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, b: int, version):
        e = self._entries.get(b)
        if e is None or e[0] != version:
            self.misses += 1
            return None
        self.hits += 1
        self._order.remove(b)
        self._order.append(b)
        return e[1], e[2]

    def put(self, b: int, version, keys: jax.Array,
            counts: jax.Array) -> None:
        nbytes = int(keys.size) * (keys.dtype.itemsize
                                   + counts.dtype.itemsize)
        if b in self._entries:
            self._order.remove(b)
        self._entries[b] = (version, keys, counts, nbytes)
        self._order.append(b)
        total = sum(e[3] for e in self._entries.values())
        while total > self.budget_bytes and len(self._order) > 1:
            oldest = self._order.pop(0)
            total -= self._entries.pop(oldest)[3]
            self.evictions += 1


def pack_queries(kmers, cfg) -> jax.Array:
    """Normalize query k-mers to the counting path's packed-word form.

    Accepts (n, k) base-code arrays (packed via `encoding.pack_kmers`,
    canonicalized iff cfg.canonical -- strand invariance for free) or
    already-packed (n,) word arrays (masked to k-mer width, canonicalized
    iff cfg.canonical, so forward-strand words query correctly against a
    canonical store).
    """
    k, bps = cfg.k, cfg.bits_per_symbol
    dt = encoding.kmer_dtype(k, bps)
    arr = jnp.asarray(kmers)
    if arr.ndim == 2:
        if arr.shape[1] != k:
            raise ValueError(
                f"code-array queries must be (n, k={k}), got {arr.shape}")
        return encoding.pack_kmers(
            arr, k, bps, canonical=cfg.canonical,
            canonical_impl=cfg.canonical_impl).reshape(-1)
    if arr.ndim != 1:
        raise ValueError(f"queries must be (n,) words or (n, k) codes, "
                         f"got shape {arr.shape}")
    w = arr.astype(dt) & encoding.kmer_mask(k, bps)
    if cfg.canonical:
        w = encoding.canonical(w, k)
    return w


def _query_executable(cfg, mesh: Mesh, axis_names, dtype_name: str,
                      n_local: int, store_cap: int):
    """The jitted shard_map query executable for one shape bucket.

    in: (P * n_local,) sentinel-padded query words, sharded store keys,
    sharded store counts. out: (P * n_local,) request-ordered counts plus
    5 psum'd stat scalars (hits, wire hi/lo, probe sum, probe max).
    """
    key = ("query", cfg, mesh, tuple(axis_names), dtype_name, n_local,
           store_cap)
    fn = fabsp._EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    axes = tuple(axis_names)
    num_pes = fabsp._mesh_pes(mesh, axes)
    grid = fabsp._topology_grid(cfg, mesh, axes)
    spec = fabsp._data_spec(axes)

    def local_query(qwords, skeys, scounts):
        sent = jnp.array(jnp.iinfo(qwords.dtype).max, qwords.dtype)
        valid = qwords != sent
        # flat PE id under the (row-major) axis fold -- the same index the
        # 2d 'oneplan' route decomposes owners into, so qid round-trips
        # across both topologies
        pe = jnp.int32(0)
        for ax in axes:
            pe = pe * mesh.shape[ax] + jax.lax.axis_index(ax)
        qid = (pe * n_local + jnp.arange(n_local, dtype=jnp.int32)
               + jnp.int32(1))           # 1-based: 0 marks tile padding
        with jax.named_scope("route"):
            owners = owner_pe(fabsp._ownership_keys(qwords, cfg), num_pes)
            rr = aggregation.route_lanes(
                (qwords, qid), ("word", "i32"), owners, valid,
                num_pes=num_pes, capacity=n_local, axis_names=axes,
                grid=grid, impl=cfg.partition_impl, route2d="oneplan")
            rwords, rqid = rr.lanes
            rvalid = rwords != sent
        with jax.named_scope("lookup"):
            counts, probes = ops.hash_lookup(
                skeys, scounts, rwords,
                countstore.store_slots(rwords, store_cap),
                sentinel_val=int(jnp.iinfo(qwords.dtype).max))
        with jax.named_scope("route"):
            back = (rqid - jnp.int32(1)) // jnp.int32(n_local)
            rr2 = aggregation.route_lanes(
                (rqid, counts), ("i32", "i32"), back, rvalid,
                num_pes=num_pes, capacity=n_local, axis_names=axes,
                grid=grid, impl=cfg.partition_impl, route2d="oneplan")
            bqid, bcounts = rr2.lanes
            # qids are globally unique, so each live answer owns its slot;
            # the padding slots (bqid == 0) scatter off the end and drop
            dst = jnp.where(bqid > jnp.int32(0),
                            (bqid - jnp.int32(1)) % jnp.int32(n_local),
                            jnp.int32(n_local))
            out = jnp.zeros((n_local,), jnp.int32).at[dst].add(
                bcounts, mode="drop")
        hits = ((counts > 0) & rvalid).sum().astype(jnp.int32)
        prb = jnp.where(rvalid, probes, 0)
        whi, wlo = fabsp._wire_add(jnp.int32(0), jnp.int32(0),
                                   rr.wire_bytes + rr2.wire_bytes)
        return out, (jax.lax.psum(hits, axes),
                     jax.lax.psum(whi, axes), jax.lax.psum(wlo, axes),
                     jax.lax.psum(prb.sum().astype(jnp.int32), axes),
                     jax.lax.pmax(prb.max().astype(jnp.int32), axes))

    fn = jax.jit(compat.shard_map(
        local_query, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, (P(),) * 5)))
    fabsp._EXEC_CACHE[key] = fn
    return fn


def query_counts(kmers, mesh: Mesh, cfg, skeys: jax.Array,
                 scounts: jax.Array,
                 axis_names: Sequence[str] = ("pe",)):
    """Batched lookup of `kmers` against a committed sharded store.

    kmers: (n,) packed words or (n, k) base codes (see `pack_queries`).
    skeys/scounts: the counter's sharded store arrays (P * store_cap,).
    Returns (counts, QueryStats): counts is an (n,) int32 np.ndarray in
    REQUEST order (0 = never counted), exact for any query set including
    duplicates and misses.
    """
    axes = tuple(axis_names)
    num_pes = fabsp._mesh_pes(mesh, axes)
    store_cap = skeys.shape[0] // num_pes
    with TraceAnnotation("query.pack"):
        words = pack_queries(kmers, cfg)
        host_words = np.asarray(words)
    nq = int(words.shape[0])
    n_local = fabsp._pow2ceil(max(1, -(-nq // num_pes)))
    dt = words.dtype
    sent = int(jnp.iinfo(dt).max)
    with TraceAnnotation("query.put"):
        padded = np.full((num_pes * n_local,), sent, dtype=dt)
        padded[:nq] = host_words
        sharding = NamedSharding(mesh, fabsp._data_spec(axes))
        qdev = jax.device_put(jnp.asarray(padded), sharding)
    with TraceAnnotation("query.run", n_local=n_local):
        fn = _query_executable(cfg, mesh, axes, str(np.dtype(dt)), n_local,
                               store_cap)
        out, (hits, whi, wlo, psum, pmax) = fn(qdev, skeys, scounts)
    # the first read of the answers waits for the lookup
    with TraceAnnotation("query.fetch"):
        counts = np.asarray(out)[:nq]
        stats = QueryStats(
            n_queries=nq, n_hits=int(hits),
            wire_bytes=(int(whi) << fabsp._WIRE_SHIFT) + int(wlo),
            probe_sum=int(psum), probe_max=int(pmax), n_local=n_local,
            batch_fill=nq / (n_local * num_pes))
    return counts, stats


def query_spilled_counts(kc, snap, kmers):
    """Two-stage lookup against a spill-engaged store generation.

    kc: the `fabsp.KmerCounter` (mesh, cfg, fold engine, bin cache).
    snap: the pinned `countstore.StoreSnapshot` to serve -- its store
    arrays AND its `spill_state` manifest view; a commit racing this
    call never leaks in. Returns (counts, QueryStats) exactly like
    `query_counts`: request-ordered, exact for any query set.

    Stage 1 probes the snapshot's (vestigial) in-core store with the
    ordinary routed executable. Stage 2 bins the query words with the
    writer's own bin key (`spill.bin_of` over `fabsp._ownership_keys` --
    under super-k-mer transport each k-mer's recomputed minimizer equals
    the minimizer its enclosing super-k-mer was binned by, the same
    invariant the engage-time export relies on), folds each touched bin
    on demand into a sharded shard via `kc._fold_pairs` (LRU-cached,
    `BinShardCache`), probes the subset of queries that bin owns, and
    adds the residuals. Bins partition k-mer space, so the sum is the
    exact committed count.
    """
    cfg, mesh, axes = kc._cfg, kc._mesh, kc._axes
    words = np.asarray(pack_queries(kmers, cfg))
    nq = int(words.shape[0])
    counts, stats = query_counts(words, mesh, cfg, snap.keys, snap.counts,
                                 axis_names=axes)
    counts = counts.copy()       # accumulate residuals in place
    sp = snap.spill_state
    n_bins = int(sp["n_bins"])
    by_bin = {}
    for seg in sp["segments"]:
        by_bin.setdefault(int(seg["bin"]), []).append(seg)
    wire = stats.wire_bytes
    probe_sum, probe_max = stats.probe_sum, stats.probe_max
    bins_probed = bin_folds = 0
    if nq and by_bin:
        cache = kc._bin_cache
        if cache is None or cache.budget_bytes != cfg.query_bin_cache_bytes:
            cache = kc._bin_cache = BinShardCache(cfg.query_bin_cache_bytes)
        qbins = np.asarray(spill.bin_of(
            fabsp._ownership_keys(jnp.asarray(words), cfg), n_bins))
        for b in np.unique(qbins):
            segs = by_bin.get(int(b))
            if not segs:
                continue         # no committed records: residual is 0
            version = tuple(s["file"] for s in segs)
            shard = cache.get(int(b), version)
            if shard is None:
                pairs = kc._bin_pairs(int(b), segments=segs)
                if pairs is None:
                    continue
                bk, bc, _cap = kc._fold_pairs(pairs[0], pairs[1])
                cache.put(int(b), version, bk, bc)
                shard = (bk, bc)
                bin_folds += 1
            idx = np.nonzero(qbins == b)[0]
            sub, sstats = query_counts(words[idx], mesh, cfg, shard[0],
                                       shard[1], axis_names=axes)
            counts[idx] += sub
            wire += sstats.wire_bytes
            probe_sum += sstats.probe_sum
            probe_max = max(probe_max, sstats.probe_max)
            bins_probed += 1
    return counts, QueryStats(
        n_queries=nq, n_hits=int((counts > 0).sum()), wire_bytes=wire,
        probe_sum=probe_sum, probe_max=probe_max, n_local=stats.n_local,
        batch_fill=stats.batch_fill, bins_probed=bins_probed,
        bin_folds=bin_folds)
