"""DAKC: the FA-BSP asynchronous k-mer counter (paper Alg. 3 + Alg. 4).

Execution structure (TPU adaptation, DESIGN.md Sec. 2):

- Phase 1 is ONE jitted `lax.scan` over chunks of reads. Each scan step
  extracts k-mers, runs the L3 compressor, packs destination-major tiles
  (L2), and issues one fused `all_to_all` (L0/L1). XLA double-buffers the
  scan: the collective for chunk i overlaps k-mer generation for chunk i+1,
  recovering the paper's compute/communication overlap without one-sided
  messages.
- The receiver is STREAMING (`receiver_impl='stream'`, the default): each
  scan step decompresses its received tiles and folds them straight into a
  carry-resident count store (core/countstore.py -- a fixed-capacity
  open-addressing table backed by the Pallas insert-or-add kernel,
  kernels/hash_table.py). This is the paper's asynchronous receiver-side
  hash-table insert: per-PE receive memory is the store plus ONE in-flight
  tile, independent of the number of chunks, and what used to be Phase 2
  shrinks to a single sort/compaction of the store after the scan.
- `receiver_impl='stacked'` keeps the old stack-then-sort oracle: every
  chunk's received tile is stacked in the scan output and one giant sort +
  accumulate runs after the phase barrier. Live receive memory grows as
  O(n_chunks * P * capacity); retained because it is the bit-exact
  reference semantics (final histograms match the stream path exactly as
  sorted (kmer, count) sets) and the honest BSP-style memory baseline.

Global synchronization count: 3 (program start, phase barrier, completion),
versus ceil(mn/bP) + 1 host-synchronous rounds for the BSP baseline
(core/bsp.py) -- exactly the paper's Eq. (7) gap.

Transport (`transport_impl`): what a routed tile slot carries.
- 'kmer' (the oracle): one packed word per k-mer, L3-compressed as below.
- 'superkmer': minimizer-routed super-k-mer transport (core/minimizer.py,
  the KMC 2 / MSPKmerCounter aggregation lever). Each chunk's reads are
  segmented into maximal runs of consecutive k-mers sharing a
  (w, m)-minimizer; the run's substring ships ONCE as S fixed payload
  words + an int32 length header, routed to `owner_pe(minimizer)`, and
  the receiving PE re-extracts the k-mers with the same fused canonical
  shift-or loop before folding them into the count store -- the k-1-base
  overlap between consecutive k-mers stops being paid on the wire
  (Eq. 11 volume drops ~(w+1)/2 / words-per-slot). Histograms are
  identical to 'kmer' as sorted (kmer, count) sets; only the per-PE
  partition of k-mer space (minimizer-hash vs kmer-hash) differs.
  `use_l3`/`l3_mode` are not consulted and the 2d topology always uses
  the one-plan route.

Heavy-hitter handling (L3, 'kmer' transport): two wire formats, selected
by `l3_mode`:
- 'packed': counts ride in the spare high bits of the k-mer word (one word
  per distinct k-mer on the wire). Valid whenever the spare bits can hold a
  chunk-local count; this is the TPU-native strengthening of the paper's
  {kmer, count} pair (zero extra lanes).
- 'dual': faithful to Alg. 4 -- NORMAL tile of raw k-mer words (local count
  <= 2 sent as duplicates) plus HEAVY tiles of {kmer, count} pairs for local
  count > 2. Needed at k=31 where a 64-bit word has no spare bits.

Routing: every transport is ONE call per lane set into
`aggregation.route_lanes` -- the lane-list routing engine (`_phase1_step`
describes each wire format as payload word lanes plus optional int32
header/count lanes; route_lanes buckets them all off one PartitionPlan,
runs the exchange, and returns exact per-lane wire bytes). The BSP
baseline's per-batch exchange rides the same engine (core/bsp.py), so
wire-stat and capacity conventions live in exactly one place.

Topologies (paper Table II): '1d' = direct all_to_all over the full axis;
'2d' = two-stage all_to_all over a factorized (row, col) device grid -- the
2D-HyperX analogue, trading an extra hop for O(sqrt(P)) tile memory. The
'2d' default routes both hops off ONE partition plan (`route2d_impl=
'oneplan'`; owner decomposed as (dest_col, dest_row) digits, hop 2 a plain
transpose + all_to_all) and accounts hop-2 occupancy straight from the
hop-1 fill histogram instead of re-scanning the received tile.
`hop2_impl='compact'` additionally SHIPS only a measured-occupancy hop-2
tile: a smaller power-of-two capacity planned from a sample of the reads
(each hop-1 bucket row is a contiguous valid prefix, so the compact tile
is a static slice); when the hop-1 fill histogram shows a bucket past the
compact capacity the drop is counted and the round retries on the padded
tile -- the KMC 3-style two-capacity scheme, cutting Eq. 11 hop-2 wire
volume at low occupancy with bit-identical histograms.

Sort-free hot path: with the default `partition_impl='radix'` /
`phase2_impl='radix'` knobs the whole counting pipeline lowers without a
single HLO `sort` -- L2 bucketing is a stable radix partition
(aggregation.route_tiles), chunk-local L3 compressors and the final
store compaction run the LSD radix engine (core/sort.py,
kernels/radix_partition.py), and canonicalization happens inside extraction
(`canonical_impl='fused'`). Every knob's 'argsort'/'sweep'/'perhop'/
'stacked' setting restores a bit-identical (or, for the receiver,
set-identical) oracle.

Overflow discipline: static capacities everywhere, drops counted and
returned, replays driven by ONE typed retry engine
(core/resilience.py, `DAKCConfig.retry`), escalating through THREE tiers:

1. **Slack retry.** A routing-tile overflow doubles the slack (cause
   'route-slack') and replays the round; a compact hop-2 misfit falls
   back to the padded tile (cause 'hop2-padded-fallback'). Cheap, fully
   in-core, bounded by `max_slack`.
2. **Rehash.** A full count store doubles its capacity and rehashes the
   committed entries (cause 'store-rehash'), bounded by
   `store_cap_ceiling` -- by default the largest table the insert kernel
   holds (VMEM on the TPU); no round ever grows past it.
3. **Spill.** Past the ceiling the in-core discipline is out of moves:
   with `DAKCConfig.spill='auto'` the `CapacityExhausted(store-rehash)`
   give-up is intercepted instead of raised -- the committed store
   exports to disk-backed bins (core/spill.py, the KMC 3-style
   external-memory tier), the batch replays through the bin-routed spill
   path, and `finalize()` drains the bins back through the fold engine
   one bin at a time at a store capacity each bin can afford.
   `spill='always'` runs pure out-of-core from the first batch;
   `spill='off'` (default) keeps tier 3 disabled and the typed give-up.

The policy bounds tiers 1-2 (slack past `max_slack`, store past
`store_cap_ceiling`, plus a total replay budget) and -- with the spill
tier off or unable to engage -- gives up with typed errors
(`resilience.CapacityExhausted` / `resilience.RetryBudgetExceeded`)
carrying the bounded round history. Replays are never silent: the
per-cause round counts come back in `DAKCStats.retry_*`, and the spill
tier reports `DAKCStats.spilled_bins/spilled_bytes/bins_folded`. Every
retry shape lands in the executable cache, and `DAKCConfig.faults` (a
seeded `resilience.FaultPlan`) can inject deterministic drops at any
named site -- including mid-bin-write deaths ('spill_write') and sealed
bin corruption ('bin_corrupt') -- to exercise each recovery path on
demand; a fault that stops firing recovers with exactly the fault-free
histogram.

Durability: `KmerCounter.save/restore` checkpoint the sharded store plus
the sticky retry state through train/checkpoint.py's atomic saver;
restoring onto a different PE count (or transport family) is an elastic
reshard -- live (key, count) entries re-route to their new owners through
one `route_lanes` call and fold back in via the normal insert path.

Incremental API: `KmerCounter` holds the sharded count store across calls
-- `update(reads)` folds one batch per call (same executables, same
overflow rounds), `finalize()` compacts the store into the usual
`AccumResult`. Two updates equal one concatenated `count_kmers` call;
unbounded workloads pay receive memory proportional to the DISTINCT k-mer
count, never the instance count.

Query/serving contract: the committed store doubles as a random-access
serving index -- `KmerCounter.count(kmers)` / `contains(kmers)` run the
aggregation protocol in REVERSE (core/query.py): query words route to
their owner PEs through one `route_lanes` call with a query-id lane
riding beside them, each shard is probed in place by the read-only
lookup kernel, and answers route back and scatter into request order.
Both hops run at capacity = per-PE batch size, so overflow is
structurally impossible and a query never retries or rehashes; batch
shapes bucket to pow2 so steady-state serving never retraces. Queries
are exact against the committed store for any key set (misses included)
in EVERY store regime: a spill-engaged counter serves through the
spilled-bin tier (`query.query_spilled_counts` -- vestigial-store probe
plus on-demand bin folds cached in a byte-bounded LRU), and `count()`
always reads the counter's epoch-pinned `countstore.StoreSnapshot`, so
a query racing an in-flight rehash, elastic fold, or spill replay
answers from the last committed histogram exactly. The typed
`query.QueryUnavailable` survives only under the opt-in strict mode
`spill_query='refuse'`. `launch/kc_serve.py` is the multi-tenant
harness over restored counters.

Executable cache: `count_kmers` memoizes the jitted shard_map executable on
(cfg, mesh, axis names, reads shape/dtype, slack, store capacity), so
repeated same-shape calls -- including both overflow-retry rounds,
benchmarks' best-of-3 loops and serving traffic -- pay tracing +
compilation exactly once per shape.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import (aggregation, compat, countstore, encoding, minimizer,
                        resilience, spill)
from repro.core.aggregation import plan_capacity
from repro.core.owner import owner_pe
from repro.core.sort import (AccumResult, accumulate, radix_sort,
                             sort_with_weights)


@dataclasses.dataclass(frozen=True)
class DAKCConfig:
    """Tuning parameters (paper Table III / Sec. VI-H)."""
    k: int
    chunk_reads: int = 256        # reads per scan step; chunk k-mers ~ C3
    slack: float = 1.5            # capacity = E[load] * slack   (L2 tile)
    heavy_frac: float = 0.5       # HEAVY tile capacity as fraction of NORMAL
    use_l3: bool = True
    l3_mode: str = "auto"         # 'packed' | 'dual' | 'auto'
    topology: str = "1d"          # '1d' | '2d'
    canonical: bool = False
    bits_per_symbol: int = 2
    # Implementation selectors ('radix' = sort-free partition engine,
    # 'argsort' = jnp comparison-sort oracle; bit-identical results).
    partition_impl: str = "radix"  # L2 bucketing (aggregation.route_tiles)
    phase2_impl: str = "radix"     # store/stream compaction + L3 compressors
    # 'fused' folds min(word, revcomp) into the extraction loop (O(1)/base);
    # 'sweep' is the separate-pass oracle. Only read when canonical=True.
    canonical_impl: str = "fused"
    # 'oneplan' routes both 2d hops off one (col, row)-digit partition plan;
    # 'perhop' is the plan-per-hop oracle. Only read when topology='2d'.
    route2d_impl: str = "oneplan"
    # Occupancy-aware hop 2 (2d 'oneplan' only): 'compact' ships a smaller
    # power-of-two hop-2 tile sized from a measured sample of the reads
    # (the KMC 3-style two-capacity scheme) -- when the hop-1 fill histogram
    # shows a bucket past the compact capacity, the drop is counted and the
    # round retries with the padded tile (the second capacity). 'padded'
    # (default, and the wire-parity oracle) always ships the full
    # (P, capacity) tile on hop 2. Histograms are bit-identical; only wire
    # volume (and, under a mis-estimate, one fallback round) differs.
    hop2_impl: str = "padded"
    # 'stream' folds received tiles into the carry-resident count store
    # inside the Phase-1 scan (receive memory independent of n_chunks);
    # 'stacked' is the stack-then-sort oracle. Histograms are identical as
    # sorted (kmer, count) sets.
    receiver_impl: str = "stream"
    # What travels the wire: 'kmer' (the oracle -- one packed word per
    # k-mer, L3-compressed) | 'superkmer' (minimizer-keyed super-k-mers,
    # core/minimizer.py: consecutive k-mers sharing a (w, m)-minimizer ship
    # as one variable-length substring + length header, routed by
    # owner_pe(minimizer); the receiver re-extracts k-mers locally).
    # 'superkmer' ignores use_l3/l3_mode (the overlap compression replaces
    # duplicate compression on the wire) and, under topology='2d', requires
    # the 'oneplan' route. Histograms are identical as sorted (kmer, count)
    # sets; the per-PE partition of k-mer space differs (minimizer-hash
    # vs kmer-hash ownership).
    transport_impl: str = "kmer"
    # Minimizer length m for 'superkmer' transport; the window is
    # w = k - m + 1 m-mers per k-mer.
    minimizer_len: int = 7
    # Minimizer comparison order ('superkmer' transport): 'plain' compares
    # m-mer words lexicographically (the KMC 2 signature order and this
    # repo's bit-parity oracle -- pathological on low-complexity sequence:
    # poly-A packs to word 0 and wins every window, concentrating runs and
    # owner load); 'hashed' compares on the fourth avalanche hash family
    # (owner.order_key, decorrelated from the owner/slot/bin families), so
    # minimizer-owner load spreads uniformly regardless of content. The
    # selected minimizer is the m-mer VALUE under either order, ownership
    # stays owner_pe(value), and histograms are identical as sorted
    # (kmer, count) sets; only run-length/owner-load statistics differ.
    # Part of the checkpoint ownership tag: sender and receiver (and a
    # restore) must agree on the order.
    minimizer_order: str = "plain"
    # Pre-route valid-slot compaction ('prefix'): between extraction and
    # the owner partition, each chunk's per-position lane set shrinks to
    # its occupied prefix via a 2-bucket Pallas prefix-compact
    # (aggregation.compact_lanes -- valid/invalid is a 1-bit partition
    # digit), and the per-destination route capacity re-derives from the
    # measured post-compaction density instead of the positional shape
    # bound. The superkmer transport leaves ~(w+1)/2 of every positional
    # tile invalid and 'packed'/'dual' leave their compression residue, so
    # partition/scatter work and hop-1 tile bytes drop by the same factor.
    # A compact-capacity misfit is counted into the route overflow and
    # replays at doubled slack (the usual round). 'off' (default) is the
    # bit-parity oracle: identical histograms, full positional tiles.
    compact_impl: str = "off"
    # Count-store sizing ('stream' only): capacity = store_capacity slots
    # per PE when set. Otherwise 'sample' (default) runs the two-pass
    # estimate -- count distinct on one sample chunk, extrapolate via the
    # uniform-pool inversion -- so the default store tracks the workload's
    # DISTINCT count; 'bound' keeps the instance-count bound oracle. Either
    # way a full store triggers the rehash round (capacity doubling).
    store_sizing: str = "sample"
    store_slack: float = 1.5
    store_capacity: Optional[int] = None
    # The one retry engine (core/resilience.py): per-cause caps, growth
    # factors, total replay budget. Every retried call -- count_kmers and
    # KmerCounter.update -- flows through this policy; by default slack
    # gives up past 8 and the store at the largest table the backend's
    # insert kernel holds (2**24 slots of VMEM on the TPU, 2**28 elsewhere).
    retry: resilience.RetryPolicy = resilience.RetryPolicy()
    # Deterministic fault injection: a seeded resilience.FaultPlan naming
    # one site (route_drop / store_drop / hop2_misfit / update_fail /
    # ckpt_write / spill_write / bin_corrupt). None (default, production)
    # injects nothing. A fault that stops firing after its `rounds`
    # attempts recovers through the retry engine with exactly the
    # fault-free histogram; a persistent fault drives the typed give-up
    # errors.
    faults: Optional[resilience.FaultPlan] = None
    # Disk-backed spill tier (core/spill.py -- KMC 3-style two-phase
    # external-memory counting; see "Overflow discipline" above).
    # 'off' (default): a store past its ceiling raises CapacityExhausted.
    # 'auto': on CapacityExhausted(store-rehash) the counter exports the
    # store to disk bins and re-runs the batch through the bin-routed
    # spill path -- graceful degradation under memory pressure.
    # 'always': every batch spills (pure out-of-core; the resident store
    # never holds counts). Requires receiver_impl='stream' and spill_dir.
    spill: str = "off"
    # How many disk bins k-mer space partitions into (bin = third
    # avalanche hash family of the ownership key, spill.bin_of); the
    # drain pass counts one bin at a time, so more bins = smaller per-bin
    # stores. None (default) sizes the bin count when the tier engages
    # from the sample-based distinct-count estimate (the
    # store_sizing='sample' machinery) and the store capacity the rehash
    # ladder stopped at -- spill.auto_bins -- so each bin's fold lands
    # near the store's sweet spot; an int pins it.
    spill_bins: Optional[int] = None
    # Directory the tier OWNS: segment files + manifest.json live here
    # (a fresh run wipes leftovers; restore prunes uncommitted files).
    spill_dir: Optional[str] = None
    # Host-side buffering: bytes accumulated per bin buffer before a
    # segment flushes to disk, and the bound on in-flight async
    # device->host copy bytes (the backpressure of the double buffer).
    spill_flush_bytes: int = 1 << 22
    spill_host_budget_bytes: int = 1 << 27
    # How count()/contains() serve a spill-engaged counter (core/query.py
    # spilled-bin query tier). 'fold' (default): probe the in-core
    # vestigial store, then group residual lookups per disk bin
    # (spill.bin_of of the query's ownership key -- the writer's own bin
    # family) and probe bin shards materialized on demand through the
    # elastic fold, cached in a byte-bounded LRU. 'refuse' is the strict
    # opt-out: raise the typed query.QueryUnavailable instead (a serving
    # harness that would rather 503 than pay a fold on the read path).
    spill_query: str = "fold"
    # Byte budget of the per-counter LRU of materialized bin shards
    # (query.BinShardCache): each entry costs P * store_cap slots of
    # (key + int32 count). Small budgets stay correct -- a miss just
    # re-folds the bin on the next touch.
    query_bin_cache_bytes: int = 1 << 26

    def __post_init__(self):
        for knob, allowed in (
                ("partition_impl", ("radix", "argsort")),
                ("phase2_impl", ("radix", "argsort")),
                ("canonical_impl", ("fused", "sweep")),
                ("route2d_impl", ("oneplan", "perhop")),
                ("hop2_impl", ("padded", "compact")),
                ("receiver_impl", ("stream", "stacked")),
                ("transport_impl", ("kmer", "superkmer")),
                ("minimizer_order", ("plain", "hashed")),
                ("compact_impl", ("prefix", "off")),
                ("store_sizing", ("sample", "bound")),
                ("spill_query", ("fold", "refuse"))):
            v = getattr(self, knob)
            if v not in allowed:
                raise ValueError(f"{knob} must be one of {allowed}, got {v!r}")
        if (self.topology == "2d" and self.route2d_impl == "perhop"
                and self.hop2_impl == "compact"):
            raise ValueError(
                "hop2_impl='compact' slices the one-plan route's "
                "already-partitioned hop-2 tile; the 'perhop' oracle "
                "re-plans per hop and has no compact seam")
        if self.transport_impl == "superkmer":
            if not 1 <= self.minimizer_len <= self.k:
                raise ValueError(
                    f"minimizer_len {self.minimizer_len} outside "
                    f"[1, k={self.k}]")
            if self.topology == "2d" and self.route2d_impl == "perhop":
                raise ValueError(
                    "superkmer transport routes 2d hops off the one-plan "
                    "decomposition; route2d_impl='perhop' (which re-derives "
                    "owners from received words) is kmer-transport-only")
        # a 0-slot store would turn the capacity-doubling rehash round into
        # a no-op loop (0 * 2 == 0)
        if self.store_capacity is not None and self.store_capacity < 1:
            raise ValueError(
                f"store_capacity must be >= 1, got {self.store_capacity}")
        if self.store_slack <= 0:
            raise ValueError(
                f"store_slack must be positive, got {self.store_slack}")
        if self.spill not in ("off", "auto", "always"):
            raise ValueError(
                f"spill must be one of ('off', 'auto', 'always'), "
                f"got {self.spill!r}")
        if self.spill_bins is not None and self.spill_bins < 1:
            raise ValueError(f"spill_bins must be >= 1, got {self.spill_bins}")
        if self.query_bin_cache_bytes < 1:
            raise ValueError(
                f"query_bin_cache_bytes must be >= 1, "
                f"got {self.query_bin_cache_bytes}")
        if self.spill != "off":
            if self.spill_dir is None:
                raise ValueError("spill != 'off' requires spill_dir")
            if self.receiver_impl != "stream":
                raise ValueError(
                    "the spill tier rides the streaming receiver "
                    "(receiver_impl='stream'): the stacked oracle has no "
                    "per-chunk receive tile to bin")
        if self.faults is not None:
            if (self.faults.site in ("spill_write", "bin_corrupt")
                    and self.spill == "off"):
                raise ValueError(
                    f"FaultPlan site {self.faults.site!r} targets the spill "
                    f"tier; it requires spill='auto' or 'always'")
            if (self.faults.site == "store_drop"
                    and self.receiver_impl != "stream"):
                raise ValueError(
                    "FaultPlan site 'store_drop' targets the streaming "
                    "receiver's count store; receiver_impl='stacked' has "
                    "no store to drop inserts from")
            if self.faults.site == "hop2_misfit" and not _hop2_engaged(self):
                raise ValueError(
                    "FaultPlan site 'hop2_misfit' forces a compact hop-2 "
                    "misfit: it requires topology='2d', "
                    "hop2_impl='compact', route2d_impl='oneplan'")


class DAKCStats(NamedTuple):
    overflow: jax.Array            # () int32: entries dropped by ROUTING capacity
    sent_words: jax.Array          # () int32: valid payload slots on the wire
                                   # (packed k-mer words; super-k-mer slots
                                   # under transport_impl='superkmer')
    wire_bytes: np.int64           # exact padded bytes actually moved (int64-safe:
                                   # carried through the scan as a base-2**20
                                   # int32 pair, combined host-side)
    raw_kmers: jax.Array           # () int32: k-mer instances before compression
    num_global_syncs: int          # static: 3 for DAKC (paper Sec. I)
    store_overflow: jax.Array      # () int32: inserts dropped by a full count
                                   # store (stream receiver; 0 for 'stacked')
    hop2_dropped: jax.Array = 0    # () int32: entries past the compact hop-2
                                   # capacity (hop2_impl='compact' only; a
                                   # nonzero value triggers the padded
                                   # fallback round)
    # Load-imbalance observability, computed host-side from the hop-1
    # per-destination fill histogram the routing engine already psums
    # (RouteResult.fill -- no extra collectives): max / mean of the
    # per-destination valid-slot totals (1.0 = perfectly even; 0.0 when
    # nothing routed or the topology reports no fill, e.g. the 'perhop'
    # 2d oracle), and the 99th-percentile per-destination fill. Under the
    # 2d 'oneplan' route the histogram is a fixed permutation of the
    # destination axis, which max/mean/percentile cannot see.
    load_max_over_mean: float = 0.0
    owner_fill_p99: int = 0
    # Per-cause replayed-round counts for this call (host-side Python
    # ints, zero-cost in-trace): how many rounds doubled the routing
    # slack, rehashed the store, or fell back to the padded hop-2 tile
    # before the returned (clean) round. A caller that sees zeros here
    # paid exactly one execution.
    retry_route_slack: int = 0
    retry_store_rehash: int = 0
    retry_hop2_fallback: int = 0
    # Spill-tier observability (core/spill.py; nonzero only once
    # DAKCConfig.spill engages). Lifetime totals of the tier at the time
    # of the call: distinct bins holding committed data, committed
    # segment bytes on disk, and bins folded back through the drain pass
    # (finalize() / the spilled count_kmers path).
    spilled_bins: int = 0
    spilled_bytes: int = 0
    bins_folded: int = 0
    # The receiver insert's grouped path (kernels/hash_table.py): groups
    # of consecutive inserted items that held a live item, and those of
    # them that fell back to the one-item walk (a shared home row, or an
    # item whose home row from its home lane on was full). () int32,
    # psum'd; 0 for tables too small to group and off the stream receiver.
    insert_groups: jax.Array = 0
    insert_fallbacks: jax.Array = 0


# Flat per-call stats tuple threaded out of the shard_map body, in order:
# (route_overflow, store_overflow, sent_words, wire_hi, wire_lo, raw_kmers,
#  hop2_dropped, fill, insert_groups, insert_fallbacks). All scalars except
# `fill`, the (num_pes,) int32 hop-1 per-destination fill histogram
# (psum'd like the rest; consumers that index the tuple numerically must
# special-case index 7).
STATS_FIELDS = 10


def _imbalance(fill) -> Tuple[float, int]:
    """(load_max_over_mean, owner_fill_p99) of one psum'd fill histogram."""
    fill = np.asarray(fill, dtype=np.float64)
    if fill.size == 0 or fill.sum() <= 0:
        return 0.0, 0
    return (float(fill.max() / fill.mean()),
            int(np.percentile(fill, 99)))

# Wire volume is carried as an int32 (hi, lo) pair in base 2**20: lo stays
# exact per PE, psum(hi)/psum(lo) stay inside int32 for any realistic mesh,
# and the host recombines exactly (the old float32 accumulator silently lost
# words past ~2**24 bytes of traffic). The pair counts BYTES: each transport
# converts its slot count to bytes in-trace (word lanes plus any int32
# header/count lanes), so mixed-width wire formats -- the dual HEAVY pair,
# the super-k-mer payload + length header -- are accounted exactly rather
# than rounded through a word-unit convention.
_WIRE_SHIFT = 20
_WIRE_BASE = 1 << _WIRE_SHIFT


def _wire_add(whi: jax.Array, wlo: jax.Array, wire_bytes: jax.Array):
    lo = wlo + wire_bytes.astype(jnp.int32)
    return whi + (lo >> _WIRE_SHIFT), lo & jnp.int32(_WIRE_BASE - 1)


def _stamp_retries(stats: DAKCStats, counts) -> DAKCStats:
    """Fold a RetryController's per-cause round counts into the stats."""
    return stats._replace(
        retry_route_slack=counts[resilience.ROUTE_SLACK],
        retry_store_rehash=counts[resilience.STORE_REHASH],
        retry_hop2_fallback=counts[resilience.HOP2_FALLBACK])


def _resolve_l3_mode(cfg: DAKCConfig, chunk_kmers: int) -> str:
    if not cfg.use_l3:
        return "none"
    if cfg.l3_mode != "auto":
        return cfg.l3_mode
    cap = encoding.count_capacity(cfg.k, cfg.bits_per_symbol)
    return "packed" if cap >= chunk_kmers else "dual"


def _l3_split_dual(words: jax.Array, valid: jax.Array, k: int, bps: int,
                   impl: str = "radix"):
    """Alg. 4 AddToL2Buffer: local accumulate -> NORMAL dups + HEAVY pairs.

    Returns (normal_words, normal_valid, heavy_words, heavy_counts,
    heavy_valid), all of the input length.
    """
    sent = jnp.array(jnp.iinfo(words.dtype).max, words.dtype)
    masked = jnp.where(valid, words, sent)
    sent_i = int(jnp.iinfo(words.dtype).max)
    if impl == "radix":
        acc = accumulate(
            radix_sort(masked, encoding.kmer_bits(k, bps),
                       sentinel_val=sent_i),
            sentinel_val=sent_i, impl="fused")
    else:
        acc = accumulate(jnp.sort(masked), sentinel_val=sent_i)
    n = words.shape[0]
    slot_valid = jnp.arange(n) < acc.num_unique
    cnt = acc.counts
    is_heavy = slot_valid & (cnt > 2)
    is_norm = slot_valid & (cnt <= 2)
    # NORMAL: count==1 -> one copy; count==2 -> two copies (paper duplicates).
    norm1 = jnp.where(is_norm, acc.unique, sent)
    norm2 = jnp.where(is_norm & (cnt == 2), acc.unique, sent)
    normal_words = jnp.concatenate([norm1, norm2])
    normal_valid = normal_words != sent
    heavy_words = jnp.where(is_heavy, acc.unique, sent)
    heavy_counts = jnp.where(is_heavy, cnt, 0)
    return normal_words, normal_valid, heavy_words, heavy_counts, is_heavy


def _phase1_step(chunk, *, cfg: DAKCConfig, num_pes: int, cap_n: int,
                 cap_h: int, mode: str, axis_names, grid, hop2_caps=None,
                 compact_caps=None, chunk_idx=None, fault=None):
    """One scan step: parse -> L3 / super-k-mer segmentation -> one
    `aggregation.route_lanes` exchange per lane set.

    Every wire format is a lane list: 'packed'/'none' route one word lane,
    'dual' routes a NORMAL word lane plus a HEAVY (word, i32-count) pair,
    'superkmer' routes S payload word lanes plus the i32 length header --
    route_lanes buckets each set off ONE partition plan and returns the
    exact wire bytes (per-lane byte widths are accounted in
    aggregation.lane_wire_bytes, the single source of truth).

    Canonicalization (cfg.canonical) happens inside the extraction loop
    (encoding.extract_kmers canonical=/canonical_impl=): no separate
    revcomp sweep over the packed words. `hop2_caps` is the optional
    (normal, heavy) compact hop-2 capacity pair (hop2_impl='compact').

    `compact_caps` is the optional pre-route compaction plan
    (compact_impl='prefix', resolved by `_resolve_compact`): a
    (compact_n, compact_h, route_cap_n, route_cap_h) tuple. Each lane
    set's owners are computed on the full positional layout, then the
    lanes (owners riding as an 'i32' lane) shrink to their occupied
    prefix via `aggregation.compact_lanes` and route at the re-derived
    measured-density capacity instead of the positional `cap_n`/`cap_h`.
    Valid entries past the compact capacity are counted into the
    overflow stat -- the round replays at doubled slack, which re-derives
    larger capacities, exactly like a tile overflow.

    `chunk_idx` is the traced scan counter and `fault` an armed
    'route_drop' FaultPlan (resilience.active_trace_fault): the seeded
    drop mask invalidates a deterministic subset of the primary lane's
    entries BEFORE routing, and the drop count rides the overflow stat so
    the round replays at doubled slack exactly like a real tile overflow.

    Returns (recv, (raw, sent_valid, wire_bytes, overflow, hop2_dropped,
    fill)), `fill` the (num_pes,) hop-1 per-destination valid histogram.
    """
    k, bps = cfg.k, cfg.bits_per_symbol
    h2n, h2h = (None, None) if hop2_caps is None else hop2_caps
    cc_n, cc_h, rc_n, rc_h = ((None,) * 4 if compact_caps is None
                              else compact_caps)

    def inject_drop(pvalid):
        if fault is None or fault.site != "route_drop":
            return pvalid, jnp.int32(0)
        hit = resilience.fault_mask(pvalid.shape[0], fault, chunk_idx)
        return pvalid & ~hit, jnp.sum(pvalid & hit).astype(jnp.int32)

    if mode == "superkmer":
        # Minimizer transport: route packed super-k-mer windows, not
        # k-mers. Extraction moves to the receiver (_recv_pairs).
        with jax.named_scope("extract"):
            sk = minimizer.segment_superkmers(
                chunk, k, cfg.minimizer_len, bps, canonical=cfg.canonical,
                canonical_impl=cfg.canonical_impl, order=cfg.minimizer_order)
        raw = jnp.int32(sk.lengths.shape[0])   # one slot per k-mer instance
        n_lanes = sk.words.shape[1]
        sk_valid, injected = inject_drop(sk.lengths > 0)
        with jax.named_scope("route"):
            lanes = (tuple(sk.words[:, s] for s in range(n_lanes))
                     + (sk.lengths,))
            kinds = ("word",) * n_lanes + ("i32",)
            owners = owner_pe(sk.minimizers, num_pes)
            cap, covf = cap_n, jnp.int32(0)
            if cc_n is not None and cc_n < sk.lengths.shape[0]:
                out, sk_valid, covf = aggregation.compact_lanes(
                    lanes + (owners,), kinds + ("i32",), sk_valid, cc_n,
                    impl=cfg.partition_impl)
                lanes, owners, cap = out[:-1], out[-1], rc_n
            rr = aggregation.route_lanes(
                lanes, kinds, owners, sk_valid,
                num_pes=num_pes, capacity=cap, axis_names=axis_names,
                grid=grid, impl=cfg.partition_impl, route2d="oneplan",
                hop2_capacity=h2n)
            rw = jnp.stack(rr.lanes[:-1], axis=1)
        return (rw, rr.lanes[-1], None), (raw, rr.sent_valid, rr.wire_bytes,
                                          rr.overflow + covf + injected,
                                          rr.hop2_dropped, rr.fill)

    with jax.named_scope("extract"):
        words = encoding.extract_kmers(chunk, k, bps,
                                       canonical=cfg.canonical,
                                       canonical_impl=cfg.canonical_impl)
    raw = jnp.int32(words.shape[0])
    valid = jnp.ones(words.shape, bool)
    mask = encoding.kmer_mask(k, bps)

    @jax.named_scope("route")
    def route(payload, counts, pvalid, capacity, hop2, ccap, rcap):
        lanes = (payload,) if counts is None else (payload, counts)
        kinds = ("word",) if counts is None else ("word", "i32")
        owners = owner_pe(payload & mask, num_pes)
        covf = jnp.int32(0)
        if ccap is not None and ccap < payload.shape[0]:
            out, pvalid, covf = aggregation.compact_lanes(
                lanes + (owners,), kinds + ("i32",), pvalid, ccap,
                impl=cfg.partition_impl)
            lanes, owners, capacity = out[:-1], out[-1], rcap
        rr = aggregation.route_lanes(
            lanes, kinds, owners, pvalid,
            num_pes=num_pes, capacity=capacity, axis_names=axis_names,
            grid=grid, impl=cfg.partition_impl, route2d=cfg.route2d_impl,
            hop2_capacity=hop2,
            rederive_owners=lambda w: owner_pe(w & mask, num_pes))
        return rr, covf

    if mode == "packed":
        from repro.core.aggregation import l3_compress
        with jax.named_scope("l3"):
            payload, pvalid = l3_compress(words, k, bps,
                                          impl=cfg.phase2_impl)
        pvalid, injected = inject_drop(pvalid)
        rr, covf = route(payload, None, pvalid, cap_n, h2n, cc_n, rc_n)
        return (rr.lanes[0], None, None), (raw, rr.sent_valid, rr.wire_bytes,
                                           rr.overflow + covf + injected,
                                           rr.hop2_dropped, rr.fill)

    if mode == "dual":
        with jax.named_scope("l3"):
            nw, nv, hw, hc, hv = _l3_split_dual(words, valid, k, bps,
                                                impl=cfg.phase2_impl)
        nv, injected = inject_drop(nv)
        rn, covn = route(nw, None, nv, cap_n, h2n, cc_n, rc_n)
        rh, covh = route(hw, hc, hv, cap_h, h2h, cc_h, rc_h)
        return (rn.lanes[0], rh.lanes[0], rh.lanes[1]), \
            (raw, rn.sent_valid + rh.sent_valid,
             rn.wire_bytes + rh.wire_bytes,
             rn.overflow + rh.overflow + covn + covh + injected,
             rn.hop2_dropped + rh.hop2_dropped, rn.fill + rh.fill)

    # mode == 'none': BSP-style raw words, single lane, no compression.
    valid, injected = inject_drop(valid)
    rr, covf = route(words, None, valid, cap_n, h2n, cc_n, rc_n)
    return (rr.lanes[0], None, None), (raw, rr.sent_valid, rr.wire_bytes,
                                       rr.overflow + covf + injected,
                                       rr.hop2_dropped, rr.fill)


def _recv_pairs(recv, *, cfg: DAKCConfig, mode: str):
    """Decompress one step's received tiles into (kmer, count) lanes.

    Sentinel entries come out with count 0 (skipped by the store insert and
    by accumulate alike); HEAVY packets keep their pre-aggregated counts.
    Super-k-mer tiles are re-extracted locally (minimizer.superkmer_to_kmers
    -- the same fused canonical shift-or loop the sender runs): `recv` is
    then (payload (N, S), length headers (N,), None) and each slot expands
    to up to w unit-count k-mers. ONE decoder for both receivers: the
    streaming fold and the stacked Phase 2 consume identical pairs.
    """
    k, bps = cfg.k, cfg.bits_per_symbol
    rn, rh, rhc = recv
    sent = jnp.array(jnp.iinfo(rn.dtype).max, rn.dtype)
    if mode == "superkmer":
        return minimizer.superkmer_to_kmers(
            rn, rh, k, cfg.minimizer_len, bps, canonical=cfg.canonical,
            canonical_impl=cfg.canonical_impl)
    if mode == "packed":
        from repro.core.aggregation import l3_decompress
        return l3_decompress(rn, k, bps)
    if mode == "dual":
        kmers = jnp.concatenate([rn, rh])
        counts = jnp.concatenate(
            [(rn != sent).astype(jnp.int32),
             jnp.where(rh != sent, rhc.astype(jnp.int32), 0)])
        return kmers, counts
    return rn, (rn != sent).astype(jnp.int32)


def _phase2(recv_normal, recv_heavy, recv_heavy_counts, *, cfg: DAKCConfig,
            mode: str) -> AccumResult:
    """Sort + accumulate the stacked received stream ('stacked' oracle).

    phase2_impl='radix': ONE stable LSD radix sort of the full stream
    (ceil(2k / 8) counting-partition passes over the Pallas engine, weights
    riding the same scatters) followed by the FUSED Pallas boundary +
    segment-sum sweep (accumulate impl='fused': the received stream is read
    once, no XLA segment_sum re-read). 'argsort' keeps the jnp oracle
    (comparison sort + boundary flags + segment_sum).
    """
    k, bps = cfg.k, cfg.bits_per_symbol
    impl = cfg.phase2_impl
    total_bits = encoding.kmer_bits(k, bps)
    accum_impl = "fused" if impl == "radix" else "segment_sum"
    sent = int(jnp.iinfo(recv_normal.dtype).max)
    if mode == "superkmer":
        # stacked (n_chunks, N, S) payload + (n_chunks, N) headers: decode
        # the whole received stream, then sort + accumulate as usual.
        kmers, weights = _recv_pairs(
            (recv_normal.reshape(-1, recv_normal.shape[-1]),
             recv_heavy.reshape(-1), None), cfg=cfg, mode=mode)
        keys, w = sort_with_weights(kmers, weights, impl=impl,
                                    total_bits=total_bits, sentinel_val=sent)
        return accumulate(keys, w, sentinel_val=sent, impl=accum_impl)
    flat = recv_normal.reshape(-1)
    if mode == "none":
        # single raw-word lane: skip the weights lane entirely
        if impl == "radix":
            skeys = radix_sort(flat, total_bits, sentinel_val=sent)
        else:
            skeys = jnp.sort(flat)
        return accumulate(skeys, sentinel_val=sent, impl=accum_impl)
    # 'packed' / 'dual': decode the wire format with the same _recv_pairs
    # the streaming receiver folds from -- one decoder for both receivers.
    recv = (flat,
            None if recv_heavy is None else recv_heavy.reshape(-1),
            None if recv_heavy_counts is None
            else recv_heavy_counts.reshape(-1))
    kmers, weights = _recv_pairs(recv, cfg=cfg, mode=mode)
    keys, w = sort_with_weights(kmers, weights, impl=impl,
                                total_bits=total_bits, sentinel_val=sent)
    return accumulate(keys, w, sentinel_val=sent, impl=accum_impl)


def _stream_fold(chunks, store: countstore.CountStore, *, cfg: DAKCConfig,
                 num_pes: int, cap_n: int, cap_h: int, mode: str, axis_names,
                 grid, hop2_caps=None, compact_caps=None, fault=None):
    """Phase-1 scan with the streaming receiver: route each chunk, then fold
    its decompressed receive tiles into the carry-resident count store.

    `fault` is an armed in-trace FaultPlan (or None): 'route_drop' rides
    into `_phase1_step`; 'store_drop' zeroes a seeded subset of the chunk's
    decoded insert counts here -- optionally gated on the store holding at
    least `fault.fill` of its capacity -- and charges them to
    `store.dropped`, so the round replays as a rehash exactly like a real
    full table.

    Returns (store, (raw, sent_words, wire_hi, wire_lo, route_overflow,
    hop2_dropped, fill, insert_groups, insert_fallbacks)). The scan emits
    NO per-chunk outputs -- receive memory is the store plus one in-flight
    tile, independent of the chunk count.
    """

    def step(carry, xs):
        chunk, cidx = xs
        raw_t, sent_t, whi, wlo, ovf_t, h2_t, fill_t, grp_t, st = carry
        recv, (raw, sent_w, wire, ovf, h2, fl) = _phase1_step(
            chunk, cfg=cfg, num_pes=num_pes, cap_n=cap_n, cap_h=cap_h,
            mode=mode, axis_names=axis_names, grid=grid, hop2_caps=hop2_caps,
            compact_caps=compact_caps, chunk_idx=cidx, fault=fault)
        with jax.named_scope("insert"):
            st, groups = _fold_recv(recv, st, cfg=cfg, mode=mode,
                                    fault=fault, cidx=cidx)
        whi, wlo = _wire_add(whi, wlo, wire)
        # explicit int32: x64 mode (k=31 words) promotes reductions to int64
        return (raw_t + raw.astype(jnp.int32),
                sent_t + sent_w.astype(jnp.int32), whi, wlo,
                ovf_t + ovf.astype(jnp.int32),
                h2_t + h2.astype(jnp.int32),
                fill_t + fl.astype(jnp.int32), grp_t + groups, st), None

    zero = jnp.int32(0)
    zfill = jnp.zeros((num_pes,), jnp.int32)
    chunk_ids = jnp.arange(chunks.shape[0], dtype=jnp.int32)
    (raw, sent_w, whi, wlo, ovf, h2, fill, groups, store), _ = jax.lax.scan(
        step, (zero, zero, zero, zero, zero, zero, zfill,
               jnp.zeros((2,), jnp.int32), store),
        (chunks, chunk_ids))
    return store, (raw, sent_w, whi, wlo, ovf, h2, fill, groups[0],
                   groups[1])


def _fold_recv(recv, st: countstore.CountStore, *, cfg: DAKCConfig,
               mode: str, fault, cidx):
    """Decode one step's received tiles and insert them into the store
    (the `insert` layer of `_stream_fold`'s scan step). Returns (store,
    the insert's (2,) group counts)."""
    kmers, cnts = _recv_pairs(recv, cfg=cfg, mode=mode)
    if fault is None or fault.site != "store_drop":
        return countstore.store_insert_counted(st, kmers, cnts)
    hit = resilience.fault_mask(kmers.shape[0], fault, cidx)
    if fault.fill > 0:
        sent_k = jnp.array(jnp.iinfo(st.keys.dtype).max, st.keys.dtype)
        occupied = jnp.sum(st.keys != sent_k)
        hit = hit & (occupied.astype(jnp.float32)
                     >= fault.fill * st.keys.shape[0])
    drop = hit & (cnts > 0)
    st, groups = countstore.store_insert_counted(st, kmers,
                                                 jnp.where(drop, 0, cnts))
    return st._replace(
        dropped=st.dropped + jnp.sum(drop).astype(jnp.int32)), groups


def _chunked(reads_local: jax.Array, chunk_reads: int) -> jax.Array:
    n_local, m = reads_local.shape
    if n_local % chunk_reads != 0:
        raise ValueError(
            f"local reads {n_local} not divisible by chunk_reads "
            f"{chunk_reads}; pad via data.genome.shard_reads")
    return reads_local.reshape(n_local // chunk_reads, chunk_reads, m)


def _local_count(reads_local: jax.Array, *, cfg: DAKCConfig, num_pes: int,
                 cap_n: int, cap_h: int, store_cap: int, mode: str,
                 axis_names, grid, hop2_caps=None, compact_caps=None,
                 fault=None) -> Tuple[AccumResult, tuple]:
    chunks = _chunked(reads_local, cfg.chunk_reads)
    if cfg.receiver_impl == "stream":
        dt = encoding.kmer_dtype(cfg.k, cfg.bits_per_symbol)
        store = countstore.empty_store(store_cap, dt)
        store, (raw, sent_w, whi, wlo, ovf, h2, fill, grp,
                fb) = _stream_fold(
            chunks, store, cfg=cfg, num_pes=num_pes, cap_n=cap_n,
            cap_h=cap_h, mode=mode, axis_names=axis_names, grid=grid,
            hop2_caps=hop2_caps, compact_caps=compact_caps, fault=fault)
        result = countstore.store_histogram(
            store, total_bits=encoding.kmer_bits(cfg.k, cfg.bits_per_symbol),
            impl=cfg.phase2_impl)
        store_ovf = store.dropped
    else:
        def step(carry, xs):
            chunk, cidx = xs
            recv, (raw, sent_w, wire, ovf, h2, fl) = _phase1_step(
                chunk, cfg=cfg, num_pes=num_pes, cap_n=cap_n, cap_h=cap_h,
                mode=mode, axis_names=axis_names, grid=grid,
                hop2_caps=hop2_caps, compact_caps=compact_caps,
                chunk_idx=cidx, fault=fault)
            raw_t, sent_t, whi, wlo, ovf_t, h2_t, fill_t = carry
            whi, wlo = _wire_add(whi, wlo, wire)
            return (raw_t + raw.astype(jnp.int32),
                    sent_t + sent_w.astype(jnp.int32), whi, wlo,
                    ovf_t + ovf.astype(jnp.int32),
                    h2_t + h2.astype(jnp.int32),
                    fill_t + fl.astype(jnp.int32)), recv

        zero = jnp.int32(0)
        zfill = jnp.zeros((num_pes,), jnp.int32)
        (raw, sent_w, whi, wlo, ovf, h2, fill), recvs = jax.lax.scan(
            step, (zero, zero, zero, zero, zero, zero, zfill),
            (chunks, jnp.arange(chunks.shape[0], dtype=jnp.int32)))
        recv_n, recv_h, recv_hc = recvs
        result = _phase2(recv_n, recv_h, recv_hc, cfg=cfg, mode=mode)
        store_ovf = grp = fb = jnp.int32(0)

    ax = tuple(axis_names)
    stats = tuple(jax.lax.psum(x, ax)
                  for x in (ovf, store_ovf, sent_w, whi, wlo, raw, h2, fill,
                            grp, fb))
    return AccumResult(unique=result.unique, counts=result.counts,
                       num_unique=result.num_unique.reshape(1)), stats


# Jitted shard_map executables, keyed on everything that shapes the trace:
# (cfg, mesh, axis names, reads shape/dtype, resolved slack, resolved store
# capacity) plus a role tag for the incremental-API executables. A jax.jit
# callable built fresh on every count_kmers call re-traces every time; the
# memo makes repeated same-shape calls (benchmark loops, serving traffic,
# both overflow-retry rounds at their doubled slack/capacity) reuse the
# compiled executable. Bounded in practice by the handful of distinct
# workload shapes a process sees; `clear_executable_cache` resets it (tests).
_EXEC_CACHE: dict = {}


def clear_executable_cache() -> None:
    _EXEC_CACHE.clear()


def _mesh_pes(mesh: Mesh, axis_names) -> int:
    return math.prod(mesh.shape[a] for a in axis_names)


def _default_store_capacity(cfg: DAKCConfig, shape, num_pes: int) -> int:
    """Per-PE count-store slots from the instance-count BOUND.

    Slots are consumed by distinct k-mers only; with only the reads SHAPE
    in hand the safe bound is min(total instances, |alphabet|**k) spread
    over PEs with `store_slack` headroom (hash-uniform spread; the rehash
    round absorbs the tail). This is the `store_sizing='bound'` oracle and
    the shape-only fallback (dry-run lowering, analytic benchmarks);
    `count_kmers` itself defaults to the two-pass sample estimate
    (`_sampled_store_capacity`), and callers with distinct-count knowledge
    set `store_capacity` directly.
    """
    if cfg.receiver_impl != "stream":
        return 0
    if cfg.store_capacity is not None:
        return cfg.store_capacity
    n_reads, m = shape
    total = n_reads * (m - cfg.k + 1)
    distinct_bound = min(total,
                         1 << encoding.kmer_bits(cfg.k, cfg.bits_per_symbol))
    return _within_ceiling(
        plan_capacity(distinct_bound, num_pes, cfg.store_slack), cfg)


def _within_ceiling(cap: int, cfg: DAKCConfig) -> int:
    """A planned store capacity clamped to the retry policy's ceiling: a
    store that starts there and still overflows spills (or raises
    CapacityExhausted) instead of asking for a table that cannot compile."""
    return min(cap, cfg.retry.store_ceiling())


def _sampled_distinct_estimate(reads, cfg: DAKCConfig,
                               num_pes: int) -> Optional[int]:
    """Two-pass GLOBAL distinct-count estimate: distinct-count one sample
    chunk, then extrapolate to the full read set.

    The sample's (instances s, distinct d) pair is inverted under the
    uniform-pool model -- find the pool size U with
    E[distinct | s draws from U] = U * (1 - (1 - 1/U)^s) = d -- and the
    same curve evaluated at the full instance count gives the estimate.
    When the workload's distinct set saturates (deep coverage of a finite
    genome), U is finite and the estimate stops scaling with input size.
    A fully-distinct sample (d == s) carries no saturation information:
    returns None (callers fall back to the instance-count bound).

    Two consumers: the `store_sizing='sample'` store capacity
    (`_sampled_store_capacity`) and -- via `KmerCounter._distinct_est` --
    the spill tier's automatic bin count (`spill.auto_bins`), so one
    sampling pass prices both the resident store and the disk partition.
    """
    n_reads, m = reads.shape
    k, bps = cfg.k, cfg.bits_per_symbol
    sample = jnp.asarray(reads)[:min(cfg.chunk_reads, n_reads)]
    words = np.asarray(encoding.extract_kmers(
        sample, k, bps, canonical=cfg.canonical,
        canonical_impl=cfg.canonical_impl))
    s = int(words.size)
    d = int(np.unique(words).size)
    total = n_reads * (m - k + 1)
    bound = min(total, 1 << encoding.kmer_bits(k, bps))
    if d >= s:
        return None

    def exp_distinct(u: float, n: int) -> float:
        return u * -math.expm1(n * math.log1p(-1.0 / u))

    lo, hi = float(max(d, 2)), float(bound)
    if exp_distinct(hi, s) < d:
        u = hi                         # even the bound-sized pool saturates
    else:
        for _ in range(60):            # log-space bisection; f is monotone
            mid = math.sqrt(lo * hi)
            if exp_distinct(mid, s) < d:
                lo = mid
            else:
                hi = mid
        u = hi
    return min(max(int(math.ceil(exp_distinct(u, total))), d), bound)


def _sampled_store_capacity(reads, cfg: DAKCConfig, num_pes: int) -> int:
    """Per-PE store slots from the sample estimate (`store_sizing='sample'`;
    an under-estimate costs one rehash round, the same discipline as every
    other static capacity here).

    The capacity is rounded UP to a power of two: the estimate is
    data-dependent, and without quantization every same-shape batch with
    slightly different content would miss the executable cache (capacity
    is part of the trace key) and pay a full recompile -- at most 2x slots
    buys back cache hits across a serving stream.
    """
    est = _sampled_distinct_estimate(reads, cfg, num_pes)
    if est is None:
        return _default_store_capacity(cfg, tuple(reads.shape), num_pes)
    cap = plan_capacity(est, num_pes, cfg.store_slack)
    return _within_ceiling(1 << (cap - 1).bit_length(), cfg)


def _resolve_store_capacity(reads, cfg: DAKCConfig, num_pes: int) -> int:
    """Store slots for one concrete read set: explicit override >
    'sample' two-pass estimate > shape-only instance bound."""
    if cfg.receiver_impl != "stream":
        return 0
    if cfg.store_capacity is not None:
        return cfg.store_capacity
    if cfg.store_sizing == "sample":
        return _sampled_store_capacity(reads, cfg, num_pes)
    return _default_store_capacity(cfg, tuple(reads.shape), num_pes)


def _topology_grid(cfg: DAKCConfig, mesh: Mesh, axis_names):
    sizes = [mesh.shape[a] for a in axis_names]
    if cfg.topology == "2d":
        if len(axis_names) != 2:
            raise ValueError("2d topology needs two axis names (row, col)")
        return (sizes[0], sizes[1])
    return None


def _plan_caps(cfg: DAKCConfig, num_pes: int, shape, slack: float):
    """(mode, cap_n, cap_h) for one reads shape -- shared by count_kmers,
    the incremental-update executable and launch/kc_dryrun.

    transport_impl='superkmer' reports mode 'superkmer': cap_n is then the
    per-destination SUPER-K-MER slot capacity, planned from the expected
    run density 2 / (w + 1) (minimizer.expected_superkmers); the L3 mode
    machinery (and cap_h) does not apply -- overlap compression replaces
    duplicate compression on the wire.
    """
    n_reads, m = shape
    chunk_kmers = cfg.chunk_reads * (m - cfg.k + 1)
    if cfg.transport_impl == "superkmer":
        est = minimizer.expected_superkmers(cfg.chunk_reads, m, cfg.k,
                                            cfg.minimizer_len)
        return "superkmer", plan_capacity(est, num_pes, slack), 0
    mode = _resolve_l3_mode(cfg, chunk_kmers)
    # 'dual' NORMAL lane can carry up to 2x duplicated entries.
    n_items = chunk_kmers * (2 if mode == "dual" else 1)
    cap_n = plan_capacity(n_items, num_pes, slack)
    cap_h = max(8, int(cap_n * cfg.heavy_frac))
    return mode, cap_n, cap_h


def _pow2ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


# How many evenly-spaced chunks _chunk_valid_estimate samples: one chunk's
# count is Poisson-noisy for the small lanes (the HEAVY split especially --
# a skewed read set can put 20x more heavy k-mers in a later chunk than in
# the first), and the compact capacity sizes for the max.
_HOP2_SAMPLE_CHUNKS = 4


def _chunk_valid_estimate(reads, cfg: DAKCConfig, mode: str, shape,
                          num_pes: int = 1
                          ) -> Tuple[int, int, int, int]:
    """Measured per-chunk (normal, heavy, peak_normal, peak_heavy) VALID
    slot estimate -- the occupancy the compact hop 2 sizes its tile for,
    plus the single-owner PEAK the compact pre-route sizes its caps for.

    Up to `_HOP2_SAMPLE_CHUNKS` evenly-spaced chunks of the reads are
    pushed through the mode's own compression ('packed': distinct count;
    'dual': duplicate/heavy split; 'superkmer': actual minimizer-run
    count) and the per-chunk MAX is the estimate; 'none' ships every
    instance so the shape bound is already exact. peak_* is the max over
    sampled chunks of the busiest single destination's slot count under
    the real owner hash (`owner_pe` of the lane's routed key: the word
    for k-mer transport, the minimizer for super-k-mers) -- mean-density
    caps under-fit exactly when this peak outruns est/P, i.e. on skewed
    input. With no reads in hand (shape-only lowering) the estimate
    degrades to the instance bound, the peak to the mean, and compact
    degenerates to padded. A sample smaller than one chunk is scaled up
    (over-estimating -- the safe direction; an under-estimate costs one
    padded-fallback round, the same discipline as every static capacity).
    """
    n_reads, m = shape
    chunk_kmers = cfg.chunk_reads * (m - cfg.k + 1)

    def flat(est_n, est_h):
        # no data: the best peak guess is the mean density
        return (est_n, est_h, -(-est_n // num_pes), -(-est_h // num_pes))

    if mode == "none" or reads is None or n_reads == 0:
        if mode == "superkmer":
            return flat(minimizer.expected_superkmers(
                cfg.chunk_reads, m, cfg.k, cfg.minimizer_len), 0)
        return flat(chunk_kmers * (2 if mode == "dual" else 1), chunk_kmers)

    def owner_peak(words, weights=None):
        if words.size == 0:
            return 0
        own = np.asarray(owner_pe(jnp.asarray(words), num_pes))
        return int(np.bincount(own, weights=weights,
                               minlength=num_pes).max())

    reads = jnp.asarray(reads)
    n_chunks = max(1, n_reads // cfg.chunk_reads)
    est_n = est_h = peak_n = peak_h = 0
    for c in sorted({(i * n_chunks) // _HOP2_SAMPLE_CHUNKS
                     for i in range(min(_HOP2_SAMPLE_CHUNKS, n_chunks))}):
        lo = c * cfg.chunk_reads
        sample = reads[lo:lo + min(cfg.chunk_reads, n_reads)]
        scale = -(-cfg.chunk_reads // sample.shape[0])
        if mode == "superkmer":
            sk = minimizer.segment_superkmers(
                sample, cfg.k, cfg.minimizer_len, cfg.bits_per_symbol,
                canonical=cfg.canonical, canonical_impl=cfg.canonical_impl,
                order=cfg.minimizer_order)
            valid = np.asarray(sk.lengths) > 0
            est_n = max(est_n, scale * int(valid.sum()))
            peak_n = max(peak_n, scale * owner_peak(
                np.asarray(sk.minimizers)[valid]))
            continue
        words = np.asarray(encoding.extract_kmers(
            sample, cfg.k, cfg.bits_per_symbol, canonical=cfg.canonical,
            canonical_impl=cfg.canonical_impl))
        uniq, counts = np.unique(words, return_counts=True)
        if mode == "packed":
            est_n = max(est_n, scale * int(counts.size))
            peak_n = max(peak_n, scale * owner_peak(uniq))
            continue
        # 'dual': NORMAL ships `count` copies for count <= 2, HEAVY a pair.
        est_n = max(est_n, scale * int((counts == 1).sum()
                                       + 2 * (counts == 2).sum()))
        est_h = max(est_h, scale * int((counts > 2).sum()))
        normal = counts <= 2
        peak_n = max(peak_n, scale * owner_peak(
            uniq[normal], counts[normal].astype(np.float64)))
        peak_h = max(peak_h, scale * owner_peak(uniq[~normal]))
    return est_n, est_h, peak_n, peak_h


def _hop2_engaged(cfg: DAKCConfig) -> bool:
    """Whether the compact hop-2 scheme applies to this config at all."""
    return (cfg.topology == "2d" and cfg.hop2_impl == "compact"
            and cfg.route2d_impl == "oneplan")


def _resolve_hop2_caps(reads, cfg: DAKCConfig, num_pes: int, shape,
                       slack: float,
                       est: Optional[Tuple[int, int]] = None
                       ) -> Optional[Tuple[int, int]]:
    """(normal, heavy) compact hop-2 capacities, or None for the padded
    oracle (also when compact would not engage: 1d, perhop, or
    hop2_impl='padded').

    Each capacity is the measured-occupancy plan (`_chunk_valid_estimate`
    spread over PEs with the routing slack) rounded UP to a power of two:
    the estimate is data-dependent, and quantizing keeps near-identical
    batches on one executable-cache entry (the same discipline as the
    sampled store sizing). Floored at 64 slots -- per-bucket fills are
    Poisson, and for small estimates the relative tail is wide while 64
    slots cost next to nothing -- and clamped to the hop-1 capacity, where
    compact degenerates to the padded tile exactly. `est` short-circuits
    the sampling pass: retry rounds re-derive capacities at their doubled
    slack without re-reading the data (the estimate is slack-independent).
    """
    if not _hop2_engaged(cfg):
        return None
    mode, cap_n, cap_h = _plan_caps(cfg, num_pes, shape, slack)
    est_n, est_h = (_chunk_valid_estimate(reads, cfg, mode, shape)
                    if est is None else est)[:2]

    def cap2(cap, est_lane):
        return min(cap, max(64, _pow2ceil(
            plan_capacity(max(est_lane, 1), num_pes, slack))))

    return cap2(cap_n, est_n), cap2(cap_h, est_h) if cap_h else 0


def _compact_engaged(cfg: DAKCConfig) -> bool:
    """Whether the pre-route prefix compaction applies to this config."""
    return cfg.compact_impl == "prefix"


def _resolve_compact(reads, cfg: DAKCConfig, num_pes: int, shape,
                     slack: float,
                     est: Optional[Tuple[int, int]] = None
                     ) -> Optional[Tuple[int, int, int, int]]:
    """(compact_n, compact_h, route_cap_n, route_cap_h) for the pre-route
    prefix compaction, or None when the seam cannot pay (compact_impl=
    'off', the 'none' wire format -- every positional slot ships -- or a
    chunk the measured density shows is already dense).

    compact_* is the kept-prefix length each lane set shrinks to: the
    measured per-chunk VALID estimate (`_chunk_valid_estimate` -- the same
    sample the compact hop 2 plans from, shared via `est`) with the
    routing slack, rounded UP to a power of two for executable-cache
    stability and floored at 64 (Poisson tails at tiny estimates cost
    nothing). route_cap_* is the re-derived per-destination capacity the
    compacted lanes route at -- sized to the LARGER of the mean-density
    plan and the measured single-owner peak with the routing slack as
    headroom: mean density alone under-fits exactly on skewed input
    (poly-A or power-law reads concentrate one minimizer's whole load on
    one owner), which burnt a doubled-slack retry round per batch before
    the peak term. Clamped to the positional capacity, where compaction
    degenerates to the plain tile. A mis-estimate still costs only one
    doubled-slack round (both capacities re-derive from the controller's
    slack), the usual discipline.
    """
    if not _compact_engaged(cfg):
        return None
    mode, cap_n, cap_h = _plan_caps(cfg, num_pes, shape, slack)
    if mode == "none":
        return None
    est_n, est_h, peak_n, peak_h = (
        _chunk_valid_estimate(reads, cfg, mode, shape, num_pes)
        if est is None else est)
    n_reads, m = shape
    chunk_kmers = cfg.chunk_reads * (m - cfg.k + 1)
    n_n = chunk_kmers * (2 if mode == "dual" else 1)

    def caps(n_slots, est_lane, peak_lane, cap_lane):
        cc = max(64, _pow2ceil(int(math.ceil(max(est_lane, 1) * slack))))
        if cc >= n_slots:
            return n_slots, cap_lane     # already dense: seam is a no-op
        peak_need = int(math.ceil(max(peak_lane, 1) * slack))
        target = max(plan_capacity(max(est_lane, 1), num_pes, slack),
                     peak_need)
        # The ceiling is the positional cap while the measured peak fits
        # under it (routing above what the padded tile ships would only
        # inflate the wire), but when the hottest owner overflows the
        # positional cap -- the skewed inputs the peak term exists for,
        # where the mean-density plan burnt a doubled-slack round -- it
        # lifts to the compacted slot count: a sender only HAS cc slots,
        # so rc == cc routes any skew overflow-free.
        ceiling = cap_lane if peak_need <= cap_lane else cc
        rc = min(ceiling, max(64, _pow2ceil(target)))
        return cc, rc

    cc_n, rc_n = caps(n_n, est_n, peak_n, cap_n)
    cc_h, rc_h = (caps(chunk_kmers, est_h, peak_h, cap_h) if mode == "dual"
                  else (0, 0))
    if cc_n >= n_n and (mode != "dual" or cc_h >= chunk_kmers):
        return None
    return cc_n, cc_h, rc_n, rc_h


def _data_spec(axis_names):
    return P(axis_names if len(axis_names) > 1 else axis_names[0])


def _counting_executable(cfg: DAKCConfig, mesh: Mesh, axis_names, shape,
                         dtype_name: str, slack: float,
                         store_cap: Optional[int] = None,
                         hop2_caps: Optional[Tuple[int, int]] = None,
                         compact_caps: Optional[Tuple[int, int, int,
                                                      int]] = None,
                         fault=None):
    num_pes = _mesh_pes(mesh, axis_names)
    if store_cap is None:
        store_cap = _default_store_capacity(cfg, shape, num_pes)
    # `fault` (the armed in-trace FaultPlan, hashable) is part of the key:
    # a faulted round and its clean retry are distinct executables, both
    # cached.
    key = (cfg, mesh, axis_names, shape, dtype_name, slack, store_cap,
           hop2_caps, compact_caps, fault)
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    grid = _topology_grid(cfg, mesh, axis_names)
    mode, cap_n, cap_h = _plan_caps(cfg, num_pes, shape, slack)

    spec = _data_spec(axis_names)
    fn = jax.jit(compat.shard_map(
        functools.partial(_local_count, cfg=cfg, num_pes=num_pes, cap_n=cap_n,
                          cap_h=cap_h, store_cap=store_cap, mode=mode,
                          axis_names=axis_names, grid=grid,
                          hop2_caps=hop2_caps, compact_caps=compact_caps,
                          fault=fault),
        mesh=mesh, in_specs=(spec,),
        out_specs=(AccumResult(unique=spec, counts=spec, num_unique=spec),
                   (P(),) * STATS_FIELDS)))
    _EXEC_CACHE[key] = fn
    return fn


def _host_stats(cfg: DAKCConfig, raw_stats) -> DAKCStats:
    (route_ovf, store_ovf, sent_w, whi, wlo, raw, hop2_dropped,
     fill, groups, fallbacks) = raw_stats
    # the traced accumulator already counts bytes (see _wire_add)
    wire_bytes = (int(whi) << _WIRE_SHIFT) + int(wlo)
    lmm, p99 = _imbalance(fill)
    return DAKCStats(overflow=route_ovf, sent_words=sent_w,
                     wire_bytes=np.int64(wire_bytes),
                     raw_kmers=raw, num_global_syncs=3,
                     store_overflow=store_ovf, hop2_dropped=hop2_dropped,
                     load_max_over_mean=lmm, owner_fill_p99=p99,
                     insert_groups=groups, insert_fallbacks=fallbacks)


def _retry_hop2_caps(reads, cfg: DAKCConfig, num_pes: int, shape,
                     ctrl: "resilience.RetryController",
                     est) -> Optional[Tuple[int, int]]:
    """Compact hop-2 capacities for the controller's current round (None
    once the round runs on the padded tile). An armed 'hop2_misfit' fault
    forces a 1-slot compact tile, which the hop-1 fill histogram cannot
    fit -- the padded-fallback recovery path, on demand."""
    if ctrl.hop2_padded:
        return None
    caps = _resolve_hop2_caps(reads, cfg, num_pes, shape, ctrl.slack,
                              est=est)
    plan = cfg.faults
    if (caps is not None and plan is not None
            and plan.site == "hop2_misfit" and plan.fires(ctrl.attempts)):
        caps = (1, 1 if caps[1] else 0)
    return caps


def count_kmers(reads: jax.Array, mesh: Mesh, cfg: DAKCConfig,
                axis_names: Sequence[str] = ("pe",),
                _slack_override: Optional[float] = None,
                _store_cap_override: Optional[int] = None,
                _hop2_padded: bool = False,
                _hop2_est: Optional[Tuple[int, int]] = None
                ) -> Tuple[AccumResult, DAKCStats]:
    """Distributed asynchronous k-mer counting (DAKC).

    reads: (n_reads, m) symbol codes, sharded (or shardable) over
           axis_names[0] on `mesh`. n_reads must divide evenly.
    Returns the per-shard AccumResult (each shard owns a disjoint k-mer set;
    the global histogram is the concatenation) and wire statistics.

    Overflow rounds run through `cfg.retry` (one resilience.RetryController
    per call): routing-capacity overflow (possible only under adversarial
    skew with L3 off) replays at doubled slack; a full count store (stream
    receiver sized below the distinct-count) replays at doubled store
    capacity -- a rehash round; a compact hop-2 tile the hop-1 fill
    histogram did not fit (hop2_impl='compact' under skew or a
    mis-estimated sample) replays on the PADDED hop-2 tile -- the second
    capacity of the two-capacity scheme. Per-cause replay counts come back
    in `DAKCStats.retry_*`; a cause that persists past its policy cap
    raises `resilience.CapacityExhausted` (and the total budget,
    `resilience.RetryBudgetExceeded`), both carrying the round history.
    All retry shapes land in the executable cache
    (`_counting_executable`). The underscore parameters seed the
    controller's initial state (tests and the dry-run drive specific
    rounds through them).
    """
    axis_names = tuple(axis_names)
    if cfg.spill != "off":
        # Out-of-core path: delegate to the incremental counter (one
        # update + drain), so the spill implementation lives in exactly
        # one place for both APIs, on every transport and topology. The
        # underscore seed parameters do not apply to the spilled path.
        kc = KmerCounter(mesh, cfg, axis_names)
        ustats = kc.update(reads)
        result, fstats = kc.finalize()
        return result, ustats._replace(
            retry_route_slack=fstats.retry_route_slack,
            retry_store_rehash=fstats.retry_store_rehash,
            retry_hop2_fallback=fstats.retry_hop2_fallback,
            spilled_bins=fstats.spilled_bins,
            spilled_bytes=fstats.spilled_bytes,
            bins_folded=fstats.bins_folded)
    num_pes = _mesh_pes(mesh, axis_names)
    shape = tuple(reads.shape)
    slack = _slack_override if _slack_override is not None else cfg.slack
    store_cap = (_store_cap_override if _store_cap_override is not None
                 else _resolve_store_capacity(reads, cfg, num_pes))
    engaged = _hop2_engaged(cfg) and not _hop2_padded
    if ((engaged or _compact_engaged(cfg)) and _hop2_est is None):
        # sample once; retries re-plan on it (shared by the compact hop-2
        # tile and the pre-route compaction -- one measured estimate)
        mode = _plan_caps(cfg, num_pes, shape, slack)[0]
        _hop2_est = _chunk_valid_estimate(reads, cfg, mode, shape, num_pes)
    ctrl = resilience.RetryController(cfg.retry, slack=slack,
                                      store_cap=store_cap,
                                      hop2_padded=not engaged)
    while True:
        hop2_caps = _retry_hop2_caps(reads, cfg, num_pes, shape, ctrl,
                                     _hop2_est)
        compact_caps = _resolve_compact(reads, cfg, num_pes, shape,
                                        ctrl.slack, est=_hop2_est)
        fault = resilience.active_trace_fault(cfg.faults, ctrl.attempts)
        fn = _counting_executable(cfg, mesh, axis_names, shape,
                                  str(reads.dtype), ctrl.slack,
                                  store_cap=ctrl.store_cap,
                                  hop2_caps=hop2_caps,
                                  compact_caps=compact_caps, fault=fault)
        result, raw_stats = fn(reads)
        stats = _host_stats(cfg, raw_stats)
        if not ctrl.observe(route_dropped=int(stats.overflow),
                            store_dropped=int(stats.store_overflow),
                            hop2_dropped=int(stats.hop2_dropped)):
            return result, _stamp_retries(stats, ctrl.counts)


# ---------------------------------------------------------------------------
# Incremental API: repeated batches accumulate into one persistent store.
# ---------------------------------------------------------------------------


def _update_executable(cfg: DAKCConfig, mesh: Mesh, axis_names, shape,
                       dtype_name: str, slack: float, store_cap: int,
                       hop2_caps: Optional[Tuple[int, int]] = None,
                       compact_caps: Optional[Tuple[int, int, int,
                                                    int]] = None,
                       fault=None):
    key = ("update", cfg, mesh, axis_names, shape, dtype_name, slack,
           store_cap, hop2_caps, compact_caps, fault)
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    num_pes = _mesh_pes(mesh, axis_names)
    grid = _topology_grid(cfg, mesh, axis_names)
    mode, cap_n, cap_h = _plan_caps(cfg, num_pes, shape, slack)
    spec = _data_spec(axis_names)

    def local_update(reads_local, skeys, scounts):
        chunks = _chunked(reads_local, cfg.chunk_reads)
        store = countstore.CountStore(keys=skeys, counts=scounts,
                                      dropped=jnp.int32(0))
        store, (raw, sent_w, whi, wlo, ovf, h2, fill, grp,
                fb) = _stream_fold(
            chunks, store, cfg=cfg, num_pes=num_pes, cap_n=cap_n,
            cap_h=cap_h, mode=mode, axis_names=axis_names, grid=grid,
            hop2_caps=hop2_caps, compact_caps=compact_caps, fault=fault)
        ax = tuple(axis_names)
        stats = tuple(jax.lax.psum(x, ax)
                      for x in (ovf, store.dropped, sent_w, whi, wlo, raw,
                                h2, fill, grp, fb))
        return store.keys, store.counts, stats

    fn = jax.jit(compat.shard_map(
        local_update, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec, (P(),) * STATS_FIELDS)))
    _EXEC_CACHE[key] = fn
    return fn


def _finalize_executable(cfg: DAKCConfig, mesh: Mesh, axis_names,
                         store_cap: int):
    key = ("finalize", cfg, mesh, axis_names, store_cap)
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    spec = _data_spec(axis_names)
    total_bits = encoding.kmer_bits(cfg.k, cfg.bits_per_symbol)

    def local_finalize(skeys, scounts):
        with jax.named_scope("finalize"):
            res = countstore.store_histogram(
                countstore.CountStore(keys=skeys, counts=scounts,
                                      dropped=jnp.int32(0)),
                total_bits=total_bits, impl=cfg.phase2_impl)
        return AccumResult(unique=res.unique, counts=res.counts,
                           num_unique=res.num_unique.reshape(1))

    fn = jax.jit(compat.shard_map(
        local_finalize, mesh=mesh, in_specs=(spec, spec),
        out_specs=AccumResult(unique=spec, counts=spec, num_unique=spec)))
    _EXEC_CACHE[key] = fn
    return fn


def _grow_executable(cfg: DAKCConfig, mesh: Mesh, axis_names,
                     new_cap: int, old_cap: int):
    key = ("grow", cfg, mesh, axis_names, new_cap, old_cap)
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    spec = _data_spec(axis_names)

    def local_grow(skeys, scounts):
        st = countstore.store_grow(
            countstore.CountStore(keys=skeys, counts=scounts,
                                  dropped=jnp.int32(0)), new_cap)
        return st.keys, st.counts, jax.lax.psum(st.dropped,
                                                tuple(axis_names))

    fn = jax.jit(compat.shard_map(
        local_grow, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, spec, P())))
    _EXEC_CACHE[key] = fn
    return fn


def _ownership_keys(words: jax.Array, cfg: DAKCConfig) -> jax.Array:
    """The key `owner_pe` hashes for one stored k-mer word.

    'kmer' transport owns by the masked word itself. 'superkmer' transport
    owns by the k-mer's (canonical) minimizer -- a pure function of the
    word, recomputed here by unpacking the word back to base codes (base j
    sits at bit offset bps*(k-1-j), the pack_kmers layout) and running the
    same windowed-minimum the sender used. A reshard MUST preserve the
    ownership family: routing restored superkmer-counted entries by k-mer
    hash would land them away from where future updates send fresh copies,
    splitting counts across PEs.
    """
    k, bps = cfg.k, cfg.bits_per_symbol
    w = words & encoding.kmer_mask(k, bps)
    if cfg.transport_impl != "superkmer":
        return w
    shifts = (jnp.arange(k - 1, -1, -1).astype(words.dtype)
              * words.dtype.type(bps))
    codes = ((w[:, None] >> shifts[None, :])
             & words.dtype.type((1 << bps) - 1)).astype(jnp.uint8)
    return minimizer.window_minimizers(
        codes, k, cfg.minimizer_len, bps, canonical=cfg.canonical,
        canonical_impl=cfg.canonical_impl, order=cfg.minimizer_order)[:, 0]


def _reshard_executable(cfg: DAKCConfig, mesh: Mesh, axis_names,
                        dtype_name: str, n_local: int, route_cap: int,
                        store_cap: int):
    """One elastic-reshard round: each PE re-routes its slice of the saved
    (key, count) entries to the entries' owners under THIS mesh's PE count
    via one `route_lanes` call, and folds the received lanes into a fresh
    store through the normal insert path. Returns (keys, counts,
    psum(route_dropped), psum(store_dropped)) -- both drop counters ride
    the caller's RetryController exactly like a counting round's."""
    key = ("reshard", cfg, mesh, axis_names, dtype_name, n_local, route_cap,
           store_cap)
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    num_pes = _mesh_pes(mesh, axis_names)
    grid = _topology_grid(cfg, mesh, axis_names)
    spec = _data_spec(axis_names)

    def local_reshard(keys_local, counts_local):
        sent = jnp.array(jnp.iinfo(keys_local.dtype).max, keys_local.dtype)
        valid = (keys_local != sent) & (counts_local > 0)
        owners = owner_pe(_ownership_keys(keys_local, cfg), num_pes)
        rr = aggregation.route_lanes(
            (keys_local, counts_local), ("word", "i32"), owners, valid,
            num_pes=num_pes, capacity=route_cap, axis_names=axis_names,
            grid=grid, impl=cfg.partition_impl, route2d="oneplan")
        st = countstore.store_insert(
            countstore.empty_store(store_cap, keys_local.dtype),
            rr.lanes[0], rr.lanes[1])
        ax = tuple(axis_names)
        return (st.keys, st.counts, jax.lax.psum(rr.overflow, ax),
                jax.lax.psum(st.dropped, ax))

    fn = jax.jit(compat.shard_map(
        local_reshard, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, spec, P(), P())))
    _EXEC_CACHE[key] = fn
    return fn


def _spill_route_executable(cfg: DAKCConfig, mesh: Mesh, axis_names, shape,
                            dtype_name: str, slack: float, n_bins: int,
                            fault=None):
    """One spill-tier chunk step: route chunk `cidx`'s lanes to owner PEs
    (the unchanged `_phase1_step` exchange -- zero extra wire bytes), then
    derive each received record's BIN in-trace: the recovered run minimizer
    for the superkmer transport (`minimizer.superkmer_minimizers`), the
    masked k-mer word otherwise, through the third hash family
    (`spill.bin_of`). Returns ((payload..., bins), psum'd stats); the host
    loop streams the lanes to `spill.SpillWriter` through the async
    double buffer. Hop 2 always runs padded and the route uncompacted here
    (the compact schemes' fallback rounds would interleave badly with the
    per-chunk host loop). `n_bins` is the resolved bin count (cfg.spill_bins
    or the engage-time spill.auto_bins sizing).
    """
    key = ("spill", cfg, mesh, axis_names, shape, dtype_name, slack, n_bins,
           fault)
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    num_pes = _mesh_pes(mesh, axis_names)
    grid = _topology_grid(cfg, mesh, axis_names)
    mode, cap_n, cap_h = _plan_caps(cfg, num_pes, shape, slack)
    spec = _data_spec(axis_names)
    mask = encoding.kmer_mask(cfg.k, cfg.bits_per_symbol)

    def local_spill(reads_local, cidx):
        chunks = _chunked(reads_local, cfg.chunk_reads)
        chunk = jax.lax.dynamic_index_in_dim(chunks, cidx, axis=0,
                                             keepdims=False)
        recv, (raw, sent_w, wire, ovf, h2, fl) = _phase1_step(
            chunk, cfg=cfg, num_pes=num_pes, cap_n=cap_n, cap_h=cap_h,
            mode=mode, axis_names=axis_names, grid=grid, hop2_caps=None,
            chunk_idx=cidx, fault=fault)
        if mode == "superkmer":
            words, lengths, _ = recv
            minz = minimizer.superkmer_minimizers(
                words, cfg.k, cfg.minimizer_len, cfg.bits_per_symbol,
                canonical=cfg.canonical, canonical_impl=cfg.canonical_impl,
                order=cfg.minimizer_order)
            lanes = (words, lengths.astype(jnp.int32),
                     spill.bin_of(minz, n_bins))
        else:
            kmers, cnts = _recv_pairs(recv, cfg=cfg, mode=mode)
            lanes = (kmers, cnts.astype(jnp.int32),
                     spill.bin_of(kmers & mask, n_bins))
        whi, wlo = _wire_add(jnp.int32(0), jnp.int32(0), wire)
        ax = tuple(axis_names)
        stats = tuple(jax.lax.psum(x, ax)
                      for x in (ovf.astype(jnp.int32), jnp.int32(0),
                                sent_w.astype(jnp.int32), whi, wlo,
                                raw.astype(jnp.int32), h2.astype(jnp.int32),
                                fl.astype(jnp.int32), jnp.int32(0),
                                jnp.int32(0)))
        return lanes, stats

    fn = jax.jit(compat.shard_map(
        local_spill, mesh=mesh, in_specs=(spec, P()),
        out_specs=((spec, spec, spec), (P(),) * STATS_FIELDS)))
    _EXEC_CACHE[key] = fn
    return fn


# Checkpoint-manifest compatibility: `_fingerprint` fields define what the
# stored WORDS mean (a mismatch is unrecoverable -> restore refuses);
# `_ownership_tag` fields define which PE owns a word (a mismatch, like a
# different PE count, just means the restore path reshards).
_FINGERPRINT_FIELDS = ("k", "bits_per_symbol", "canonical")


def _cfg_fingerprint(cfg: DAKCConfig) -> dict:
    return {f: getattr(cfg, f) for f in _FINGERPRINT_FIELDS}


def _ownership_tag(cfg: DAKCConfig) -> dict:
    sk = cfg.transport_impl == "superkmer"
    return {"transport_impl": cfg.transport_impl,
            "minimizer_len": cfg.minimizer_len if sk else None,
            # which m-mer wins a window decides the owning minimizer, so
            # the comparison order is part of the ownership family: a
            # restore across orders reshards (counts re-route exactly)
            "minimizer_order": cfg.minimizer_order if sk else None}


class KmerCounter:
    """Incremental DAKC: fold arbitrary batches into one persistent store.

    The streaming receiver's count store outlives a single `count_kmers`
    call: `update(reads)` runs the full Phase-1 pipeline (extract -> L3 ->
    route -> fold) for one batch, accumulating into the sharded store;
    `finalize()` compacts the store into the usual per-shard `AccumResult`.
    Two updates produce exactly the histogram of one concatenated
    `count_kmers` call. Receive memory is the store -- proportional to the
    DISTINCT k-mer count, never to how many batches streamed through.

    Overflow rounds per update run through `cfg.retry` (the same
    resilience.RetryController engine as `count_kmers`): a full store
    rehashes into doubled capacity (`store_grow`) and replays the batch
    (updates are functional -- the committed store is untouched until a
    batch folds cleanly); routing overflow doubles the slack for this and
    future batches; a compact hop-2 misfit moves this stream onto the
    padded tile. Per-batch replay counts come back in the returned
    `DAKCStats.retry_*`; give-ups raise the typed resilience errors with
    the round history attached. Store capacity starts from
    `cfg.store_capacity`, else from the first batch's two-pass sample
    estimate (`store_sizing='sample'`, the default) or its instance-count
    bound ('bound').

    Durability: `save()` checkpoints the sharded store plus every piece of
    sticky host state through train/checkpoint.py's atomic saver;
    `restore()` rebuilds a counter mid-stream. Restoring onto a different
    PE count (or a different ownership family) is an elastic reshard --
    see `restore`.
    """

    def __init__(self, mesh: Mesh, cfg: DAKCConfig,
                 axis_names: Sequence[str] = ("pe",)):
        if cfg.receiver_impl != "stream":
            raise ValueError("KmerCounter requires receiver_impl='stream'")
        self._mesh = mesh
        self._cfg = cfg
        self._axes = tuple(axis_names)
        self._num_pes = _mesh_pes(mesh, self._axes)
        self._dtype = encoding.kmer_dtype(cfg.k, cfg.bits_per_symbol)
        self._slack = cfg.slack
        self._store_cap: Optional[int] = cfg.store_capacity
        # compact hop-2 state: once a batch's hop-1 fill histogram misses
        # the compact tile, this stream stays on the padded fallback (the
        # second capacity) -- sticky, like the doubled routing slack.
        self._hop2_padded = False
        self._skeys = None
        self._scounts = None
        # the first batch's sampled global distinct-count estimate
        # (None before any update, or when the sample was uninformative);
        # consumed by the spill tier's auto bin sizing and persisted by
        # save/restore
        self._distinct_est: Optional[int] = None
        # host-side running totals across updates (Python ints: an
        # unbounded stream overruns int32 long before the store fills)
        self._raw = 0
        self._sent = 0
        self._wire_bytes = 0
        # lifetime per-destination hop-1 fill histogram (np.int64 once the
        # first batch lands; finalize() reports its imbalance)
        self._fill = None
        # cumulative per-cause replayed-round counts across the stream's
        # lifetime (finalize() reports them; save() persists them)
        self._retries = {c: 0 for c in resilience.CAUSES}
        self._n_updates = 0
        # bounded lifetime round history (resilience first-plus-ring
        # discipline): seeds every controller this counter builds, rides
        # save/restore, so a post-restore give-up carries rounds spanning
        # the restore boundary
        self._rounds: list = []
        # the spill tier (core/spill.py), None until it engages
        self._spill: Optional[spill.SpillWriter] = None
        self._bins_folded = 0
        # stats of the most recent count()/contains() batch
        # (core/query.py QueryStats; None before any query)
        self.last_query_stats = None
        # epoch-pinned committed generation (countstore.StoreSnapshot):
        # count()/contains() read ONLY this, never the live references
        # above, so a query racing an in-flight rehash / fold / spill
        # replay answers from the last committed histogram exactly
        self._gen = 0
        self._committed: Optional[countstore.StoreSnapshot] = None
        # lazy per-counter LRU of materialized bin shards for the
        # spilled-bin query tier (query.BinShardCache)
        self._bin_cache = None

    @property
    def store_capacity(self) -> Optional[int]:
        return self._store_cap

    def _sharding(self) -> NamedSharding:
        return NamedSharding(self._mesh, _data_spec(self._axes))

    def _alloc(self, reads) -> None:
        cfg = self._cfg
        if self._distinct_est is None and cfg.store_sizing == "sample":
            self._distinct_est = _sampled_distinct_estimate(reads, cfg,
                                                            self._num_pes)
        if self._store_cap is None:
            if cfg.store_capacity is None and self._distinct_est is not None:
                cap = plan_capacity(self._distinct_est, self._num_pes,
                                    cfg.store_slack)
                self._store_cap = _within_ceiling(
                    1 << (cap - 1).bit_length(), cfg)
            else:
                self._store_cap = _resolve_store_capacity(reads, cfg,
                                                          self._num_pes)
        self._alloc_store()

    def _alloc_store(self) -> None:
        sent = jnp.iinfo(self._dtype).max
        n = self._num_pes * self._store_cap
        self._skeys = jax.device_put(jnp.full((n,), sent, self._dtype),
                                     self._sharding())
        self._scounts = jax.device_put(jnp.zeros((n,), jnp.int32),
                                       self._sharding())

    def _grow(self, new_cap: int) -> None:
        """Rehash the committed store into `new_cap` slots per PE (the
        rehash round; ceilings live in `cfg.retry`, not here)."""
        fn = _grow_executable(self._cfg, self._mesh, self._axes, new_cap,
                              self._store_cap)
        nk, nc, dropped = fn(self._skeys, self._scounts)
        if int(dropped) != 0:   # unreachable unless store state corrupted
            raise resilience.RehashInvariantBroken(
                f"rehash into {new_cap} slots/PE dropped {int(dropped)} "
                f"live entries",
                self._rounds, dict(self._retries), dropped=int(dropped))
        self._skeys, self._scounts = nk, nc
        self._store_cap = new_cap

    def _publish(self) -> None:
        """Publish the current store state as the committed generation.

        Called exactly once per clean batch commit (and on restore) --
        one reference assignment, so it is atomic with respect to any
        concurrent `count()`. jax arrays are immutable and sealed spill
        segments are immutable files, so the snapshot stays valid however
        the live references move afterwards (`_grow`, `_engage_spill`,
        a failed replay, ...)."""
        self._gen += 1
        self._committed = countstore.StoreSnapshot(
            gen=self._gen, keys=self._skeys, counts=self._scounts,
            store_cap=self._store_cap,
            spill_state=None if self._spill is None else self._spill.state())

    def update(self, reads: jax.Array) -> DAKCStats:
        """Fold one (n_reads, m) batch into the store; returns this batch's
        wire statistics (post-retry: overflow fields are the final clean
        round's zeros, with the replay counts in the retry_* fields).

        With `cfg.spill` enabled the batch may instead ride the disk
        tier: 'always' spills from the first batch; 'auto' runs in-core
        until the rehash ladder hits `store_cap_ceiling`, then exports
        the committed store to bins and replays THIS batch through the
        spill path (exactly-once: the committed store is untouched until
        a batch folds cleanly, so nothing double-counts)."""
        with TraceAnnotation("kc.update", batch=self._n_updates):
            plan = self._cfg.faults
            if (plan is not None and plan.site == "update_fail"
                    and self._n_updates == plan.update_n):
                # the preemption drill: die host-side before anything
                # commits (the committed store, totals and counters are
                # untouched -- the caller restores from its last
                # checkpoint and replays)
                raise resilience.InjectedFault(
                    f"injected failure at update #{self._n_updates} "
                    f"(FaultPlan site='update_fail')")
            if self._spill is None and self._cfg.spill == "always":
                self._engage_spill()
            if self._spill is not None:
                return self._spill_update(reads)
            try:
                return self._incore_update(reads)
            except resilience.CapacityExhausted as e:
                if (self._cfg.spill != "auto"
                        or e.cause != resilience.STORE_REHASH):
                    raise
                # tier 3 (graceful degradation): the rehash ladder ran out
                # of HBM -- export the committed store to disk bins and
                # replay this batch out-of-core. The ladder's rounds seed
                # the spill controllers' history, so later give-ups still
                # show WHY the tier engaged.
                self._rounds = list(e.rounds)
                for cause, n in e.counts.items():
                    self._retries[cause] += n
                self._engage_spill()
                return self._spill_update(reads)

    def _incore_update(self, reads: jax.Array) -> DAKCStats:
        batch = self._n_updates
        with TraceAnnotation("kc.plan", batch=batch, round=0):
            if self._skeys is None:
                self._alloc(reads)
            plan = self._cfg.faults
            shape = tuple(reads.shape)
            engaged = _hop2_engaged(self._cfg) and not self._hop2_padded
            hop2_est = None
            if engaged or _compact_engaged(self._cfg):
                mode = _plan_caps(self._cfg, self._num_pes, shape,
                                  self._slack)[0]
                hop2_est = _chunk_valid_estimate(reads, self._cfg, mode,
                                                 shape, self._num_pes)
            ctrl = resilience.RetryController(
                self._cfg.retry, slack=self._slack,
                store_cap=self._store_cap, hop2_padded=not engaged,
                history=self._rounds)
        while True:
            rnd = ctrl.attempts
            if ctrl.store_cap != self._store_cap:
                # rehash round; then replay
                with TraceAnnotation("kc.grow", batch=batch, round=rnd):
                    self._grow(ctrl.store_cap)
            with TraceAnnotation("kc.plan", batch=batch, round=rnd):
                hop2_caps = _retry_hop2_caps(reads, self._cfg,
                                             self._num_pes, shape, ctrl,
                                             hop2_est)
                compact_caps = _resolve_compact(reads, self._cfg,
                                                self._num_pes, shape,
                                                ctrl.slack, est=hop2_est)
                fault = resilience.active_trace_fault(plan, ctrl.attempts)
                fn = _update_executable(
                    self._cfg, self._mesh, self._axes, shape,
                    str(reads.dtype), ctrl.slack, self._store_cap,
                    hop2_caps=hop2_caps, compact_caps=compact_caps,
                    fault=fault)
            with TraceAnnotation("kc.run", batch=batch, round=rnd):
                nk, nc, raw_stats = fn(reads, self._skeys, self._scounts)
            # the host's first read of the stats waits for the device
            with TraceAnnotation("kc.sync", batch=batch, round=rnd):
                stats = _host_stats(self._cfg, raw_stats)
                again = ctrl.observe(
                    route_dropped=int(stats.overflow),
                    store_dropped=int(stats.store_overflow),
                    hop2_dropped=int(stats.hop2_dropped))
            if not again:
                break
        with TraceAnnotation("kc.commit", batch=batch,
                             insert_groups=int(stats.insert_groups),
                             insert_fallbacks=int(stats.insert_fallbacks),
                             wire_bytes=int(stats.wire_bytes),
                             load_max_over_mean=stats.load_max_over_mean):
            self._skeys, self._scounts = nk, nc
            # write the controller's final knobs back into the sticky
            # state (doubled slack and the padded-hop-2 fallback persist
            # for future batches; the grown store already committed via
            # _grow)
            self._slack = ctrl.slack
            self._rounds = ctrl.rounds
            if _hop2_engaged(self._cfg):
                self._hop2_padded = ctrl.hop2_padded
            for cause, n in ctrl.counts.items():
                self._retries[cause] += n
            self._n_updates += 1
            self._raw += int(stats.raw_kmers)
            self._sent += int(stats.sent_words)
            self._wire_bytes += int(stats.wire_bytes)
            batch_fill = np.asarray(raw_stats[7], dtype=np.int64)
            self._fill = (batch_fill if self._fill is None
                          else self._fill + batch_fill)
            self._publish()
            return _stamp_retries(stats, ctrl.counts)

    # --- the spill tier (core/spill.py) --------------------------------------

    # Once the tier engages, the resident store only needs to exist for
    # the API invariants (finalize/save run against it); 8 slots per PE
    # keeps every executable tiny.
    _SPILL_STORE_CAP = 8

    def _spill_fault(self) -> Optional[resilience.FaultPlan]:
        plan = self._cfg.faults
        if plan is not None and plan.site in ("spill_write", "bin_corrupt"):
            return plan
        return None

    def _engage_spill(self) -> None:
        """Stand up the spill writer; if a committed store exists, export
        its live (key, count) entries into their bins and shrink it --
        from here on batches spill and `finalize()` drains bins."""
        cfg = self._cfg
        n_bins = cfg.spill_bins
        if n_bins is None:
            # size the disk partition so each bin's drain-time fold lands
            # near the store capacity the rehash ladder could afford
            n_bins = spill.auto_bins(self._distinct_est, self._num_pes,
                                     self._store_cap, cfg.store_slack)
        meta = {"transport": cfg.transport_impl, "k": cfg.k,
                "bits_per_symbol": cfg.bits_per_symbol,
                "canonical": cfg.canonical,
                "minimizer_len": cfg.minimizer_len,
                "minimizer_order": cfg.minimizer_order}
        self._spill = spill.SpillWriter(
            cfg.spill_dir, n_bins, meta=meta,
            flush_bytes=cfg.spill_flush_bytes, fault=self._spill_fault())
        if self._skeys is not None:
            keys = np.asarray(self._skeys)
            counts = np.asarray(self._scounts)
            sent = np.iinfo(keys.dtype).max
            live = (keys != sent) & (counts > 0)
            if live.any():
                k_live = keys[live]
                okeys = _ownership_keys(jnp.asarray(k_live), cfg)
                bins = np.asarray(spill.bin_of(okeys, n_bins))
                self._spill.add_pairs(bins, k_live, counts[live])
            self._spill.commit()
            # release the pressured store: the tier owns the counts now
            self._store_cap = self._SPILL_STORE_CAP
            self._alloc_store()
        else:
            # spill='always' before any in-core batch: the resident store
            # never held counts, but the API invariants (finalize/save)
            # still run against one -- allocate it at the tiny cap
            self._store_cap = self._SPILL_STORE_CAP
            self._alloc_store()

    def _absorb_spill(self, host_lanes, mode: str) -> None:
        """Feed one materialized chunk's host lanes to the writer, dropping
        tile padding (zero length header / zero count)."""
        if mode == "superkmer":
            words, lengths, bins = host_lanes
            live = lengths > 0
            self._spill.add_superkmers(bins[live], words[live], lengths[live])
        else:
            kmers, cnts, bins = host_lanes
            live = cnts > 0
            self._spill.add_pairs(bins[live], kmers[live], cnts[live])

    def _spill_update(self, reads: jax.Array) -> DAKCStats:
        """Partition-phase update: run each chunk's exchange on device,
        stream the received lanes host-side through the bounded async
        double buffer, and append them to bin segments. Nothing enters
        the manifest until the whole batch routed cleanly (a route
        overflow aborts the pending segments and replays at doubled
        slack), so replays never double-spill."""
        cfg = self._cfg
        w = self._spill
        shape = tuple(reads.shape)
        n_chunks = (shape[0] // self._num_pes) // cfg.chunk_reads
        mode = _plan_caps(cfg, self._num_pes, shape, self._slack)[0]
        plan = cfg.faults
        ctrl = resilience.RetryController(
            cfg.retry, slack=self._slack,
            store_cap=self._store_cap or self._SPILL_STORE_CAP,
            hop2_padded=True, history=self._rounds)
        while True:
            w.begin_batch()
            fault = resilience.active_trace_fault(plan, ctrl.attempts)
            fn = _spill_route_executable(cfg, self._mesh, self._axes, shape,
                                         str(reads.dtype), ctrl.slack,
                                         w.n_bins, fault=fault)
            copier = spill.AsyncHostCopier(cfg.spill_host_budget_bytes)
            parts = []
            for c in range(n_chunks):
                lanes, st = fn(reads, jnp.int32(c))
                parts.append(st)       # device scalars; int() deferred so
                for host in copier.submit(lanes):  # D2H overlaps compute
                    self._absorb_spill(host, mode)
            for host in copier.drain():
                self._absorb_spill(host, mode)
            rs = [sum(int(p[i]) for p in parts) for i in range(7)]
            fill = np.sum([np.asarray(p[7]) for p in parts], axis=0)
            if not ctrl.observe(route_dropped=rs[0], hop2_dropped=rs[6]):
                w.commit()             # seal this batch into the manifest
                break
            w.abort_batch()            # pending segments die with the round
        self._slack = ctrl.slack
        self._rounds = ctrl.rounds
        for cause, n in ctrl.counts.items():
            self._retries[cause] += n
        wire = (rs[3] << _WIRE_SHIFT) + rs[4]
        self._n_updates += 1
        self._raw += rs[5]
        self._sent += rs[2]
        self._wire_bytes += wire
        fill = fill.astype(np.int64)
        self._fill = fill if self._fill is None else self._fill + fill
        self._publish()
        lmm, p99 = _imbalance(fill)
        stats = DAKCStats(
            overflow=0, sent_words=rs[2], wire_bytes=np.int64(wire),
            raw_kmers=rs[5], num_global_syncs=3, store_overflow=0,
            hop2_dropped=rs[6], load_max_over_mean=lmm, owner_fill_p99=p99,
            spilled_bins=w.spilled_bins, spilled_bytes=w.spilled_bytes,
            bins_folded=self._bins_folded)
        return _stamp_retries(stats, ctrl.counts)

    def _bin_pairs(self, b: int, segments=None):
        """Read + decode one bin's committed records into host (keys,
        counts) arrays, or None for an empty bin. `segments` pins the
        manifest view (a snapshot's `spill_state['segments']`) so the
        spilled-bin query tier reads its own committed generation; None
        reads the live manifest (the drain path). Super-k-mer segments
        decode back to k-mer pairs here, so every consumer folds one
        uniform record stream."""
        cfg = self._cfg
        keys_l, cnts_l = [], []
        for kind, arrays in self._spill.read_bin(b, segments=segments):
            if kind == "pairs":
                keys_l.append(np.asarray(arrays["keys"], dtype=self._dtype))
                cnts_l.append(np.asarray(arrays["counts"], dtype=np.int32))
            else:
                kk, cc = minimizer.superkmer_to_kmers(
                    jnp.asarray(arrays["words"]),
                    jnp.asarray(arrays["lengths"]), cfg.k,
                    cfg.minimizer_len, cfg.bits_per_symbol,
                    canonical=cfg.canonical,
                    canonical_impl=cfg.canonical_impl)
                kk, cc = np.asarray(kk), np.asarray(cc)
                m = cc > 0
                keys_l.append(kk[m])
                cnts_l.append(cc[m].astype(np.int32))
        if not keys_l:
            return None
        return np.concatenate(keys_l), np.concatenate(cnts_l)

    def _drain_bins(self) -> Tuple[AccumResult, int]:
        """Fold phase: count each bin independently -- read + checksum its
        segments (-> `spill.SpillCorrupt`), decode super-k-mer slots back
        to k-mers, route the records to their owner PEs through the
        elastic fold path, and compact. Per-bin per-shard prefixes
        concatenate (then sort per shard) into the standard AccumResult
        layout -- bins partition k-mer space, so this IS the exact global
        histogram. Runs on the CURRENT mesh: a spilled run restored onto
        a different PE count drains elastically for free."""
        cfg = self._cfg
        w = self._spill
        nsh = self._num_pes
        sent = int(jnp.iinfo(self._dtype).max)
        shard_u = [[] for _ in range(nsh)]
        shard_c = [[] for _ in range(nsh)]
        folded = 0
        for b in range(w.n_bins):
            pairs = self._bin_pairs(b)
            if pairs is None:
                continue
            keys, cnts = pairs
            nk, nc, cap = self._fold_pairs(keys, cnts)
            res = _finalize_executable(cfg, self._mesh, self._axes,
                                       cap)(nk, nc)
            u = np.asarray(res.unique).reshape(nsh, cap)
            c = np.asarray(res.counts).reshape(nsh, cap)
            nu = np.asarray(res.num_unique)
            for s in range(nsh):
                n = int(nu[s])
                shard_u[s].append(u[s, :n])
                shard_c[s].append(c[s, :n])
            folded += 1
        L = max([sum(x.size for x in shard_u[s]) for s in range(nsh)] + [1])
        out_u = np.full((nsh * L,), sent, dtype=self._dtype)
        out_c = np.zeros((nsh * L,), np.int32)
        out_n = np.zeros((nsh,), np.int32)
        for s in range(nsh):
            if not shard_u[s]:
                continue
            uu = np.concatenate(shard_u[s])
            cc = np.concatenate(shard_c[s])
            order = np.argsort(uu, kind="stable")
            uu, cc = uu[order], cc[order]
            out_u[s * L:s * L + uu.size] = uu
            out_c[s * L:s * L + cc.size] = cc
            out_n[s] = uu.size
        # jnp-backed like the in-core finalize, so callers can
        # block_until_ready / device_put uniformly
        return AccumResult(unique=jnp.asarray(out_u),
                           counts=jnp.asarray(out_c),
                           num_unique=jnp.asarray(out_n)), folded

    def finalize(self) -> Tuple[AccumResult, DAKCStats]:
        """Compact the store into the per-shard histogram (callable more
        than once; the store keeps accepting updates in between). With
        the spill tier engaged this is the DRAIN: per-bin fold + compact
        (`_drain_bins`), host-resident AccumResult, same layout."""
        with TraceAnnotation("kc.finalize"):
            lmm, p99 = (_imbalance(self._fill) if self._fill is not None
                        else (0.0, 0))
            if self._spill is not None:
                result, folded = self._drain_bins()
                self._bins_folded = folded
                stats = DAKCStats(
                    overflow=np.int64(0), sent_words=np.int64(self._sent),
                    wire_bytes=np.int64(self._wire_bytes),
                    raw_kmers=np.int64(self._raw), num_global_syncs=3,
                    store_overflow=np.int64(0),
                    load_max_over_mean=lmm, owner_fill_p99=p99,
                    spilled_bins=self._spill.spilled_bins,
                    spilled_bytes=self._spill.spilled_bytes,
                    bins_folded=folded)
                return result, _stamp_retries(stats, self._retries)
            if self._skeys is None:
                raise RuntimeError("KmerCounter.finalize before any update")
            fn = _finalize_executable(self._cfg, self._mesh, self._axes,
                                      self._store_cap)
            result = fn(self._skeys, self._scounts)
            # int64 throughout: an unbounded stream's cumulative totals outgrow
            # int32 long before anything else breaks. retry_* counters are the
            # stream's LIFETIME totals (per-batch counts ride each update()'s
            # returned stats).
            stats = DAKCStats(
                overflow=np.int64(0), sent_words=np.int64(self._sent),
                wire_bytes=np.int64(self._wire_bytes),
                raw_kmers=np.int64(self._raw), num_global_syncs=3,
                store_overflow=np.int64(0),
                load_max_over_mean=lmm, owner_fill_p99=p99)
            return result, _stamp_retries(stats, self._retries)

    # --- the query path (core/query.py) --------------------------------------

    def count(self, kmers) -> np.ndarray:
        """Batched lookup: per-query occurrence counts from the committed
        store generation, in request order (0 = never counted).

        `kmers` is (n,) packed words or (n, k) base codes; packing and
        canonicalization match the counting path exactly, so the returned
        counts equal lookups against the `finalize()` histogram for ANY
        query set (misses and duplicates included). Read-only -- the
        store is untouched and updates may continue afterwards. Each
        call's `query.QueryStats` lands in `self.last_query_stats`.

        Serves the epoch-pinned `countstore.StoreSnapshot` published at
        the last batch commit, NEVER the live references: a query racing
        an in-flight rehash, elastic fold, or spill replay answers from
        the last committed histogram exactly. A spill-engaged generation
        serves through the spilled-bin tier (`query.query_spilled_counts`
        -- vestigial-store probe, then per-bin residual lookups against
        on-demand bin folds cached in a `query_bin_cache_bytes`-bounded
        LRU); under the strict opt-in `spill_query='refuse'` it raises
        the typed `query.QueryUnavailable` instead.

        Executable reuse: batch sizes are bucketed by the pow2 per-PE
        slot count, so a serving stream retraces once per bucket and
        store generation, never per request.
        """
        from repro.core import query as query_lib
        snap = self._committed
        if snap is None:
            raise RuntimeError("KmerCounter.count before any update")
        if snap.spill_state is not None:
            # dispatch on the COMMITTED generation, not self._spill: an
            # auto-engage whose first spill replay died leaves the live
            # tier engaged while the committed histogram is still in-core
            if self._cfg.spill_query == "refuse":
                raise query_lib.QueryUnavailable(
                    "counter's committed generation has an engaged spill "
                    "tier and cfg.spill_query='refuse' opts out of the "
                    "spilled-bin query tier's on-demand folds")
            counts, stats = query_lib.query_spilled_counts(self, snap,
                                                           kmers)
        else:
            counts, stats = query_lib.query_counts(
                kmers, self._mesh, self._cfg, snap.keys, snap.counts,
                axis_names=self._axes)
        self.last_query_stats = stats
        return counts

    def contains(self, kmers) -> np.ndarray:
        """Batched membership: `count(kmers) > 0`, request order."""
        return self.count(kmers) > 0

    # --- durability ----------------------------------------------------------

    def save(self, ckpt_dir: Optional[str] = None, step: int = 0, *,
             saver=None, keep: int = 3):
        """Checkpoint the live store plus every piece of sticky host state.

        Rides train/checkpoint.py: stage-then-rename, so a crash mid-write
        (including an injected `FaultPlan(site='ckpt_write')`) leaves prior
        checkpoints intact and `latest_step` pointing at the last complete
        one. Pass `saver=AsyncSaver(...)` for the overlapped path (returns
        None; the saver's `wait()` surfaces write failures), or `ckpt_dir`
        for a blocking save (returns the checkpoint directory path).
        """
        if self._skeys is None:
            raise RuntimeError("KmerCounter.save before any update")
        if (ckpt_dir is None) == (saver is None):
            raise ValueError("pass exactly one of ckpt_dir / saver")
        from repro.train import checkpoint as ckpt_lib
        trees = {"store": {"keys": self._skeys, "counts": self._scounts}}
        extra = {
            "format": 1,
            "fingerprint": _cfg_fingerprint(self._cfg),
            "ownership": _ownership_tag(self._cfg),
            "num_pes": self._num_pes,
            "store_cap": self._store_cap,
            "slack": self._slack,
            "hop2_padded": self._hop2_padded,
            "raw": self._raw,
            "sent": self._sent,
            "wire_bytes": self._wire_bytes,
            "n_updates": self._n_updates,
            "distinct_est": self._distinct_est,
            "retries": dict(self._retries),
            # bounded round history + the spill tier's manifest: a run
            # killed mid-spill restores with the checkpoint's view of the
            # committed bins (core/spill.py durability contract) and its
            # retry history spanning the restore boundary
            "rounds": resilience.rounds_to_json(self._rounds),
            "spill": None if self._spill is None else self._spill.state(),
        }
        if saver is not None:
            saver.save(step, trees, extra=extra)
            return None
        plan = self._cfg.faults
        fault = plan if (plan is not None
                         and plan.site == "ckpt_write") else None
        return ckpt_lib.save(ckpt_dir, step, trees, extra=extra, keep=keep,
                             fault=fault)

    @classmethod
    def restore(cls, ckpt_dir: str, mesh: Mesh, cfg: DAKCConfig,
                axis_names: Sequence[str] = ("pe",),
                step: Optional[int] = None) -> "KmerCounter":
        """Rebuild a counter mid-stream from a checkpoint.

        If the new mesh has the same PE count and ownership family
        (transport_impl + minimizer length) as the saved one, the sharded
        store is loaded in place. Otherwise this is an elastic reshard:
        `owner_pe` is a pure function of P, so every live (key, count)
        entry is re-routed to its new owner in one `route_lanes` exchange
        and folded through the ordinary insert path into a fresh store --
        counts merge exactly, order-independent. The cfg must agree with
        the saved fingerprint on k / bits_per_symbol / canonical (anything
        else changes what the stored words MEAN).
        """
        from repro.train import checkpoint as ckpt_lib
        if step is None:
            step = ckpt_lib.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(
                    f"no complete checkpoint under {ckpt_dir}")
        dt = encoding.kmer_dtype(cfg.k, cfg.bits_per_symbol)
        templates = {"store": {"keys": np.zeros(0, dt),
                               "counts": np.zeros(0, np.int32)}}
        trees, extra = ckpt_lib.restore(ckpt_dir, step, templates)
        saved_fp = extra["fingerprint"]
        want_fp = _cfg_fingerprint(cfg)
        if saved_fp != want_fp:
            raise ValueError(
                f"checkpoint fingerprint {saved_fp} is incompatible with "
                f"cfg {want_fp}: the stored words would be reinterpreted")
        self = cls(mesh, cfg, axis_names)
        self._raw = int(extra["raw"])
        self._sent = int(extra["sent"])
        self._wire_bytes = int(extra["wire_bytes"])
        self._n_updates = int(extra["n_updates"])
        de = extra.get("distinct_est")
        self._distinct_est = None if de is None else int(de)
        saved_retries = extra.get("retries", {})
        self._retries = {c: int(saved_retries.get(c, 0))
                         for c in resilience.CAUSES}
        self._slack = float(extra["slack"])
        self._hop2_padded = bool(extra["hop2_padded"])
        self._rounds = resilience.rounds_from_json(extra.get("rounds"))
        sp = extra.get("spill")
        if sp is not None:
            if cfg.spill == "off" or cfg.spill_dir is None:
                raise ValueError(
                    "checkpoint has an engaged spill tier; restoring it "
                    "needs a cfg with spill enabled and the spill_dir the "
                    "bins live under")
            # spill_bins=None adopts the checkpoint's partition as-is;
            # an explicit pin must match it (bins partition k-mer space)
            if (cfg.spill_bins is not None
                    and int(sp["n_bins"]) != cfg.spill_bins):
                raise ValueError(
                    f"checkpoint spilled into {sp['n_bins']} bins; "
                    f"cfg.spill_bins={cfg.spill_bins} would repartition "
                    f"k-mer space mid-run")
            self._spill = spill.SpillWriter.attach(
                cfg.spill_dir, sp, flush_bytes=cfg.spill_flush_bytes,
                fault=self._spill_fault())
        keys_np = np.asarray(trees["store"]["keys"], dtype=dt)
        counts_np = np.asarray(trees["store"]["counts"], dtype=np.int32)
        if (self._num_pes == int(extra["num_pes"])
                and extra["ownership"] == _ownership_tag(cfg)):
            self._store_cap = int(extra["store_cap"])
            self._skeys = jax.device_put(jnp.asarray(keys_np),
                                         self._sharding())
            self._scounts = jax.device_put(jnp.asarray(counts_np),
                                           self._sharding())
        else:
            self._reshard_from(keys_np, counts_np)
        self._publish()
        return self

    def _fold_pairs(self, keys: np.ndarray, counts: np.ndarray, *,
                    store_cap: Optional[int] = None, sticky: bool = False):
        """Route host (key, count) records to their owner PEs and fold
        them into a fresh store -- the one fold engine behind elastic
        restore (`_reshard_from`) and the spill drain (`_drain_bins`).

        One `route_lanes` exchange (the reshard executable) moves every
        live record to its owner under THIS mesh's PE count; overflow on
        either side retries through `cfg.retry` like any other round (a
        fresh store per attempt -- no rehash needed, capacity is just
        re-planned). Per-PE record counts and the store capacity are
        pow2-quantized so every bin / batch shape reuses one cached
        executable. `sticky=True` commits the controller's final slack to
        the counter (the restore path); retries and round history are
        recorded either way. Returns (keys, counts, store_cap)."""
        n_pes = self._num_pes
        sent = int(np.iinfo(keys.dtype).max)
        live = int(((keys != sent) & (counts > 0)).sum())
        if store_cap is None:
            store_cap = _pow2ceil(plan_capacity(
                max(live, 1), n_pes, self._cfg.store_slack))
        n_local = _pow2ceil(max(1, -(-keys.shape[0] // n_pes)))
        n_pad = n_local * n_pes
        gk = np.full((n_pad,), sent, keys.dtype)
        gc = np.zeros((n_pad,), np.int32)
        gk[:keys.shape[0]] = keys
        gc[:counts.shape[0]] = counts
        gk = jax.device_put(jnp.asarray(gk), self._sharding())
        gc = jax.device_put(jnp.asarray(gc), self._sharding())
        ctrl = resilience.RetryController(
            self._cfg.retry, slack=self._slack, store_cap=store_cap,
            hop2_padded=True, history=self._rounds)
        while True:
            store_cap = ctrl.store_cap   # fresh store each attempt
            route_cap = plan_capacity(n_local, n_pes, ctrl.slack)
            fn = _reshard_executable(self._cfg, self._mesh, self._axes,
                                     str(keys.dtype), n_local, route_cap,
                                     store_cap)
            nk, nc, route_drop, store_drop = fn(gk, gc)
            if not ctrl.observe(route_dropped=int(route_drop),
                                store_dropped=int(store_drop)):
                break
        if sticky:
            self._slack = ctrl.slack
        self._rounds = ctrl.rounds
        for cause, n in ctrl.counts.items():
            self._retries[cause] += n
        return nk, nc, store_cap

    def _reshard_from(self, keys: np.ndarray, counts: np.ndarray) -> None:
        """Re-route saved (key, count) entries onto this mesh's ownership
        (see `_fold_pairs`) and commit the folded store."""
        if self._store_cap is None:
            sent = int(np.iinfo(keys.dtype).max)
            live = int(((keys != sent) & (counts > 0)).sum())
            self._store_cap = _pow2ceil(plan_capacity(
                max(live, 1), self._num_pes, self._cfg.store_slack))
        nk, nc, cap = self._fold_pairs(keys, counts,
                                       store_cap=self._store_cap,
                                       sticky=True)
        self._skeys, self._scounts = nk, nc
        self._store_cap = cap
