"""Multilevel message aggregation (paper Sec. IV, Alg. 4) adapted to SPMD.

TPU adaptation (DESIGN.md Sec. 2):

- L0/L1 (runtime buffering)  -> chunked processing: one fused all_to_all per
  chunk instead of per-k-mer traffic; XLA double-buffers the scan so chunk i's
  collective overlaps chunk i+1's compute.
- L2 (header amortization)   -> destination-major dense tiles `(P, capacity)`.
  SPMD collectives carry no per-packet headers; the slot position *is* the
  route, so the 32-bit-header overhead the paper fights goes to exactly zero.
- L3 (heavy-hitter compression) -> local sort+accumulate of each chunk before
  sending; counts packed into spare high bits (encoding.pack_counts). Under
  skew this is ALSO what keeps the static per-destination capacity safe:
  10^5 copies of (AATGG)n collapse to one {kmer,count} word instead of
  overflowing one destination's tile.

Receiver side: with the default streaming receiver
(fabsp.DAKCConfig.receiver_impl='stream') each tile built here lives for
exactly one scan step -- `l3_decompress` splits it back into (kmer, count)
lanes and the pair is folded straight into the carry-resident count store
(core/countstore.py, the paper's Alg. 3 hash-table insert). The 'stacked'
oracle instead stacks every chunk's tile for one deferred sort -- receive
memory O(n_chunks * P * capacity) vs the store's fixed footprint.

Static-shape discipline: tiles are fixed `(P, capacity)`; entries beyond a
destination's fill are the sort-to-the-end sentinel; overflow (entries dropped
because a destination exceeded capacity) is *counted and returned* -- callers
either assert it is zero (tests; uniform/hash-spread traffic) or run the
overflow round (`fabsp.count_kmers` does).

Data path (the L2 hot loop): `route_lanes` is THE routing implementation --
every transport in the repo (the 'kmer' and 'superkmer' DAKC transports,
fabsp._phase1_step; the BSP baseline's per-batch exchange, bsp._batch_round)
is one call to it. A route takes an arbitrary LIST of payload lanes (packed
k-mer words, super-k-mer payload words, int32 length headers or HEAVY
counts) plus one owner map, buckets every lane off ONE `PartitionPlan`
(per-tile Pallas owner histogram + exclusive-prefix offsets + stable ranks;
kernels/radix_partition.py -- sort-free, one scatter per lane), runs the
1d or hierarchical 2d all_to_all, and accounts the exact wire bytes of
every lane in one place (per-slot byte width summed over lanes; headers and
counts are int32 = 4 bytes, word lanes their dtype width). `route_tiles` is
the pre-collective stage (the L2 tile build), exposed for the conformance
property tests (tests/test_routing.py) and for `bucket_by_owner`, the
two-lane wrapper kept for its external users (the partition-plan test
surfaces).

Pre-route compaction seam (`compact_lanes`): positional extraction layouts
arrive mostly invalid (one slot per k-mer position, valid only at run
starts / compression survivors), so callers may first shrink the lane set
to its occupied prefix with a stable 2-bucket partition -- validity as a
1-bit digit through the SAME PartitionPlan machinery -- and route the
compacted lanes at a capacity re-derived from the measured valid density
(fabsp.DAKCConfig.compact_impl='prefix'). The seam sits strictly BETWEEN
extraction and `route_lanes`; owners are computed before it and ride
through as an 'i32' lane, so routing semantics are untouched.

2d topologies: the 'oneplan' route buckets ONCE by the two-digit
(dest_col, dest_row) key so hop 2 is a plain transpose + all_to_all served
by the same plan; the 'perhop' oracle re-derives owners from the received
word lane and re-plans per hop. Hop 2 may additionally be OCCUPANCY-AWARE
(`hop2_capacity`): each bucket row of the hop-1 tile is a contiguous valid
prefix, so the route ships only the first `hop2_capacity` slots per row on
the second hop -- a smaller measured-occupancy tile. Whether the hop-1 fill
histogram actually fits is checked from the sender-side fills (exact after
the stats psum, no tile re-scan); entries past the compact capacity are
counted in `RouteResult.hop2_dropped` and ride the caller's overflow round
(fabsp falls back to the padded tile -- the KMC 3-style two-capacity
scheme).

`impl='argsort'` swaps the plan builder for the stable-argsort oracle
(kernels/ref.partition_plan_ref); both plans drive the SAME tile build, so
the two impls are bit-identical by construction.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import encoding
from repro.core.sort import accumulate, radix_sort
from repro.kernels import ops


class BucketResult(NamedTuple):
    tile: jax.Array       # (P, capacity) words, sentinel-padded
    fill: jax.Array       # (P,) int32 valid entries per destination
    overflow: jax.Array   # () int32 dropped entries (capacity exceeded)
    counts: Optional[jax.Array] = None  # (P, capacity) int32 lane (HEAVY)


class RouteResult(NamedTuple):
    """One `route_lanes` exchange, as seen by this PE.

    `sent_valid`, `wire_bytes` and the drop counters follow the fill-aware
    convention: each PE charges its OWN bucket fills for every hop (the
    exchange preserves the global totals, so the psum'd stats are exact;
    per-PE they need not equal 'what I received').
    """
    lanes: Tuple[jax.Array, ...]  # received lanes, each flat (recv_slots,)
    sent_valid: jax.Array         # () int32 valid slots moved (all hops)
    wire_bytes: jax.Array         # () int32 exact padded bytes moved
    overflow: jax.Array           # () int32 bucket-capacity drops
    hop2_dropped: jax.Array       # () int32 compact-hop-2 drops (0 unless
                                  # 2d 'oneplan' with hop2_capacity set)
    fill: Optional[jax.Array] = None
                                  # (num_pes,) int32 hop-1 per-destination
                                  # valid counts (this PE's buckets; psum
                                  # for the global histogram). Under the 2d
                                  # 'oneplan' route the axis is the fixed
                                  # (dest_col, dest_row) permutation of PE
                                  # ids -- harmless for any permutation-
                                  # invariant statistic (max/mean/p99). The
                                  # 'perhop' oracle re-plans per hop and
                                  # reports zeros.


def lane_wire_bytes(lanes, kinds) -> int:
    """Exact wire bytes of ONE routed tile slot: the single source of truth
    for per-lane byte accounting (every transport's wire stat derives from
    it). 'word' lanes cost their dtype width; 'i32' header/count lanes 4."""
    if len(lanes) != len(kinds):
        raise ValueError(f"{len(lanes)} lanes vs {len(kinds)} kinds")
    total = 0
    for lane, kind in zip(lanes, kinds):
        if kind == "word":
            total += jnp.iinfo(lane.dtype).bits // 8
        elif kind == "i32":
            total += 4
        else:
            raise ValueError(f"unknown lane kind {kind!r}")
    return total


def route_tiles(lanes, kinds, owners, valid, num_pes: int, capacity: int, *,
                plan: Optional[ops.PartitionPlan] = None,
                impl: str = "radix"):
    """Bucket an arbitrary lane list into destination-major (P, capacity)
    tiles off ONE partition plan (the pre-collective stage of every route).

    lanes: tuple of (n,) arrays, all routed by the same (owners, valid);
           zipped tuples survive -- slot (p, j) of every tile holds the
           same source element.
    kinds: per-lane 'word' (payload; invalid/empty slots hold the dtype-max
           sentinel) | 'i32' (length-header / count lane; int32, zero pad).
    plan:  optional precomputed PartitionPlan over the (num_pes + 1)-bucket
           key `where(valid, owners, num_pes)` ('radix' impl only).
    impl:  'radix' (sort-free Pallas plan, default) | 'argsort' (the
           stable-argsort oracle plan) -- both drive the same tile build,
           so results are bit-identical.

    Returns (tiles, fill, overflow). On overflow the first `capacity`
    entries per destination in stream order are kept.
    """
    if len(lanes) != len(kinds) or not lanes:
        raise ValueError("lanes/kinds must be equal-length and non-empty")
    for kind in kinds:
        if kind not in ("word", "i32"):
            raise ValueError(f"unknown lane kind {kind!r}")
    if plan is not None and impl != "radix":
        raise ValueError(f"plan= requires impl='radix', got {impl!r}")
    key = jnp.where(valid, owners.astype(jnp.int32), num_pes)  # invalid last
    if impl == "radix":
        if plan is None:
            plan = ops.make_partition_plan(key, num_pes + 1)
    elif impl == "argsort":
        plan = ops.make_partition_plan_ref(key, num_pes + 1)
    else:
        raise ValueError(f"unknown route impl {impl!r}")
    dst, fill, overflow = plan.tile_slots(key, valid, capacity)
    tiles = []
    for lane, kind in zip(lanes, kinds):
        if kind == "word":
            sent = jnp.array(jnp.iinfo(lane.dtype).max, lane.dtype)
            flat = jnp.full((num_pes * capacity,), sent, lane.dtype)
            tiles.append(flat.at[dst].set(
                jnp.where(valid, lane, sent),
                mode="drop").reshape(num_pes, capacity))
        else:  # 'i32' (validated by lane_wire_bytes callers / kinds above)
            tiles.append(jnp.zeros((num_pes * capacity,), jnp.int32).at[dst]
                         .set(jnp.where(valid, lane.astype(jnp.int32), 0),
                              mode="drop").reshape(num_pes, capacity))
    return tuple(tiles), fill, overflow


def compact_lanes(lanes, kinds, valid, capacity: int, *,
                  impl: str = "radix"):
    """Pre-route prefix compaction: shrink a per-position lane set to its
    occupied prefix (the compaction seam between extraction and
    `route_lanes`).

    Positional extraction layouts (one slot per k-mer position) leave a
    large invalid fraction in every lane -- ~(w-1)/(w+1) of super-k-mer
    slots, the duplicate residue of the L3 compressors -- and the owner
    partition would histogram, rank and scatter every dead slot anyway.
    This pass is a stable 2-bucket partition (valid -> bucket 0, invalid ->
    the trash bucket: validity IS a 1-bit partition digit) through the same
    `PartitionPlan.tile_slots` machinery the router uses, so each lane
    shrinks from n slots to `capacity` before any per-destination work.
    Callers route the compacted lanes with a per-destination capacity
    re-derived from the measured valid density (fabsp._resolve_compact) --
    that re-derivation, not this pass, is where the wire bytes drop.

    Owners must be computed BEFORE compaction and carried through as an
    'i32' lane: the source positions die here.

    lanes/kinds/impl: as `route_tiles`. capacity: static kept-slot count;
    valid entries past it (stream order) are counted in the returned
    overflow -- callers ride their usual overflow round (doubled slack
    re-derives a larger capacity).

    Returns (compacted lanes each (capacity,), new_valid (capacity,) bool,
    overflow () int32). The kept prefix preserves stream order, so routing
    compacted lanes is bit-identical to routing the originals (the dropped
    slots were invalid and never routed).
    """
    if len(lanes) != len(kinds) or not lanes:
        raise ValueError("lanes/kinds must be equal-length and non-empty")
    key = jnp.where(valid, 0, 1)          # valid first; invalid -> trash
    if impl == "radix":
        plan = ops.make_partition_plan(key, 2)
    elif impl == "argsort":
        plan = ops.make_partition_plan_ref(key, 2)
    else:
        raise ValueError(f"unknown compact impl {impl!r}")
    dst, fill, overflow = plan.tile_slots(key, valid, capacity)
    out = []
    for lane, kind in zip(lanes, kinds):
        if kind == "word":
            sent = jnp.array(jnp.iinfo(lane.dtype).max, lane.dtype)
            out.append(jnp.full((capacity,), sent, lane.dtype).at[dst].set(
                jnp.where(valid, lane, sent), mode="drop"))
        elif kind == "i32":
            out.append(jnp.zeros((capacity,), jnp.int32).at[dst].set(
                jnp.where(valid, lane.astype(jnp.int32), 0), mode="drop"))
        else:
            raise ValueError(f"unknown lane kind {kind!r}")
    new_valid = jnp.arange(capacity, dtype=jnp.int32) < fill[0]
    return tuple(out), new_valid, overflow


def oneplan_bucket_key(owners, rows: int, cols: int):
    """Two-digit bucket key of the one-plan 2d decomposition: col-major
    (dest_col, dest_row), so hop 1's chunks are contiguous per destination
    column AND pre-partitioned by destination row."""
    return (owners % cols) * rows + owners // cols


def exchange(tile, axis):
    """The collective of every route: a tiled `all_to_all` of a (P, cap)
    tile over `axis`, under the `exchange` layer scope (nested in the
    caller's `route`), so its device time reads apart from the partition
    plan and the scatter around it."""
    with jax.named_scope("exchange"):
        return jax.lax.all_to_all(tile, axis, 0, 0, tiled=True)


def _oneplan_two_hop(tiles, axis_names, rows: int, cols: int, capacity: int,
                     hop2_capacity: int):
    """Hop 1 + (src_col, dest_row) -> (dest_row, src_col) transpose + hop 2
    for tiles bucketed by `oneplan_bucket_key`. With hop2_capacity <
    capacity, each row's contiguous valid prefix is sliced to the compact
    measured-occupancy width before the second hop."""
    def swap(t):
        return t.reshape(cols, rows, capacity).transpose(1, 0, 2) \
            .reshape(rows * cols, capacity)

    out = []
    for t in tiles:
        h1 = swap(exchange(t, axis_names[1]))
        out.append(exchange(h1[:, :hop2_capacity], axis_names[0]))
    return out


def route_lanes(lanes, kinds, owners, valid, *, num_pes: int, capacity: int,
                axis_names, grid=None, impl: str = "radix",
                route2d: str = "oneplan",
                hop2_capacity: Optional[int] = None,
                rederive_owners=None) -> RouteResult:
    """THE routing implementation: bucket an arbitrary lane list by owner,
    exchange, account exact wire bytes. Runs inside shard_map.

    lanes/kinds/impl: as `route_tiles` (one partition plan per bucket
    stage; every lane rides the same plan, so zipped tuples survive the
    route).
    owners: (n,) int32 destination PE per element -- callers hash whatever
    keys their transport owns by (k-mer words, minimizers) BEFORE routing.
    grid: None for the 1d topology (one all_to_all over axis_names[0]) or
    (rows, cols) for the hierarchical 2d exchange over (axis_names[0],
    axis_names[1]).

    2d 'oneplan' (default): one two-digit (dest_col, dest_row) plan; hop 2
    is a transpose + all_to_all of the already-partitioned tile. With
    `hop2_capacity` set (the occupancy-aware compact scheme) only the first
    hop2_capacity slots of each bucket row travel the second hop; entries
    the hop-1 fill histogram shows past that capacity are counted in
    `hop2_dropped` (sender-side fills, exact after psum) and must ride the
    caller's overflow round.

    2d 'perhop' (oracle): each hop re-plans from the received words;
    requires kinds[0] == 'word' and `rederive_owners` (maps the received
    word lane back to owner PEs). Incompatible with hop2_capacity.

    Returns a RouteResult; received lanes come back flat, length
    P * capacity (1d / perhop's rows * capacity * cols) or
    P * hop2_capacity (2d oneplan).
    """
    slot_bytes = lane_wire_bytes(lanes, kinds)
    zero = jnp.int32(0)

    if grid is None:
        if hop2_capacity is not None:
            raise ValueError("hop2_capacity (compact hop 2) requires the "
                             "2d 'oneplan' topology; the 1d route has no "
                             "second hop to compact")
        tiles, fill, ovf = route_tiles(lanes, kinds, owners, valid, num_pes,
                                       capacity, impl=impl)
        out = tuple(exchange(t, axis_names[0]).reshape(-1) for t in tiles)
        return RouteResult(
            lanes=out, sent_valid=fill.sum().astype(jnp.int32),
            wire_bytes=jnp.int32(num_pes * capacity * slot_bytes),
            overflow=ovf, hop2_dropped=zero, fill=fill.astype(jnp.int32))

    rows, cols = grid
    if route2d == "oneplan":
        cap2 = capacity if hop2_capacity is None \
            else min(hop2_capacity, capacity)
        tiles, fill, ovf = route_tiles(
            lanes, kinds, oneplan_bucket_key(owners, rows, cols), valid,
            num_pes, capacity, impl=impl)
        out = _oneplan_two_hop(tiles, axis_names, rows, cols, capacity, cap2)
        # Fill-aware two-hop accounting: hop 2 forwards exactly the (possibly
        # compacted) prefixes hop 1 delivered and the exchange preserves the
        # GLOBAL fill total, so after the stats psum each PE may charge its
        # own fill histogram for both hops -- no O(P * capacity) sentinel
        # re-scan of the received tile, no metadata exchange. The same
        # histogram prices the compact hop 2: entries past cap2 in any
        # bucket are sliced off on the receiving side, and their count here
        # is globally exact.
        fwd = jnp.minimum(fill, cap2)
        return RouteResult(
            lanes=tuple(t.reshape(-1) for t in out),
            sent_valid=(fill.sum() + fwd.sum()).astype(jnp.int32),
            wire_bytes=jnp.int32(num_pes * (capacity + cap2) * slot_bytes),
            overflow=ovf,
            hop2_dropped=(fill - fwd).sum().astype(jnp.int32),
            fill=fill.astype(jnp.int32))

    if route2d != "perhop":
        raise ValueError(f"unknown route2d {route2d!r}")
    if hop2_capacity is not None:
        raise ValueError("hop2_capacity (compact hop 2) requires the "
                         "'oneplan' 2d route")
    if rederive_owners is None or kinds[0] != "word":
        raise ValueError("the 'perhop' oracle re-plans from the received "
                         "word lane: kinds[0] must be 'word' and "
                         "rederive_owners must be provided")
    # Stage 1 routes along the column axis to the destination column,
    # stage 2 re-derives owners from the received words and re-plans.
    cap1 = capacity * rows  # per-column capacity: rows destinations share it
    tiles1, fill1, ovf1 = route_tiles(lanes, kinds, owners % cols, valid,
                                      cols, cap1, impl=impl)
    recv1 = tuple(exchange(t, axis_names[1]).reshape(-1) for t in tiles1)
    sent1 = jnp.array(jnp.iinfo(recv1[0].dtype).max, recv1[0].dtype)
    valid1 = recv1[0] != sent1
    dest_row = rederive_owners(recv1[0]) // cols
    cap2 = capacity * cols  # stage-2 input is cols * cap1 entries
    tiles2, fill2, ovf2 = route_tiles(recv1, kinds, dest_row, valid1, rows,
                                      cap2, impl=impl)
    out = tuple(exchange(t, axis_names[0]).reshape(-1) for t in tiles2)
    return RouteResult(
        lanes=out, sent_valid=(fill1.sum() + fill2.sum()).astype(jnp.int32),
        wire_bytes=jnp.int32((cols * cap1 + rows * cap2) * slot_bytes),
        overflow=ovf1 + ovf2, hop2_dropped=zero,
        fill=jnp.zeros((rows * cols,), jnp.int32))


def plan_capacity(num_items: int, num_pes: int, slack: float = 1.5,
                  align: int = 8) -> int:
    """Per-destination tile capacity for ~uniform (hashed) traffic.

    Hashing spreads distinct k-mers near-uniformly; the binomial tail at
    chunk sizes >= 4k items makes slack 1.5 overflow-free in practice
    (property-tested). Aligned up so the lane dimension tiles cleanly.
    """
    expected = num_items / num_pes
    cap = int(math.ceil(expected * slack))
    return max(align, ((cap + align - 1) // align) * align)


@functools.partial(jax.jit, static_argnums=(3, 4), static_argnames=("impl",))
def bucket_by_owner(words: jax.Array, owners: jax.Array, valid: jax.Array,
                    num_pes: int, capacity: int,
                    counts: Optional[jax.Array] = None,
                    plan: Optional[ops.PartitionPlan] = None, *,
                    impl: str = "radix") -> BucketResult:
    """Pack words into a destination-major (P, capacity) tile (the L2 layer).

    words:  (n,) payload words (k-mers, possibly count-packed)
    owners: (n,) int32 destination PE per word
    valid:  (n,) bool; invalid entries are not routed
    counts: optional (n,) int32 second lane (HEAVY {kmer, count} packets);
            partitioned with the same plan, returned as `BucketResult.counts`
            (zero-padded where the words tile holds the sentinel)
    plan:   optional precomputed PartitionPlan over the (num_pes + 1)-bucket
            key `where(valid, owners, num_pes)` -- an exposed hook for
            callers that route several lane sets off one histogram pass
            ('radix' impl only; rejected under 'argsort')
    impl:   'radix' (sort-free partition, default) | 'argsort' (jnp oracle)

    On overflow (a destination receiving more than `capacity` entries) the
    first `capacity` entries in stream order are kept, identically for both
    implementations. This is a two-lane wrapper over `route_tiles` (the
    lane-list tile build every transport routes through).
    """
    lanes = (words,) if counts is None else (words, counts)
    kinds = ("word",) if counts is None else ("word", "i32")
    tiles, fill, overflow = route_tiles(lanes, kinds, owners, valid, num_pes,
                                        capacity, plan=plan, impl=impl)
    return BucketResult(tile=tiles[0], fill=fill, overflow=overflow,
                        counts=tiles[1] if counts is not None else None)


@functools.partial(jax.jit, static_argnums=(1, 2), static_argnames=("impl",))
def l3_compress(words: jax.Array, k: int, bits_per_symbol: int = 2, *,
                impl: str = "radix") -> Tuple[jax.Array, jax.Array]:
    """L3: sort+accumulate a local block, pack counts into spare high bits.

    words: (C3,) raw k-mer words (sentinel for padding).
    returns (packed, valid): (C3,) count-packed words (sentinel-padded) and
    their validity mask. len(valid.sum()) == number of *distinct* k-mers in
    the block -- the compression the paper's Fig. 12 measures.
    impl: 'radix' sorts the block with the sort-free partition engine and
    accumulates with the fused Pallas boundary+segment-sum sweep; 'argsort'
    is the jnp oracle.
    """
    sent = int(jnp.iinfo(words.dtype).max)
    if impl == "radix":
        swords = radix_sort(words, encoding.kmer_bits(k, bits_per_symbol),
                            sentinel_val=sent)
        acc = accumulate(swords, sentinel_val=sent, impl="fused")
    else:
        acc = accumulate(jnp.sort(words), sentinel_val=sent)
    valid = jnp.arange(words.shape[0]) < acc.num_unique
    packed = jnp.where(
        valid,
        encoding.pack_counts(acc.unique & encoding.kmer_mask(k, bits_per_symbol),
                             jnp.maximum(acc.counts, 1), k, bits_per_symbol),
        jnp.array(sent, words.dtype))
    return packed, valid


@functools.partial(jax.jit, static_argnums=(1, 2))
def l3_decompress(packed_tile: jax.Array, k: int, bits_per_symbol: int = 2
                  ) -> Tuple[jax.Array, jax.Array]:
    """Receiver side: split count-packed words into (kmer, count) lanes.

    Sentinel entries yield count 0 (i.e. ignored by accumulate).
    """
    sent = jnp.array(jnp.iinfo(packed_tile.dtype).max, packed_tile.dtype)
    flat = packed_tile.reshape(-1)
    kmers, counts = encoding.unpack_counts(flat, k, bits_per_symbol)
    is_valid = flat != sent
    counts = jnp.where(is_valid, counts, 0)
    kmers = jnp.where(is_valid, kmers, sent)
    return kmers, counts


def l3_max_block(k: int, bits_per_symbol: int = 2) -> int:
    """Largest C3 such that a block-local count always fits the spare bits."""
    return encoding.count_capacity(k, bits_per_symbol)


def aggregation_memory_bytes(num_pes: int, protocol: str = "1d",
                             c1: int = 1024, c2: int = 32, c3: int = 10_000,
                             word_bytes: int = 8) -> dict:
    """Paper Table III: per-PE memory of each aggregation layer.

    L0 follows the Conveyors buffer law 40KB * P^x with x in {1, 1/2, 1/3};
    on TPU the analogue is the (P, capacity) tile footprint per stage of the
    (possibly hierarchical) all_to_all.
    """
    x = {"1d": 1.0, "2d": 0.5, "3d": 1.0 / 3.0}[protocol]
    return {
        "L0": 40_000 * (num_pes ** x),
        "L1": c1 * 264,                    # paper: 264 KB at C1=1024
        "L2": c2 * 8.25 * num_pes,         # paper: 264 B/PE at C2=32
        "L3": c3 * word_bytes,
    }
