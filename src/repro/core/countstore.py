"""Carry-resident count store: the streaming receiver's state (paper Alg. 3).

The paper's receiving PEs never materialize the incoming stream: each
aggregated message is folded into a local hash table on arrival, so per-PE
receive memory is bounded by the table capacity -- independent of how many
chunks the senders emit. `CountStore` is that table for the TPU pipeline: a
fixed-capacity open-addressing (linear-probing) array pair carried through
`fabsp`'s Phase-1 scan. `store_insert` folds one decompressed receive tile
per scan step (the Pallas insert-or-add kernel, kernels/hash_table.py);
`store_histogram` is all that remains of Phase 2 -- one sort/compaction of
the table into the usual `AccumResult`.

Sizing: slots are consumed by DISTINCT k-mers only, so the right capacity
tracks the workload's distinct-count, not its instance-count. Callers that
know neither start from a bound (`fabsp` defaults to
min(total instances, 4**k) / P * store_slack) and rely on the overflow
round: a full table drops-and-counts, and the caller rehashes into
capacity scaled by `RetryPolicy.store_growth` (default: doubled,
`store_grow`) and replays -- the same growth discipline as the routing
tiles, and since this PR the same ENGINE: both loops run through
`resilience.RetryController`, which records every rehash round
(`DAKCStats.retry_store_rehash`), enforces the capacity ceiling
(`RetryPolicy.store_cap_ceiling`, default `max_store_capacity()`: the
VMEM-resident table bound on the TPU, 1<<28 slots/PE elsewhere), and gives up
with a typed `CapacityExhausted` carrying the full round history instead
of an anonymous RuntimeError. Since the spill tier (core/spill.py) that
give-up is itself recoverable: with `DAKCConfig.spill='auto'` the
`CapacityExhausted(store-rehash)` is intercepted, the table's live
entries are exported to disk bins, and counting continues out-of-core --
the table shrinks to a vestigial few slots and each bin is later folded
back through this same store at a capacity it can afford. Dropping is
deliberate and counted
(`CountStore.dropped`), never silent: a drop either triggers a recorded
rehash round or surfaces in the raised error. Empty slots are keyed by
the all-ones sentinel, the same value that pads every routed tile, so
receive padding is skipped for free.

Slot hashing uses `owner.slot_hash`, a second avalanche family independent
of `owner_pe`: every k-mer reaching PE p already satisfies
hash(x) == p (mod P), and reusing that hash for slots would populate only
1/P of the table.

Backend note: `impl='auto'` runs the Pallas kernel on TPU and the
bit-identical jnp oracle elsewhere (ops.hash_insert -- interpret-mode
emulation of the probe loop costs O(capacity) per store, so it is
reserved for the kernel parity tests).

Query/serving contract (`store_lookup`, core/query.py): the committed
store doubles as a random-access serving index. `store_lookup` is the
read-only reverse of `store_insert` -- the same home-slot hash and the
same linear probe walk, but a match reads the slot's count and nothing is
written, so lookups are safe to run concurrently against a live store and
bit-stable across repeats. A probe that reaches an empty slot (or
exhausts the sweep) is a definitive miss: the insert path guarantees
every stored key is reachable from its home slot without crossing an
empty slot, so count 0 means "never counted", never "maybe". Distributed
queries route through `query.query_counts` (the aggregation protocol in
reverse) and probe each PE's shard in place with this function.

Generation handoff (`StoreSnapshot`): serving never reads the counter's
LIVE arrays. `KmerCounter.update` publishes a `StoreSnapshot` -- the
sharded key/count arrays, their capacity, and the spill tier's committed
manifest view -- atomically at each batch commit, and `count()` probes
that pinned generation. Store arrays are immutable jax values and sealed
spill segments are immutable files, so a rehash, elastic fold, or spill
replay in flight mutates only the counter's live references; a query
racing it answers from the last committed histogram exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import owner
from repro.core.sort import AccumResult, accumulate, sort_with_weights
from repro.kernels import hash_table, ops


class CountStore(NamedTuple):
    keys: jax.Array     # (capacity,) k-mer words; sentinel == empty slot
    counts: jax.Array   # (capacity,) int32 accumulated counts
    dropped: jax.Array  # () int32 live entries dropped (table full)


class StoreSnapshot(NamedTuple):
    """One committed store generation -- everything a query needs, pinned.

    Published atomically (one reference assignment) by `KmerCounter` at
    each batch commit; `count()` reads the snapshot, never the live
    counter state. `spill_state` is the spill tier's committed manifest
    (`SpillWriter.state()`) frozen at the same commit, or None while the
    counter is purely in-core -- the spilled-bin query tier reads bins
    through this pinned view (`SpillWriter.read_bin(b, segments=...)`),
    so a later spill commit never leaks into an older generation's
    answers.
    """
    gen: int                      # monotone commit counter (diagnostics)
    keys: jax.Array               # (P * store_cap,) sharded store keys
    counts: jax.Array             # (P * store_cap,) sharded store counts
    store_cap: int                # per-PE slot count of THIS generation
    spill_state: Optional[dict]   # committed manifest view, or None


def max_store_capacity() -> int:
    """Largest per-PE store the backend's insert path holds: the
    VMEM-resident table bound where the kernels compile for the TPU
    (`hash_table.max_vmem_capacity`), 2**28 slots elsewhere (the jnp
    oracle). The retry policy's default store ceiling; past it a store
    spills (`DAKCConfig.spill='auto'`) instead of failing to compile."""
    if ops._interpret():
        return 1 << 28
    return hash_table.max_vmem_capacity()


def empty_store(capacity: int, dtype) -> CountStore:
    """All-empty store: sentinel keys, zero counts."""
    sent = jnp.iinfo(dtype).max
    return CountStore(keys=jnp.full((capacity,), sent, dtype),
                      counts=jnp.zeros((capacity,), jnp.int32),
                      dropped=jnp.int32(0))


def store_slots(words: jax.Array, capacity: int) -> jax.Array:
    """Home slot of each word: owner-independent hash modulo capacity."""
    h = owner.slot_hash(words)
    return (h % h.dtype.type(capacity)).astype(jnp.int32)


def store_insert(store: CountStore, words: jax.Array,
                 counts: Optional[jax.Array] = None, *,
                 impl: str = "auto") -> CountStore:
    """Fold (words, counts) into the store; sentinel / zero-count entries
    are skipped. Returns the updated store with `dropped` accumulated."""
    return store_insert_counted(store, words, counts, impl=impl)[0]


def store_insert_counted(store: CountStore, words: jax.Array,
                         counts: Optional[jax.Array] = None, *,
                         impl: str = "auto"):
    """`store_insert`, plus the insert's (2,) int32 group counts (groups
    with a live item, groups that fell back to the one-item walk:
    `ops.hash_insert_counted`). Returns (store, groups)."""
    sent = jnp.iinfo(words.dtype).max
    if counts is None:
        counts = (words != words.dtype.type(sent)).astype(jnp.int32)
    capacity = store.keys.shape[0]
    keys, cnts, dropped, groups = ops.hash_insert_counted(
        store.keys, store.counts, words, counts,
        store_slots(words, capacity), sentinel_val=int(sent), impl=impl)
    return CountStore(keys=keys, counts=cnts,
                      dropped=store.dropped + dropped), groups


def store_lookup(store: CountStore, words: jax.Array, *,
                 impl: str = "auto"):
    """Batched read-only probe: per-word counts out of the committed store.

    Returns (counts, probes), both (n,) int32: counts[i] is the stored
    count of words[i] (0 = miss, including sentinel padding), probes[i]
    the probe-walk length (serving probe-depth stat). Never writes --
    the store is unchanged, so lookups compose with a live receiver.
    """
    sent = jnp.iinfo(store.keys.dtype).max
    capacity = store.keys.shape[0]
    return ops.hash_lookup(store.keys, store.counts, words,
                           store_slots(words, capacity),
                           sentinel_val=int(sent), impl=impl)


def store_grow(store: CountStore, new_capacity: int, *,
               impl: str = "auto") -> CountStore:
    """Rehash every live entry into a fresh table of `new_capacity` slots
    (the store's overflow round). Resets `dropped` (a grown table, sized
    strictly above the live-entry count, drops nothing)."""
    if new_capacity < store.keys.shape[0]:
        raise ValueError("store_grow cannot shrink the table")
    return store_insert(empty_store(new_capacity, store.keys.dtype),
                        store.keys, store.counts, impl=impl)


@functools.partial(jax.jit, static_argnames=("total_bits", "impl"))
def store_histogram(store: CountStore, *, total_bits: int,
                    impl: str = "radix") -> AccumResult:
    """The residual Phase 2: one sort/compaction of the table.

    Table keys are already distinct, so this is a pure layout change --
    occupied slots sort to an ascending prefix with their counts riding the
    weights lane, exactly the `AccumResult` contract every consumer of the
    stacked path expects. impl follows `phase2_impl`: 'radix' is the
    sort-free engine + fused Pallas sweep, 'argsort' the jnp oracle.
    """
    sent = int(jnp.iinfo(store.keys.dtype).max)
    keys, w = sort_with_weights(store.keys, store.counts, impl=impl,
                                total_bits=total_bits, sentinel_val=sent)
    accum_impl = "fused" if impl == "radix" else "segment_sum"
    return accumulate(keys, w, sentinel_val=sent, impl=accum_impl)
