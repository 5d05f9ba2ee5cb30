"""Pallas TPU kernel: open-addressing insert-or-add (the streaming receiver).

The paper's receiving PEs count asynchronously: every aggregated message is
folded into a local hash table as it arrives (Alg. 3's `LocalHashTable`
insert), so receive memory is bounded by the table -- not by the number of
chunks in flight. This kernel is that insert, adapted to the TPU's
static-shape world:

- The table is a fixed-capacity open-addressing array pair (`keys`,
  `counts`), empty slots keyed by the all-ones sentinel. The kernel views
  it as rows of `_LANES` slots, `(cap / 128, 128)`, and copies it ONCE from
  HBM into a VMEM scratch at the first grid step (and back to HBM at the
  last), so the sequential grid keeps one resident copy across every input
  tile -- the accumulator pattern, carrying a mutable table instead of a
  partial sum. Table size is therefore bounded by VMEM (`max_vmem_capacity`;
  the retry policy's store ceiling stops there on the TPU and growth past
  it goes to the spill tier).
- The batch (keys, weights, home slots) arrives in `tile`-sized SMEM blocks:
  the items are scalars to the probe loop. Slots are hashed OUTSIDE the
  kernel so the kernel stays dtype-thin; words travel as int32 bit
  patterns (only equality is ever asked of them).
- Each item probes row by row: one dynamic row load resolves up to 128
  linear-probe steps at once (the first lane, at or after the home lane,
  holding the sentinel or the key), wrapping modulo capacity. A walk that
  visits every slot without landing means the table is full: the item is
  dropped and counted, and the caller's overflow round grows the capacity
  (the same discipline the routing tiles use). A landing writes its row
  back through a one-lane masked select.
- Items are resolved `GROUP` (8, the sublane count) at a time where the
  table has `_GROUP_MIN_ROWS` rows of 128 lanes or more (`insert_group`;
  tiny test tables keep the one-item walk). A group with no live item
  costs its scalar reads. Otherwise, if no two live items share a home
  row (scalar compares of the SMEM slots), each live item's home row is
  loaded and its landing lane found on the vector side, and one
  vector-to-scalar move asks whether every live item landed in its home
  row. When both hold, no item reads a row another writes, so storing
  each row once (masked to the live items) is the stream-order fold.
  Either condition failing -- a shared row, a home row full from the home
  lane on, a wrap, a full table -- folds the group one item at a time by
  the walk above, in stream order.
- Dropped items, groups with a live item and groups that fell back
  accumulate in an SMEM carry across grid steps (sequential grid =>
  exact, as in segment_count.py).

Determinism: tiles execute in order, items within a tile fold in input
order (a group's fast path is that order's result), and the row walk lands
on exactly the slot a one-slot-at-a-time linear probe would, so the final
table state is bit-identical to the sequential pure-jnp oracle
(`ref.hash_insert_ref`, which also counts the groups) -- slot layout
included, not just the key->count multiset. The lookup kernel's probe
counts are the oracle's step counts, recovered from the landing slot's
distance to home.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# VMEM of one TPU v5e TensorCore. The table scratch (int32 keys + counts,
# 8 bytes a slot) is the kernels' only large buffer; the compile rehearsal
# (tests/test_tpu_compile.py) admits 2^24 slots and refuses 2^25.
_VMEM_BYTES = 128 * 1024 * 1024
_VMEM_HEADROOM = 8 * 1024 * 1024
# Items the insert resolves per home-row pass (the sublane count), and the
# fewest table rows that take that path: in a smaller table most groups
# would share a row and fall back.
GROUP = 8
_GROUP_MIN_ROWS = 4 * GROUP


def max_vmem_capacity() -> int:
    """Largest power-of-two slot count whose (keys, counts) int32 table
    fits one core's VMEM: the per-PE store ceiling on the TPU."""
    cap = 1
    while 2 * cap * 8 <= _VMEM_BYTES:
        cap *= 2
    return cap


def _layout(cap: int):
    """(rows, width) view of a `cap`-slot table: rows of 128 lanes, or one
    full-width row when `cap` is not a lane multiple (tiny test tables)."""
    width = _LANES if cap % _LANES == 0 else cap
    return cap // width, width


def _compiler_params(cap: int):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=min(cap * 8 + _VMEM_HEADROOM, _VMEM_BYTES))


def _sent_i32(sentinel_val: int, word) -> int:
    if np.dtype(word).itemsize != 4:
        raise ValueError(f"hash kernels take 32-bit words, got {word}")
    return int(np.asarray(sentinel_val, np.dtype(word)).view(np.int32))


def _as_i32(x):
    return x if x.dtype == jnp.int32 else jax.lax.bitcast_convert_type(
        x, jnp.int32)


def _walk(tk, key, slot0, sent, active, rows: int, width: int):
    """Row-wise linear probe from `slot0`: the first slot holding `sent` or
    `key` in probe order, or -1 when all `rows * width` slots were visited
    (or the item is not `active`: padding never walks).
    Visit t covers row (home row + t) mod rows; visit 0 starts at the home
    lane and the final visit (t == rows) closes the wrap before it."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    r0 = jax.lax.div(slot0, jnp.int32(width))
    l0 = jax.lax.rem(slot0, jnp.int32(width))

    def probing(state):
        t, found = state
        return active & (found < 0) & (t <= rows)

    def visit(state):
        t, _ = state
        row = r0 + t
        row = jnp.where(row >= rows, row - rows, row)
        lo = jnp.where(t == 0, l0, 0)
        hi = jnp.where(t == rows, l0, width)
        cur = tk[pl.ds(row, 1), :]
        hit = (lane >= lo) & (lane < hi) & ((cur == sent) | (cur == key))
        first = jnp.min(jnp.where(hit, lane, width))
        return t + 1, jnp.where(first < width, row * width + first, -1)

    _, found = jax.lax.while_loop(probing, visit,
                                  (jnp.int32(0), jnp.int32(-1)))
    return found


def insert_group(cap: int, tile: int) -> int:
    """Items the insert resolves per home-row pass for a `cap`-slot table
    fed in `tile`-item blocks: `GROUP` where the table has
    `_GROUP_MIN_ROWS` rows of 128 lanes or more and the tile divides
    into groups, else 0 (tiny tables keep the one-item loop)."""
    rows, width = _layout(cap)
    if width == _LANES and rows >= _GROUP_MIN_ROWS and tile % GROUP == 0:
        return GROUP
    return 0


def _fold_one(tk, tc, keys_ref, w_ref, slots_ref, i, *, sent, rows: int,
              width: int):
    """Fold item `i` in by the sequential walk; 1 if it was dropped."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    key = keys_ref[i]
    w = w_ref[i]
    valid = (key != sent) & (w > 0)
    found = _walk(tk, key, slots_ref[i], sent, valid, rows, width)
    land = valid & (found >= 0)

    @pl.when(land)
    def _write():
        row = jax.lax.div(found, jnp.int32(width))
        mine = lane == jax.lax.rem(found, jnp.int32(width))
        cur = tk[pl.ds(row, 1), :]
        tk[pl.ds(row, 1), :] = jnp.where(mine & (cur == sent), key, cur)
        tc[pl.ds(row, 1), :] = tc[pl.ds(row, 1), :] + jnp.where(
            mine, w, jnp.int32(0))

    return jnp.where(valid & (found < 0), jnp.int32(1), jnp.int32(0))


def _fold_group(tk, tc, keys_ref, w_ref, slots_ref, base, *, sent,
                rows: int, width: int, group: int):
    """Fold items [base, base + group) in; returns (dropped, live, fell
    back) as int32 scalars.

    Fast path: every live item's first probe visit (its home row from the
    home lane on) holds the sentinel or the key, and no two live items
    share a home row. Then no item reads a row another one writes, so
    landing all of them against the rows as loaded, and storing each row
    once, is the stream-order fold. Otherwise the group folds one item at
    a time (`_fold_one`)."""
    one = functools.partial(_fold_one, tk, tc, keys_ref, w_ref, slots_ref,
                            sent=sent, rows=rows, width=width)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    ids = [base + j for j in range(group)]
    keys = [keys_ref[i] for i in ids]
    zero = jnp.int32(0)

    def sequential():
        return jax.lax.fori_loop(base, base + group,
                                 lambda i, d: d + one(i), zero), jnp.int32(1)

    def resolve():
        # a group of weight-0 items lands trivially and stores nothing
        ws = [w_ref[i] for i in ids]
        live = [(k != sent) & (w > 0) for k, w in zip(keys, ws)]
        slots = [slots_ref[i] for i in ids]
        home = [jax.lax.div(s, jnp.int32(width)) for s in slots]
        # scalar: two live items in one row cannot both take the fast path
        clash = functools.reduce(jnp.logical_or, [
            live[a] & live[b] & (home[a] == home[b])
            for a in range(group) for b in range(a + 1, group)])

        def vector():
            cur = [tk[pl.ds(r, 1), :] for r in home]
            cnt = [tc[pl.ds(r, 1), :] for r in home]
            first, worst = [], None
            for j in range(group):
                l0 = jax.lax.rem(slots[j], jnp.int32(width))
                hit = (lane >= l0) & ((cur[j] == sent) | (cur[j] == keys[j]))
                f = jnp.min(jnp.where(hit, lane, width), axis=1,
                            keepdims=True)
                first.append(f)
                f = jnp.where(live[j], f, zero)
                worst = f if worst is None else jnp.maximum(worst, f)
            landed = jnp.max(worst) < width   # the group's one vector read

            def commit():
                for j in range(group):
                    mine = lane == first[j]
                    keep = jnp.full((1, width), live[j])
                    pltpu.store(tk.at[pl.ds(home[j], 1), :],
                                jnp.where(mine & (cur[j] == sent), keys[j],
                                          cur[j]), mask=keep)
                    pltpu.store(tc.at[pl.ds(home[j], 1), :],
                                cnt[j] + jnp.where(mine, ws[j], zero),
                                mask=keep)
                return zero, zero

            return jax.lax.cond(landed, commit, sequential)

        dropped, fell = jax.lax.cond(clash, sequential, vector)
        any_live = functools.reduce(jnp.logical_or, live)
        return dropped, any_live.astype(jnp.int32), fell

    # padding groups cost their key reads
    any_key = functools.reduce(jnp.logical_or, [k != sent for k in keys])
    return jax.lax.cond(any_key, resolve, lambda: (zero, zero, zero))


def _hash_insert_kernel(keys_ref, w_ref, slots_ref, tk_hbm, tc_hbm,
                        ok_hbm, oc_hbm, stat_ref, tk, tc, carry, *,
                        sentinel_val: int, rows: int, width: int,
                        group: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        pltpu.sync_copy(tk_hbm, tk)
        pltpu.sync_copy(tc_hbm, tc)
        for j in range(3):
            carry[j] = jnp.int32(0)

    sent = jnp.int32(sentinel_val)
    n = keys_ref.shape[0]
    zero = jnp.int32(0)
    if group:
        def step(g, acc):
            out = _fold_group(tk, tc, keys_ref, w_ref, slots_ref, g * group,
                              sent=sent, rows=rows, width=width, group=group)
            return tuple(a + b for a, b in zip(acc, out))

        acc = jax.lax.fori_loop(0, n // group, step, (zero, zero, zero))
    else:
        dropped = jax.lax.fori_loop(
            0, n, lambda i, d: d + _fold_one(
                tk, tc, keys_ref, w_ref, slots_ref, i, sent=sent, rows=rows,
                width=width), zero)
        acc = (dropped, zero, zero)
    for j in range(3):
        carry[j] = carry[j] + acc[j]
        stat_ref[j] = carry[j]

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _writeback():
        pltpu.sync_copy(tk, ok_hbm)
        pltpu.sync_copy(tc, oc_hbm)


def _smem_tile(tile: int):
    return pl.BlockSpec((tile,), lambda i: (i,),
                        memory_space=pltpu.SMEM)


def hash_insert_pallas(table_keys: jax.Array, table_counts: jax.Array,
                       keys: jax.Array, weights: jax.Array,
                       slots: jax.Array, sentinel_val: int,
                       tile: int = 1024, interpret: bool = False):
    """Fold a batch of (key, weight) pairs into the open-addressing table.

    table_keys:   (cap,) 32-bit word table, empty slots == sentinel_val
    table_counts: (cap,) int32
    keys:    (n,) batch words; sentinel (or weight 0) entries are skipped
    weights: (n,) int32 multiplicities (>= 1 for live entries)
    slots:   (n,) int32 home slots in [0, cap) -- hash(key) % cap, computed
             by the caller (core/countstore.py)

    Returns (new_keys, new_counts, dropped, groups): the updated table,
    the number of live entries dropped because a full probe sweep found
    neither an empty nor a matching slot (table full => caller rehashes at
    doubled capacity), and (2,) int32 group counts -- groups of
    `insert_group(cap, tile)` items that held a live item, and those of
    them that fell back to the one-item walk (both 0 where the table is
    too small to group). n must divide by `tile`.
    """
    n = keys.shape[0]
    if n % tile != 0:
        raise ValueError(f"n {n} % tile {tile} != 0")
    cap = table_keys.shape[0]
    rows, width = _layout(cap)
    word = table_keys.dtype
    sent = _sent_i32(sentinel_val, word)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    new_keys, new_counts, stat = pl.pallas_call(
        functools.partial(_hash_insert_kernel, sentinel_val=sent, rows=rows,
                          width=width, group=insert_group(cap, tile)),
        grid=(n // tile,),
        in_specs=[_smem_tile(tile), _smem_tile(tile), _smem_tile(tile),
                  any_spec, any_spec],
        out_specs=[any_spec, any_spec,
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((rows, width), jnp.int32),
                   jax.ShapeDtypeStruct((rows, width), jnp.int32),
                   jax.ShapeDtypeStruct((3,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, width), jnp.int32),
                        pltpu.VMEM((rows, width), jnp.int32),
                        pltpu.SMEM((3,), jnp.int32)],
        compiler_params=_compiler_params(cap),
        interpret=interpret,
        name="hash_insert",
    )(_as_i32(keys), weights.astype(jnp.int32), slots.astype(jnp.int32),
      _as_i32(table_keys).reshape(rows, width),
      table_counts.astype(jnp.int32).reshape(rows, width))
    new_keys = jax.lax.bitcast_convert_type(new_keys.reshape(cap), word)
    return new_keys, new_counts.reshape(cap), stat[0], stat[1:]


def _hash_lookup_kernel(keys_ref, slots_ref, tk_hbm, tc_hbm,
                        counts_ref, probes_ref, tk, tc, *,
                        sentinel_val: int, rows: int, width: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        pltpu.sync_copy(tk_hbm, tk)
        pltpu.sync_copy(tc_hbm, tc)

    sent = jnp.int32(sentinel_val)
    cap = rows * width
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def probe_one(i, _):
        key = keys_ref[i]
        slot0 = slots_ref[i]
        valid = key != sent
        found = _walk(tk, key, slot0, sent, valid, rows, width)
        at = jnp.maximum(found, 0)
        row = jax.lax.div(at, jnp.int32(width))
        mine = lane == jax.lax.rem(at, jnp.int32(width))
        cur = jnp.max(jnp.where(mine, tk[pl.ds(row, 1), :], jnp.iinfo(
            jnp.int32).min))
        cnt = jnp.max(jnp.where(mine, tc[pl.ds(row, 1), :], 0))
        match = valid & (found >= 0) & (cur == key)
        # probe steps: landing distance from home (+1), or the full sweep
        dist = found - slot0
        steps = jnp.where(found < 0, cap,
                          jnp.where(dist < 0, dist + cap, dist) + 1)
        counts_ref[i] = jnp.where(match, cnt, 0)
        probes_ref[i] = jnp.where(valid, steps, 0)
        return 0

    jax.lax.fori_loop(0, keys_ref.shape[0], probe_one, 0)


def hash_lookup_pallas(table_keys: jax.Array, table_counts: jax.Array,
                       keys: jax.Array, slots: jax.Array, sentinel_val: int,
                       tile: int = 1024, interpret: bool = False):
    """Read-only batched probe: per-key counts out of the committed table.

    The serving-side twin of `hash_insert_pallas` -- identical probe walk
    (linear from the caller-supplied home slot, wrap modulo capacity, stop
    at empty or match), but the table is never written: a match reads the
    slot's count, an empty slot or an exhausted sweep is a miss (count 0).
    Sentinel keys (query-batch padding) are skipped with count 0.

    table_keys:   (cap,) 32-bit word table, empty slots == sentinel_val
    table_counts: (cap,) int32
    keys:  (n,) query words; sentinel entries skipped
    slots: (n,) int32 home slots -- hash(key) % cap, computed by the caller

    Returns (counts, probes), both (n,) int32: counts[i] is the stored
    count (0 = miss), probes[i] the number of probe steps the walk took
    (0 for skipped sentinels) -- the serving stats' probe-depth source.
    n must divide by `tile`. Bit-identical to `ref.hash_lookup_ref`.
    """
    n = keys.shape[0]
    if n % tile != 0:
        raise ValueError(f"n {n} % tile {tile} != 0")
    cap = table_keys.shape[0]
    rows, width = _layout(cap)
    sent = _sent_i32(sentinel_val, table_keys.dtype)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_hash_lookup_kernel, sentinel_val=sent, rows=rows,
                          width=width),
        grid=(n // tile,),
        in_specs=[_smem_tile(tile), _smem_tile(tile), any_spec, any_spec],
        out_specs=[_smem_tile(tile), _smem_tile(tile)],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, width), jnp.int32),
                        pltpu.VMEM((rows, width), jnp.int32)],
        compiler_params=_compiler_params(cap),
        interpret=interpret,
    )(_as_i32(keys), slots.astype(jnp.int32),
      _as_i32(table_keys).reshape(rows, width),
      table_counts.astype(jnp.int32).reshape(rows, width))
