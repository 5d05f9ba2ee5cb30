"""Pallas TPU kernels: sort-free radix partition (the L2 routing engine).

The owner key of a routed k-mer has only P (or, for a radix-sort digit pass,
R = 2**digit_bits) distinct values, so a comparison sort of the stream is pure
waste: a counting/radix partition moves every element exactly once. This is
the KMC/Gerbil bucket-partition insight, and it is what the paper's Phase-2
analytical model (Eq. 13) charges for -- streaming sweeps, not O(n log^2 n)
bitonic networks. On the routing side (Eqs. 11-12 traffic), the one-plan 2d
decomposition below also removes the per-hop re-planning pass the model
never budgeted: hierarchical routing costs one extra all_to_all, not an
extra histogram of the stream.

Two kernels, composed by `make_partition_plan` into a reusable
`PartitionPlan` object (positions + per-bucket totals + exclusive-prefix
starts). A plan is built from ONE histogram pass and then applied to any
number of payload lanes by pure scatters -- `aggregation.route_tiles`
buckets an arbitrary lane LIST (k-mer words, super-k-mer payload words,
int32 headers/counts) off one plan via `PartitionPlan.tile_slots`, and the
`'2d'` routing topology decomposes the owner id into (col, row) digits so
both hops of the hierarchical all_to_all run off a single plan (the second
hop is a plain transpose of the already-partitioned tile; see
`aggregation.route_lanes`).

Layout: the stream is viewed as one tile per row, eight rows (tiles) per
grid step, so every block is a whole (8, 128k) slab. Inside, a tile's
one-hot matrix puts buckets down the sublanes and elements along the lanes
-- the orientation the ids arrive in, so no lane->sublane relayout is
needed; per-bucket columns move to and from lane rows through a masked
diagonal reduction.

1. `bucket_hist_pallas`: per-tile bucket histogram: a broadcast compare
   against a sublane iota and a lane reduction -- scatter-free, VPU-only.
2. `bucket_positions_pallas`: per-tile stable rank + global offset. The
   exclusive prefix over (bucket-major, then tile-major) histograms is a tiny
   (T, B) XLA cumsum; each instance then computes every element's within-tile
   rank among equal buckets (a log-step lane cumsum of the one-hot, so the
   working set is O(tile * B) VMEM, never O(n * B) HBM) and adds its tile's
   base offset. The emitted positions are a permutation: one XLA scatter
   finishes the partition. No sort primitive appears anywhere in the
   lowering.

Stability: ranks are computed in input order within a tile and tiles are
offset in input order, so the partition is stable -- bit-identical to a
stable-argsort oracle (kernels/ref.py) and safe for LSD radix passes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# tile * num_buckets ceiling for partition_plan's auto-clamp: 512K int32
# elements = 2 MB per (B, tile) temp, ~6 MB live across the 3 temps.
_VMEM_BUDGET_ELEMS = 512 * 1024
_LANES = 128
_ROWS = 8          # tiles per grid step: one per sublane of an int32 vreg


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _onehot(row, nb_s: int):
    """(1, tile) bucket ids -> (nb_s, tile) int32 one-hot, buckets down the
    sublanes and elements along the lanes (no lane->sublane relayout)."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (nb_s, row.shape[1]), 0)
    return (ids == row).astype(jnp.int32)


def _diag(nb_s: int, nb_l: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (nb_s, nb_l), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (nb_s, nb_l), 1))


def _col_to_row(col, nb_l: int):
    """(nb_s, 1) -> (1, nb_l): a transpose as a masked diagonal reduction."""
    return jnp.sum(jnp.where(_diag(col.shape[0], nb_l), col, 0), axis=0,
                   keepdims=True, dtype=jnp.int32)


def _row_to_col(row, nb_s: int):
    """(1, nb_l) -> (nb_s, 1), the inverse of `_col_to_row`."""
    return jnp.sum(jnp.where(_diag(nb_s, row.shape[1]), row, 0), axis=1,
                   keepdims=True, dtype=jnp.int32)


def _exclusive_lane_cumsum(x):
    """Exclusive prefix sum along the lanes: log-step shifted adds."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    acc, s = x, 1
    while s < x.shape[1]:
        acc = acc + jnp.where(lane >= s, pltpu.roll(acc, s, 1), 0)
        s *= 2
    return acc - x


def _per_tile(body):
    """Run `body(row)` for each of the block's 8 tiles (a rolled loop keeps
    Mosaic's compile time flat in the bucket count)."""
    def step(r, carry):
        body(pl.ds(r, 1))
        return carry
    jax.lax.fori_loop(0, _ROWS, step, 0)


def _bucket_hist_kernel(buckets_ref, out_ref, *, nb_s: int):
    def one_tile(row):
        oh = _onehot(buckets_ref[row, :], nb_s)
        col = jnp.sum(oh, axis=1, keepdims=True, dtype=jnp.int32)
        out_ref[row, :] = _col_to_row(col, out_ref.shape[1])
    _per_tile(one_tile)


def _tile_rows(x, tile: int):
    """(n,) -> (T8, tile): one tile per row, rows padded to whole 8-row
    blocks with bucket 0 (filler rows are sliced off the outputs)."""
    t = x.shape[0] // tile
    return jnp.pad(x.astype(jnp.int32).reshape(t, tile),
                   ((0, _pad_to(t, _ROWS) - t), (0, 0)))


def bucket_hist_pallas(buckets: jax.Array, num_buckets: int, tile: int = 1024,
                       interpret: bool = False) -> jax.Array:
    """(n,) int32 bucket ids -> (n//tile, num_buckets) per-tile histograms.

    Each grid step histograms 8 tiles; the bucket axis is padded to 8
    sublanes inside the kernel and to 128 lanes in the output block."""
    n = buckets.shape[0]
    if n % tile != 0:
        raise ValueError(f"n {n} % tile {tile} != 0")
    x = _tile_rows(buckets, tile)
    nb_s, nb_l = _pad_to(num_buckets, _ROWS), _pad_to(num_buckets, _LANES)
    out = pl.pallas_call(
        functools.partial(_bucket_hist_kernel, nb_s=nb_s),
        grid=(x.shape[0] // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, tile), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, nb_l), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], nb_l), jnp.int32),
        interpret=interpret,
    )(x)
    return out[:n // tile, :num_buckets]


def _bucket_pos_kernel(buckets_ref, base_ref, out_ref, *, nb_s: int):
    def one_tile(row):
        oh = _onehot(buckets_ref[row, :], nb_s)
        within = _exclusive_lane_cumsum(oh)  # stable rank in tile
        base = _row_to_col(base_ref[row, :], nb_s)
        # Select own-bucket row without a gather: oh is 1 once per column.
        out_ref[row, :] = jnp.sum(oh * (within + base), axis=0,
                                  keepdims=True, dtype=jnp.int32)
    _per_tile(one_tile)


def bucket_positions_pallas(buckets: jax.Array, base: jax.Array,
                            tile: int = 1024,
                            interpret: bool = False) -> jax.Array:
    """Stable destination slot of every element of a bucket partition.

    buckets: (n,) int32 bucket ids in [0, num_buckets)
    base:    (n//tile, num_buckets) int32 start offset of each (tile, bucket)
             segment (exclusive prefix of the per-tile histograms,
             bucket-major then tile-major).
    returns: (n,) int32 positions -- a permutation of [0, n).
    """
    n = buckets.shape[0]
    if n % tile != 0:
        raise ValueError(f"n {n} % tile {tile} != 0")
    num_buckets = base.shape[1]
    x = _tile_rows(buckets, tile)
    nb_s, nb_l = _pad_to(num_buckets, _ROWS), _pad_to(num_buckets, _LANES)
    base = jnp.pad(base.astype(jnp.int32),
                   ((0, x.shape[0] - base.shape[0]),
                    (0, nb_l - num_buckets)))
    out = pl.pallas_call(
        functools.partial(_bucket_pos_kernel, nb_s=nb_s),
        grid=(x.shape[0] // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, tile), lambda i: (i, 0)),
                  pl.BlockSpec((_ROWS, nb_l), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, tile), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
        interpret=interpret,
    )(x, base)
    return out[:n // tile].reshape(n)


class PartitionPlan(NamedTuple):
    """One reusable histogram/rank plan of a stable bucket partition.

    Built from a single histogram pass; applying it to a payload lane is one
    scatter (`positions`), so any number of lanes -- and, for multi-digit
    bucket keys, any number of routing hops whose digit order matches the
    bucket-major layout -- share the same plan. `aggregation.route_tiles`
    applies one plan to an arbitrary LIST of payload lanes (the lane-list
    transport API); `tile_slots` below is the shared slot math it scatters
    through.
    """
    positions: jax.Array  # (n,) int32 destination slot of every element
    totals: jax.Array     # (num_buckets,) int32 per-bucket counts (no pads)
    starts: jax.Array     # (num_buckets,) int32 exclusive prefix of totals

    def tile_slots(self, key: jax.Array, valid: jax.Array, capacity: int):
        """Padded-tile destination of every element under this plan.

        Convention: the plan was built over B = `num_buckets` bucket ids
        where the LAST bucket is the invalid/trash bucket (`key == B - 1`
        for invalid elements); payload rows are the first B - 1 buckets.
        Returns (dst, fill, overflow): `dst` is the flat slot in a
        ((B - 1) * capacity,) destination-major tile, with every dropped
        element (invalid, or past its bucket's capacity) pointed one past
        the end so a scatter with mode='drop' discards it. `fill` is the
        per-bucket valid count clamped to capacity; `overflow` counts the
        clamped-off entries. Stable: within a bucket, stream order.
        """
        num_rows = self.totals.shape[0] - 1
        hist = self.totals[:num_rows]
        within = self.positions - self.starts[key]   # stable rank in bucket
        ok = valid & (key < num_rows) & (within < capacity)
        dst = jnp.where(ok, key * capacity + within, num_rows * capacity)
        fill = jnp.minimum(hist, capacity).astype(jnp.int32)
        overflow = jnp.sum(jnp.maximum(hist - capacity, 0)).astype(jnp.int32)
        return dst, fill, overflow


def make_partition_plan(buckets: jax.Array, num_buckets: int,
                        tile: int = 1024,
                        interpret: bool = False) -> PartitionPlan:
    """Full sort-free partition plan for (n,) int32 bucket ids.

    Pads to a tile multiple internally (pad elements land in the LAST bucket,
    stably after every real element, so real positions never see them --
    callers reserve bucket `num_buckets - 1` as the trash/tail bucket or
    accept a pure tail region). Real elements always land in [0, n).
    """
    n = buckets.shape[0]
    # Whole 128-lane tiles, no larger than the stream needs. VMEM budget:
    # the kernels materialize ~3 (B, tile) int32 arrays; clamp tile so large
    # bucket counts (num_pes at paper scale) stay well inside the scoped
    # VMEM instead of failing to lower.
    tile = min(tile, _pad_to(n, _LANES), _VMEM_BUDGET_ELEMS // num_buckets)
    tile = max(_LANES, tile // _LANES * _LANES)
    pad = (-n) % tile
    if pad:
        buckets = jnp.concatenate(
            [buckets.astype(jnp.int32),
             jnp.full((pad,), num_buckets - 1, jnp.int32)])
    else:
        buckets = buckets.astype(jnp.int32)
    hist = bucket_hist_pallas(buckets, num_buckets, tile, interpret=interpret)
    totals = hist.sum(axis=0)
    bucket_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(totals)[:-1].astype(jnp.int32)])
    tiles_before = (jnp.cumsum(hist, axis=0) - hist).astype(jnp.int32)
    base = bucket_start[None, :] + tiles_before
    pos = bucket_positions_pallas(buckets, base, tile, interpret=interpret)
    if pad:
        pos = pos[:n]
        totals = totals - jnp.asarray(
            [0] * (num_buckets - 1) + [pad], jnp.int32)
    return PartitionPlan(positions=pos, totals=totals, starts=bucket_start)


def partition_plan(buckets: jax.Array, num_buckets: int, tile: int = 1024,
                   interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Back-compat wrapper: (positions, totals) of `make_partition_plan`."""
    plan = make_partition_plan(buckets, num_buckets, tile,
                               interpret=interpret)
    return plan.positions, plan.totals
