"""Pallas TPU kernels: sorted-run sweeps (the Accumulate phase).

Paper Alg. 1 `Accumulate`: one comparison pass over the sorted k-mer stream.
Two kernels, both tiled over the stream:

1. `segment_boundaries_pallas`: run-start flags only (the compare pass).
   Cross-tile dependence (first element of a tile compares against the last
   element of the previous tile) is resolved by passing a second input block
   offset by one tile -- each instance reads its own tile plus the single
   preceding word, so tiles stay independent and the grid is fully parallel.
2. `segment_accumulate_pallas`: the FUSED boundary + segment-sum sweep.
   The old data path paid two extra passes after the boundary kernel -- an
   XLA `jax.ops.segment_sum` over the weights plus a gather for the run
   keys -- re-reading the received stream that Eq. 13 charges for exactly
   one streaming read. The fused kernel reads (keys, weights) once and
   emits, per element: the run-start flag, the run-end flag, and (at run
   ends only) the completed run's total weight. Per-run totals are an
   inclusive *segmented* cumsum computed tile-locally (plain cumsum minus a
   running-max-selected base at the latest run start); runs that span tiles
   are carried through a VMEM scratch tile -- TPU grids execute
   sequentially per core, so the carry is exact. The caller finishes with
   one O(n) compaction scatter (core/sort.accumulate, impl='fused').
   Mosaic has no scan primitive, so the stream is laid out as (rows, 128)
   tiles in row-major order and every shift and scan is a log-step of
   lane/sublane rolls (`pltpu.roll`) under an iota mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segment_kernel(keys_ref, prev_ref, out_ref, *, sentinel_val: int):
    keys = keys_ref[...]
    dt = keys.dtype.type
    sent = dt(sentinel_val)
    prev = jnp.concatenate([prev_ref[...][-1:], keys[:-1]])
    out_ref[...] = (keys != sent) & (keys != prev)


def segment_boundaries_pallas(sorted_keys: jax.Array, sentinel_val: int,
                              tile: int = 1024, interpret: bool = False
                              ) -> jax.Array:
    """(n,) sorted keys -> (n,) bool run-start flags (sentinel-aware).

    Index 0 is a boundary iff valid (matches ref: prev of the stream is the
    sentinel); ops.py pads a leading sentinel word to make the offset-by-one
    block well-defined for the first tile.
    """
    n = sorted_keys.shape[0]
    if n % tile != 0:
        raise ValueError(f"n {n} % tile {tile} != 0")
    sent = jnp.full((tile,), sentinel_val, sorted_keys.dtype)
    padded = jnp.concatenate([sent, sorted_keys])  # tile-aligned lookback
    grid = (n // tile,)
    return pl.pallas_call(
        functools.partial(_segment_kernel, sentinel_val=sentinel_val),
        grid=grid,
        in_specs=[pl.BlockSpec((tile,), lambda i: (i + 1,)),   # my tile
                  pl.BlockSpec((tile,), lambda i: (i,))],      # previous tile
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.bool_),
        interpret=interpret,
    )(padded, padded)


_LANES = 128
_ROWS = 8          # sublanes of one vreg: the smallest legal row block
_I32_MIN = -(1 << 31)


def _roll_rows(x, shift: int):
    shift %= x.shape[0]
    return pltpu.roll(x, shift, 0) if shift else x


def _stream_prev(x, blk):
    """Each element's predecessor in row-major stream order; element 0
    takes the last element of `blk` (the previous tile)."""
    rows, lanes = x.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    a = pltpu.roll(x, 1, 1)                         # a[r, c] = x[r, c-1]
    b = _roll_rows(a, 1)                            # b[r, 0] = x[r-1, L-1]
    e = _roll_rows(pltpu.roll(blk, 1, 1), 1)        # e[0, 0] = blk[-1, -1]
    return jnp.where(lane == 0, jnp.where(sub == 0, e, b), a)


def _stream_next(x, blk):
    """Each element's successor in row-major stream order; the last element
    takes the first element of `blk` (the next tile)."""
    rows, lanes = x.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    a = pltpu.roll(x, lanes - 1, 1)                 # a[r, c] = x[r, c+1]
    b = _roll_rows(a, rows - 1)                     # b[r, L-1] = x[r+1, 0]
    e = _roll_rows(pltpu.roll(blk, lanes - 1, 1), rows - 1)
    return jnp.where(lane == lanes - 1,
                     jnp.where(sub == rows - 1, e, b), a)


def _stream_scan(x, op, identity: int):
    """Inclusive scan of a (rows, 128) tile in row-major stream order:
    log-step shifted combines along the lanes, then the row totals scanned
    the same way down the sublanes and folded in exclusively."""
    rows, lanes = x.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    ident = jnp.int32(identity)
    s = 1
    while s < lanes:
        x = op(x, jnp.where(lane >= s, pltpu.roll(x, s, 1), ident))
        s *= 2
    tot = jnp.broadcast_to(x[:, lanes - 1:lanes], x.shape)
    s = 1
    while s < rows:
        tot = op(tot, jnp.where(sub >= s, _roll_rows(tot, s), ident))
        s *= 2
    return op(x, jnp.where(sub >= 1, _roll_rows(tot, 1), ident))


def _last(x):
    """The tile's last stream element, broadcast over the tile."""
    rows, lanes = x.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    col = jnp.sum(jnp.where(lane == lanes - 1, x, 0), axis=1, keepdims=True)
    col = jnp.broadcast_to(col, x.shape)
    row = jnp.sum(jnp.where(sub == rows - 1, col, 0), axis=0, keepdims=True)
    return jnp.broadcast_to(row, x.shape)


def _segment_accum_kernel(cur_ref, prev_ref, next_ref, w_ref,
                          isnew_ref, isend_ref, tot_ref, carry_ref, *,
                          sentinel_val: int):
    keys = cur_ref[...]                             # (rows, 128)
    sent = keys.dtype.type(sentinel_val)
    prev = _stream_prev(keys, prev_ref[...])
    nxt = _stream_next(keys, next_ref[...])
    valid = keys != sent
    w = jnp.where(valid, w_ref[...], 0)
    is_new = valid & (keys != prev)
    is_end = valid & (keys != nxt)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.int32)

    # Inclusive segmented cumsum of w via plain cumsum minus the run base:
    # the base of element i is T - w at the latest run start <= i (a running
    # max works because T is non-decreasing), or -carry when the open head
    # run began in an earlier tile.
    total = _stream_scan(w, jnp.add, 0)
    cand = jnp.where(is_new, total - w, -carry_ref[...])
    seg_sum = total - _stream_scan(cand, jnp.maximum, _I32_MIN)
    isnew_ref[...] = is_new.astype(jnp.int32)
    isend_ref[...] = is_end.astype(jnp.int32)
    tot_ref[...] = jnp.where(is_end, seg_sum, 0)
    # Carry the still-open tail run into the next grid step (sorted streams
    # put sentinels last, so an invalid tail element means no open run).
    closed = _last(is_end.astype(jnp.int32)) | _last(
        (~valid).astype(jnp.int32))
    carry_ref[...] = jnp.where(closed != 0, 0, _last(seg_sum))


def segment_accumulate_pallas(sorted_keys: jax.Array, weights: jax.Array,
                              sentinel_val: int, tile: int = 1024,
                              interpret: bool = False):
    """One fused sweep: (n,) sorted keys + int32 weights -> per-element
    (run-start flag, run-end flag, completed-run total at run ends).

    The stream is read exactly once, as (rows, 128) tiles of `tile`
    elements (rounded up to whole 8-row blocks); cross-tile runs are summed
    exactly via the sequential-grid carry. Padding must be `sentinel_val`
    (weights at padded slots are ignored); the stream is padded internally
    to a tile multiple.
    """
    n = sorted_keys.shape[0]
    block = _ROWS * _LANES
    tile = -(-max(tile, block) // block) * block
    rows = tile // _LANES
    dt = sorted_keys.dtype
    keys = sorted_keys
    if dt.itemsize == 4 and dt != jnp.int32:        # equality only: bits do
        keys = jax.lax.bitcast_convert_type(keys, jnp.int32)
    sent = int(np.asarray(sentinel_val, dt).view(keys.dtype))
    pad = (-n) % tile
    edge = jnp.full((tile,), sent, keys.dtype)
    # one leading + one trailing sentinel tile: the lookback (prev key) and
    # lookahead (next key) blocks stay tile-aligned.
    padded = jnp.concatenate(
        [edge, keys, jnp.full((pad,), sent, keys.dtype), edge]
    ).reshape(-1, _LANES)
    w = jnp.concatenate([weights.astype(jnp.int32),
                         jnp.zeros((pad,), jnp.int32)]).reshape(-1, _LANES)
    n_p = n + pad
    spec = lambda off: pl.BlockSpec((rows, _LANES), lambda i: (i + off, 0))
    out = jax.ShapeDtypeStruct((n_p // _LANES, _LANES), jnp.int32)
    is_new, is_end, tot = pl.pallas_call(
        functools.partial(_segment_accum_kernel, sentinel_val=sent),
        grid=(n_p // tile,),
        in_specs=[spec(1), spec(0), spec(2), spec(0)],
        out_specs=[spec(0), spec(0), spec(0)],
        out_shape=[out, out, out],
        scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(padded, padded, padded, w)
    flat = lambda x: x.reshape(-1)[:n]
    return (flat(is_new).astype(jnp.bool_), flat(is_end).astype(jnp.bool_),
            flat(tot))
