"""Pallas TPU kernel: sliding-window minimum (minimizer selection).

The minimizer transport layer (core/minimizer.py) needs, for every k-mer
position of a read, the minimum m-mer word over the window of
w = k - m + 1 consecutive m-mer positions the k-mer covers. That is a
classic sliding-window minimum over the per-position m-mer stream -- the
KMC 2 / MSPKmerCounter signature-selection loop, vectorized: on TPU the
window is small and static, so the minimum is an unrolled w-way
`jnp.minimum` tree over shifted slices (pure VPU work, the same structure
as the shift-or loop in kmer_extract.py), not a monotonic-queue scan.

Tiling: the position axis is tiled in whole 128-lane tiles; an output
tile at position-tile j needs input positions up to `w - 1` past its own
tile end, so each grid instance reads its tile plus the NEXT tile (an
offset-by-one input block, the same cross-tile-carry device the segment
kernels use for their lookback) and slides the window over the
concatenation by lane rolls. Tiles therefore stay independent; the wrapper
pads the position axis with the padding identity so the trailing partial
window positions are well defined, then trims them. `w <= tile` is
enforced by clamping the tile, so one lookahead block always suffices.

The rows axis (reads) is blocked in whole 8-sublane slabs: each instance
owns a (block_rows, tile) slab in VMEM. The TPU has no unsigned minimum,
so unsigned words are compared through their sign-flipped int32 image
(an order-preserving bijection) and mapped back on the way out.

`sliding_min_pair_pallas` is the keyed variant for the hashed minimizer
order (core/owner.py family 4): the minimum is taken over a KEY lane while
the m-mer VALUE lane rides along, so the kernel returns the value whose key
won each window (min-by-key). Strict `<` keeps the earliest position on key
ties; the keys are a bijective hash of the values, so tied keys imply tied
values and the choice is unobservable. Key padding uses the key image's
max, which is never strictly less than any in-window key, so padding never
wins.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128
_ROWS = 8


def _window_views(cur, nxt, window: int):
    """The `window` shifted views of a (rows, tile) block over its lookahead:
    view j holds position p + j at p, for every output position p < tile.
    Lane rolls of the (rows, 2 * tile) concatenation, read off the first
    tile, keep every slice 128-lane aligned."""
    tile = cur.shape[-1]
    ext = jnp.concatenate([cur, nxt], axis=-1)
    for j in range(window):
        view = pltpu.roll(ext, 2 * tile - j, 1) if j else ext
        yield view[:, :tile]


def _sliding_min_kernel(cur_ref, nxt_ref, out_ref, *, window: int):
    acc = None
    for v in _window_views(cur_ref[...], nxt_ref[...], window):
        acc = v if acc is None else jnp.minimum(acc, v)
    out_ref[...] = acc


def _sliding_min_pair_kernel(kcur_ref, knxt_ref, vcur_ref, vnxt_ref,
                             kout_ref, vout_ref, *, window: int):
    ak = av = None
    for nk, nv in zip(_window_views(kcur_ref[...], knxt_ref[...], window),
                      _window_views(vcur_ref[...], vnxt_ref[...], window)):
        if ak is None:
            ak, av = nk, nv
            continue
        take = nk < ak                       # strict: earliest wins key ties
        ak = jnp.minimum(ak, nk)
        av = jnp.where(take, nv, av)
    kout_ref[...] = ak
    vout_ref[...] = av


def _to_ordered_i32(x):
    """Order-preserving map into int32 (the TPU has no unsigned min):
    unsigned words flip their sign bit, signed 32-bit words pass."""
    if x.dtype == jnp.int32:
        return x
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x ^ jnp.uint32(1 << 31),
                                            jnp.int32)
    return x                                 # 64-bit: interpret mode only


def _from_ordered_i32(x, dtype):
    if x.dtype == dtype:
        return x
    return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(1 << 31)


def _bits_i32(x):
    """Carried (never compared) lanes travel as their int32 bit pattern."""
    if x.dtype.itemsize == 4 and x.dtype != jnp.int32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x


def _windowed_call(kernel, lanes, pads, window: int, block_rows: int,
                   tile: int, interpret: bool):
    """Shared tiling of the sliding-window kernels: rows padded to whole
    `block_rows` blocks (rounded up to 8 sublanes), positions to whole
    128-lane tiles plus one lookahead tile (filled with each lane's `pads`
    value), each lane passed twice (own tile, next tile)."""
    n_rows, n_pos = lanes[0].shape
    if window < 1 or window > n_pos:
        raise ValueError(f"window {window} outside [1, {n_pos}]")
    block_rows = -(-block_rows // _ROWS) * _ROWS
    n_out = n_pos - window + 1
    tile = max(window, min(tile, n_out))
    tile = -(-tile // _LANES) * _LANES       # whole lane tiles, >= window
    n_tiles = -(-n_out // tile)
    rows_p = -(-n_rows // block_rows) * block_rows
    padded = [jnp.pad(x, ((0, rows_p - n_rows),
                          (0, (n_tiles + 1) * tile - n_pos)),
                      constant_values=p) for x, p in zip(lanes, pads)]
    cur = pl.BlockSpec((block_rows, tile), lambda i, j: (i, j))
    nxt = pl.BlockSpec((block_rows, tile), lambda i, j: (i, j + 1))
    outs = pl.pallas_call(
        functools.partial(kernel, window=window),
        grid=(rows_p // block_rows, n_tiles),
        in_specs=[cur, nxt] * len(lanes),
        out_specs=[cur] * len(lanes),
        out_shape=[jax.ShapeDtypeStruct((rows_p, n_tiles * tile), x.dtype)
                   for x in lanes],
        interpret=interpret,
    )(*[x for x in padded for _ in range(2)])
    return [o[:n_rows, :n_out] for o in outs]


def sliding_min_pallas(vals: jax.Array, window: int, block_rows: int = 8,
                       tile: int = 512, interpret: bool = False) -> jax.Array:
    """(n_rows, n_pos) words -> (n_rows, n_pos - window + 1) windowed minima.

    out[r, p] = min(vals[r, p : p + window]). The minimum is taken on an
    order-preserving int32 image of the words (sign bit flipped for
    unsigned words), whose max is the padding identity: callers whose valid
    words span the full dtype range (they do not: packed m-mers keep at
    least the sentinel's spare bits free) would see padding win ties
    harmlessly -- equal values tie to the same minimum either way.
    """
    x = _to_ordered_i32(vals)
    (out,) = _windowed_call(_sliding_min_kernel, [x],
                            [jnp.iinfo(x.dtype).max], window, block_rows,
                            tile, interpret)
    return _from_ordered_i32(out, vals.dtype)


def sliding_min_pair_pallas(keys: jax.Array, vals: jax.Array, window: int,
                            block_rows: int = 8, tile: int = 512,
                            interpret: bool = False):
    """Min-by-key sliding window: (keys, vals) (n_rows, n_pos) each ->
    ((n_rows, n_out) keys, (n_rows, n_out) vals) where out position p holds
    the key/value pair with the minimum KEY over [p, p + window). Earliest
    position wins key ties (strict `<`); key padding is the key image's
    max, so trailing partial windows never select padding.
    """
    if keys.shape != vals.shape:
        raise ValueError(f"keys {keys.shape} != vals {vals.shape}")
    k = _to_ordered_i32(keys)
    v = _bits_i32(vals)
    kout, vout = _windowed_call(_sliding_min_pair_kernel, [k, v],
                                [jnp.iinfo(k.dtype).max, 0], window,
                                block_rows, tile, interpret)
    if v.dtype != vals.dtype:
        vout = jax.lax.bitcast_convert_type(vout, vals.dtype)
    return _from_ordered_i32(kout, keys.dtype), vout
