"""Jitted public wrappers for the Pallas kernels.

Backend dispatch: Pallas kernels lower to Mosaic only on TPU. Elsewhere (the
CPU test environment) the wrappers run the kernels in `interpret=True` mode
-- the kernel *body* executes with real Python/XLA semantics, so
correctness of the tiled algorithm is what the tests validate. On the TPU
every wrapper compiles its kernel; nothing falls back to interpret mode or
to the `ref.*` oracles, and a 64-bit word (k >= 16) is refused with
`WideWordUnsupported` rather than routed anywhere else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hash_table import (hash_insert_pallas, hash_lookup_pallas,
                                      insert_group)
from repro.kernels.kmer_extract import kmer_extract_pallas
from repro.kernels.minimizer import (sliding_min_pallas,
                                     sliding_min_pair_pallas)
from repro.kernels.radix_hist import radix_hist_pallas
from repro.kernels.radix_partition import (PartitionPlan, bucket_hist_pallas,
                                           bucket_positions_pallas,
                                           make_partition_plan as
                                           _make_partition_plan,
                                           partition_plan)
from repro.kernels.segment_count import (segment_accumulate_pallas,
                                         segment_boundaries_pallas)


class WideWordUnsupported(TypeError):
    """A 64-bit k-mer word reached a Pallas kernel compiled for the TPU.

    Mosaic kernels work on 32-bit lanes, so k >= 16 (a uint64 word,
    `encoding.kmer_dtype`) cannot run on the chip until words travel as two
    uint32 lanes (ROADMAP Reach item 3). k <= 15 runs on the TPU today.
    """


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _interpret_for(*operands) -> bool:
    """The `interpret` flag for a kernel over `operands`; on the compiled
    (TPU) path, refuses any 64-bit operand."""
    if _interpret():
        return True
    wide = [str(x.dtype) for x in operands if x.dtype.itemsize == 8]
    if wide:
        raise WideWordUnsupported(
            f"Pallas kernels on the TPU take 32-bit words, got {wide}: "
            "k >= 16 needs two-lane words (ROADMAP Reach item 3)")
    return False


@functools.partial(jax.jit, static_argnums=(1, 2, 3),
                   static_argnames=("k", "bits_per_symbol", "block_reads",
                                    "canonical"))
def kmer_extract(reads: jax.Array, k: int, bits_per_symbol: int = 2,
                 block_reads: int = 8, *,
                 canonical: bool = False) -> jax.Array:
    if not _interpret() and bits_per_symbol * k > 30:
        raise WideWordUnsupported(
            f"k={k} packs into a 64-bit word; Pallas kernels on the TPU "
            "take 32-bit words (ROADMAP Reach item 3)")
    return kmer_extract_pallas(reads, k, bits_per_symbol,
                               block_reads=block_reads, canonical=canonical,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def sliding_min(vals: jax.Array, window: int, block_rows: int = 8,
                tile: int = 512) -> jax.Array:
    """(n_rows, n_pos) -> (n_rows, n_pos - window + 1) windowed minima
    (minimizer selection; kernels/minimizer.py)."""
    return sliding_min_pallas(vals, window, block_rows=block_rows, tile=tile,
                              interpret=_interpret_for(vals))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def sliding_min_pair(keys: jax.Array, vals: jax.Array, window: int,
                     block_rows: int = 8, tile: int = 512):
    """Min-by-KEY sliding window carrying a value lane: ((n_rows, n_out)
    keys, (n_rows, n_out) vals) -- the hashed minimizer order's selection
    primitive (kernels/minimizer.py)."""
    return sliding_min_pair_pallas(keys, vals, window, block_rows=block_rows,
                                   tile=tile,
                                   interpret=_interpret_for(keys, vals))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def radix_hist(keys: jax.Array, shift: int, digit_bits: int = 4,
               tile: int = 1024) -> jax.Array:
    return radix_hist_pallas(keys, shift, digit_bits, tile=tile,
                             interpret=_interpret_for(keys))


@functools.partial(jax.jit, static_argnames=("sentinel_val", "tile"))
def segment_boundaries(sorted_keys: jax.Array, *, sentinel_val: int,
                       tile: int = 1024) -> jax.Array:
    return segment_boundaries_pallas(sorted_keys, sentinel_val, tile=tile,
                                     interpret=_interpret_for(sorted_keys))


@functools.partial(jax.jit, static_argnames=("sentinel_val", "tile"))
def segment_accumulate(sorted_keys: jax.Array, weights: jax.Array, *,
                       sentinel_val: int, tile: int = 1024):
    """Fused boundary + segmented-sum sweep: (is_new, is_end, run_totals)."""
    return segment_accumulate_pallas(sorted_keys, weights, sentinel_val,
                                     tile=tile,
                                     interpret=_interpret_for(sorted_keys))


def _hash_insert(table_keys, table_counts, keys, weights, slots, *,
                 sentinel_val: int, tile: int, impl: str):
    n = keys.shape[0]
    tile = min(tile, max(8, n))
    pad = (-n) % tile
    if pad:
        sent = jnp.full((pad,), sentinel_val, keys.dtype)
        keys = jnp.concatenate([keys, sent])
        weights = jnp.concatenate([weights.astype(jnp.int32),
                                   jnp.zeros((pad,), jnp.int32)])
        slots = jnp.concatenate([slots.astype(jnp.int32),
                                 jnp.zeros((pad,), jnp.int32)])
    if impl == "auto":
        impl = "ref" if _interpret_for(table_keys) else "pallas"
    if impl == "ref":
        return ref.hash_insert_ref(
            table_keys, table_counts, keys, weights, slots, sentinel_val,
            group=insert_group(table_keys.shape[0], tile))
    if impl != "pallas":
        raise ValueError(f"unknown hash_insert impl {impl!r}")
    return hash_insert_pallas(table_keys, table_counts, keys, weights, slots,
                              sentinel_val, tile=tile,
                              interpret=_interpret_for(table_keys))


@functools.partial(jax.jit, static_argnames=("sentinel_val", "tile", "impl"))
def hash_insert(table_keys: jax.Array, table_counts: jax.Array,
                keys: jax.Array, weights: jax.Array, slots: jax.Array, *,
                sentinel_val: int, tile: int = 1024, impl: str = "auto"):
    """Insert-or-add a (keys, weights, slots) batch into the open-addressing
    count table; returns (new_keys, new_counts, dropped). Pads the batch to
    a tile multiple with skipped (sentinel, weight-0) entries.

    impl: 'auto' = the Pallas kernel on TPU, the bit-identical jnp oracle
    elsewhere. Unlike the other kernels, off-TPU 'auto' does NOT interpret:
    interpret-mode state discharge turns each row store into an
    O(capacity) buffer update (far slower than the oracle's in-place
    scan), so emulation is opt-in ('pallas', what the parity tests run)
    rather than the CPU default. On the TPU, 'auto' never picks the
    oracle: a 64-bit table raises `WideWordUnsupported`.
    """
    return _hash_insert(table_keys, table_counts, keys, weights, slots,
                        sentinel_val=sentinel_val, tile=tile, impl=impl)[:3]


@functools.partial(jax.jit, static_argnames=("sentinel_val", "tile", "impl"))
def hash_insert_counted(table_keys: jax.Array, table_counts: jax.Array,
                        keys: jax.Array, weights: jax.Array,
                        slots: jax.Array, *, sentinel_val: int,
                        tile: int = 1024, impl: str = "auto"):
    """`hash_insert` plus the insert's (2,) int32 group counts: groups of
    consecutive items that held a live item, and those of them that fell
    back to the one-item walk (`hash_table.insert_group`; both 0 for a
    table too small to group). Returns (new_keys, new_counts, dropped,
    groups)."""
    return _hash_insert(table_keys, table_counts, keys, weights, slots,
                        sentinel_val=sentinel_val, tile=tile, impl=impl)


@functools.partial(jax.jit, static_argnames=("sentinel_val", "tile", "impl"))
def hash_lookup(table_keys: jax.Array, table_counts: jax.Array,
                keys: jax.Array, slots: jax.Array, *,
                sentinel_val: int, tile: int = 1024, impl: str = "auto"):
    """Read-only batched probe of the open-addressing count table; returns
    (counts, probes), both (n,) int32 -- counts[i] is the stored count of
    keys[i] (0 = miss), probes[i] the probe-walk length (the serving
    probe-depth stat). Sentinel keys skip with count 0. Pads the batch to a
    tile multiple with skipped sentinel entries.

    impl follows the `hash_insert` discipline: 'auto' = the Pallas kernel
    on TPU, the bit-identical jnp oracle elsewhere (interpret-mode scalar
    probing costs O(capacity) per lookup, so emulation is opt-in via
    'pallas' -- what the parity tests run).
    """
    n = keys.shape[0]
    tile = min(tile, max(8, n))
    pad = (-n) % tile
    if pad:
        keys = jnp.concatenate(
            [keys, jnp.full((pad,), sentinel_val, keys.dtype)])
        slots = jnp.concatenate([slots.astype(jnp.int32),
                                 jnp.zeros((pad,), jnp.int32)])
    if impl == "auto":
        impl = "ref" if _interpret_for(table_keys) else "pallas"
    if impl == "ref":
        counts, probes = ref.hash_lookup_ref(table_keys, table_counts, keys,
                                             slots, sentinel_val)
    elif impl == "pallas":
        counts, probes = hash_lookup_pallas(
            table_keys, table_counts, keys, slots, sentinel_val, tile=tile,
            interpret=_interpret_for(table_keys))
    else:
        raise ValueError(f"unknown hash_lookup impl {impl!r}")
    return counts[:n], probes[:n]


@functools.partial(jax.jit, static_argnums=(1, 2))
def bucket_hist(buckets: jax.Array, num_buckets: int,
                tile: int = 1024) -> jax.Array:
    return bucket_hist_pallas(buckets, num_buckets, tile,
                              interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(2,))
def bucket_positions(buckets: jax.Array, base: jax.Array,
                     tile: int = 1024) -> jax.Array:
    return bucket_positions_pallas(buckets, base, tile,
                                   interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(1, 2))
def radix_partition_plan(buckets: jax.Array, num_buckets: int,
                         tile: int = 1024):
    """(positions, per-bucket totals) of the stable sort-free partition."""
    return partition_plan(buckets, num_buckets, tile, interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_partition_plan(buckets: jax.Array, num_buckets: int,
                        tile: int = 1024) -> PartitionPlan:
    """Reusable PartitionPlan (positions, totals, starts); ONE histogram
    launch, applied to any number of payload lanes by the caller."""
    return _make_partition_plan(buckets, num_buckets, tile,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(1,))
def make_partition_plan_ref(buckets: jax.Array,
                            num_buckets: int) -> PartitionPlan:
    """Stable-argsort oracle of `make_partition_plan` (bit-identical plan;
    see kernels/ref.py). The `impl='argsort'` path of the lane-list routing
    engine builds its plan here so both impls share one tile-build."""
    return ref.partition_plan_ref(buckets, num_buckets)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "q_offset", "block_q", "block_k",
    "impl"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    impl: str = "auto") -> jax.Array:
    """impl: 'auto' (pallas on TPU, else interpret), 'pallas', 'ref'.

    'ref' is the differentiable path used inside train_step; 'auto' is the
    serving path.
    """
    if impl == "ref":
        return ref.mha_ref(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale, q_offset=q_offset)
    interpret = _interpret() if impl == "auto" else (impl != "pallas")
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  q_offset=q_offset, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k"))
def flash_attention_trainable(q: jax.Array, k: jax.Array, v: jax.Array, *,
                              causal: bool = True,
                              window: Optional[int] = None,
                              softcap: Optional[float] = None,
                              scale: Optional[float] = None,
                              block_q: int = 128, block_k: int = 128
                              ) -> jax.Array:
    """Flash attention with the Pallas BACKWARD kernels (training path).

    Forward saves only the per-row logsumexp; backward recomputes
    probabilities blockwise (flash_attention_bwd.py). GQA: kv expands to
    query heads for the kernels; dk/dv group-sum back.
    """
    from repro.kernels.flash_attention import flash_attention_fwd_lse
    from repro.kernels.flash_attention_bwd import flash_attention_bwd_pallas

    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    resolved_scale = d ** -0.5 if scale is None else scale
    interp = _interpret()

    @jax.custom_vjp
    def _flash(q, k, v):
        kq = jnp.repeat(k, group, axis=1)
        vq = jnp.repeat(v, group, axis=1)
        o, _ = flash_attention_fwd_lse(
            q, kq, vq, causal=causal, window=window, softcap=softcap,
            scale=resolved_scale, block_q=block_q, block_k=block_k,
            interpret=interp)
        return o

    def _fwd(q, k, v):
        kq = jnp.repeat(k, group, axis=1)
        vq = jnp.repeat(v, group, axis=1)
        o, lse = flash_attention_fwd_lse(
            q, kq, vq, causal=causal, window=window, softcap=softcap,
            scale=resolved_scale, block_q=block_q, block_k=block_k,
            interpret=interp)
        return o, (q, kq, vq, o, lse)

    def _bwd(res, do):
        q, kq, vq, o, lse = res
        dq, dk_full, dv_full = flash_attention_bwd_pallas(
            q, kq, vq, o, lse, do, scale=resolved_scale, causal=causal,
            window=window, softcap=softcap, block_q=block_q,
            block_k=block_k, interpret=interp)
        skv = kq.shape[2]
        dk = dk_full.reshape(b, hkv, group, skv, d).sum(axis=2)
        dv = dv_full.reshape(b, hkv, group, skv, d).sum(axis=2)
        return (dq.astype(q.dtype), dk.astype(kq.dtype),
                dv.astype(vq.dtype))

    _flash.defvjp(_fwd, _bwd)
    return _flash(q, k, v)
