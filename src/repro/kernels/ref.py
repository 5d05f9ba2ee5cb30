"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` is the semantic ground truth: kernels must match it to
float/integer exactness (tests sweep shapes and dtypes against these).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import encoding


# --- kmer_extract ----------------------------------------------------------

def kmer_extract_ref(reads: jax.Array, k: int, bits_per_symbol: int = 2,
                     canonical: bool = False) -> jax.Array:
    """(n_reads, m) codes -> (n_reads, m-k+1) packed words.

    canonical=True is the SWEEP oracle: pack forward words, then the
    separate O(k) revcomp pass -- the semantic ground truth the fused
    in-loop canonicalization must match bit-for-bit.
    """
    words = encoding.pack_kmers(reads, k, bits_per_symbol)
    if canonical:
        words = encoding.canonical(words, k)
    return words


# --- minimizer --------------------------------------------------------------

def sliding_min_ref(vals: jax.Array, window: int) -> jax.Array:
    """(n_rows, n_pos) -> (n_rows, n_pos - window + 1) sliding-window minima.

    out[r, p] = min(vals[r, p : p + window]) -- the semantic ground truth
    for `sliding_min_pallas` (minimizer selection), bit-identical including
    tie behavior (ties have no observable order: only the value is kept).
    """
    n_out = vals.shape[-1] - window + 1
    acc = jax.lax.slice_in_dim(vals, 0, n_out, axis=-1)
    for j in range(1, window):
        acc = jnp.minimum(acc, jax.lax.slice_in_dim(vals, j, j + n_out,
                                                    axis=-1))
    return acc


def sliding_min_pair_ref(keys: jax.Array, vals: jax.Array, window: int):
    """Min-by-key oracle of `sliding_min_pair_pallas`: out position p holds
    the (key, value) whose KEY is minimal over [p, p + window), earliest
    position winning key ties (strict `<` take rule -- bit-identical to the
    kernel; with bijective hash keys, tied keys imply tied values anyway).
    """
    n_out = keys.shape[-1] - window + 1
    ak = jax.lax.slice_in_dim(keys, 0, n_out, axis=-1)
    av = jax.lax.slice_in_dim(vals, 0, n_out, axis=-1)
    for j in range(1, window):
        nk = jax.lax.slice_in_dim(keys, j, j + n_out, axis=-1)
        nv = jax.lax.slice_in_dim(vals, j, j + n_out, axis=-1)
        take = nk < ak
        ak = jnp.minimum(ak, nk)
        av = jnp.where(take, nv, av)
    return ak, av


# --- radix_hist -------------------------------------------------------------

def radix_hist_ref(keys: jax.Array, shift: int, digit_bits: int,
                   tile: int) -> jax.Array:
    """Per-tile digit histograms: (n,) keys -> (n//tile, 2**digit_bits) int32.

    The histogram pass of an LSD radix sort (paper Eq. 12/13's per-pass
    streaming sweep); tiles correspond to the blocks a TPU core would stream
    through VMEM.
    """
    radix = 1 << digit_bits
    dt = keys.dtype.type
    digits = ((keys >> dt(shift)) & dt(radix - 1)).astype(jnp.int32)
    tiles = digits.reshape(-1, tile)
    return jax.vmap(lambda d: jnp.bincount(d, length=radix))(tiles).astype(
        jnp.int32)


# --- radix_partition --------------------------------------------------------

def partition_plan_ref(buckets: jax.Array, num_buckets: int):
    """Stable-argsort oracle of `make_partition_plan`: the same
    (positions, totals, starts) plan object, built from one comparison sort
    instead of the histogram/rank kernels.

    The positions of a stable bucket partition are exactly each element's
    rank in the stable sort by bucket id, so the two builders are
    bit-identical -- `aggregation.route_tiles` selects between them with its
    `impl` knob and runs ONE shared tile-build on either plan.
    """
    from repro.kernels.radix_partition import PartitionPlan

    n = buckets.shape[0]
    b = buckets.astype(jnp.int32)
    order = jnp.argsort(b, stable=True)
    positions = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    totals = jnp.bincount(b, length=num_buckets).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(totals)[:-1].astype(jnp.int32)])
    return PartitionPlan(positions=positions, totals=totals, starts=starts)


def bucket_hist_ref(buckets: jax.Array, num_buckets: int,
                    tile: int) -> jax.Array:
    """Per-tile bucket histograms: (n,) int32 ids -> (n//tile, B) int32."""
    tiles = buckets.astype(jnp.int32).reshape(-1, tile)
    return jax.vmap(lambda b: jnp.bincount(b, length=num_buckets))(
        tiles).astype(jnp.int32)


def bucket_positions_ref(buckets: jax.Array, base: jax.Array,
                         tile: int) -> jax.Array:
    """Stable partition slots via the argsort oracle: (n,) int32 positions.

    Semantically: element i goes to base[i//tile, buckets[i]] + (stable rank
    of i among equal-bucket elements of its tile).
    """
    n = buckets.shape[0]
    b = buckets.astype(jnp.int32).reshape(-1, tile)

    def one_tile(bt, baset):
        order = jnp.argsort(bt, stable=True)
        rank_sorted = jnp.arange(tile) - jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(jnp.bincount(bt, length=baset.shape[0]))[:-1].astype(
                 jnp.int32)])[bt[order]]
        within = jnp.zeros((tile,), jnp.int32).at[order].set(rank_sorted)
        return baset[bt] + within

    return jax.vmap(one_tile)(b, base).reshape(n)


# --- segment_count ----------------------------------------------------------

def segment_boundaries_ref(sorted_keys: jax.Array, sentinel_val: int
                           ) -> jax.Array:
    """Boundary flags of runs in a sorted array (the Accumulate sweep's
    comparison pass). bool (n,): True at the first element of each valid run.
    """
    sent = sorted_keys.dtype.type(sentinel_val)
    prev = jnp.concatenate([jnp.full((1,), sent, sorted_keys.dtype),
                            sorted_keys[:-1]])
    return (sorted_keys != sent) & (sorted_keys != prev)


def segment_accumulate_ref(sorted_keys: jax.Array, weights: jax.Array,
                           sentinel_val: int):
    """Fused-sweep oracle: (is_new, is_end, run_totals) of a sorted stream.

    is_new / is_end flag the first / last element of each valid run;
    run_totals holds the run's summed weight at its last element (0
    elsewhere). Semantic ground truth for `segment_accumulate_pallas`.
    """
    n = sorted_keys.shape[0]
    sent = sorted_keys.dtype.type(sentinel_val)
    valid = sorted_keys != sent
    w = jnp.where(valid, weights.astype(jnp.int32), 0)
    prev = jnp.concatenate([jnp.full((1,), sent, sorted_keys.dtype),
                            sorted_keys[:-1]])
    nxt = jnp.concatenate([sorted_keys[1:],
                           jnp.full((1,), sent, sorted_keys.dtype)])
    is_new = valid & (sorted_keys != prev)
    is_end = valid & (sorted_keys != nxt)
    seg = jnp.maximum(jnp.cumsum(is_new.astype(jnp.int32)) - 1, 0)
    sums = jax.ops.segment_sum(w, seg, num_segments=n)
    run_tot = jnp.where(is_end, sums[seg], 0)
    return is_new, is_end, run_tot


# --- hash_table -------------------------------------------------------------

def hash_insert_ref(table_keys: jax.Array, table_counts: jax.Array,
                    keys: jax.Array, weights: jax.Array, slots: jax.Array,
                    sentinel_val: int, group: int = 0):
    """Sequential insert-or-add oracle: fold the batch in stream order.

    Linear probing from `slots[i]` wrapping modulo capacity: first empty
    slot inserts, first matching key adds; a probe sweep that visits every
    slot drops the item and counts it. Semantic ground truth for
    `hash_insert_pallas` -- the final table state must match bit-for-bit
    (slot layout included, since both fold in stream order).

    `group` > 0 also counts what the kernel's grouped path does with each
    run of `group` consecutive items (the batch length a multiple of it):
    a group with a live item falls back unless every live item's home row
    (128-lane rows), from its home lane on, holds the sentinel or its key,
    and no two live items share a home row -- judged on the table as the
    group starts. Returns (new_keys, new_counts, dropped, groups), groups
    the (2,) int32 (groups with a live item, groups that fell back); both
    0 when `group` is 0.
    """
    cap = table_keys.shape[0]
    sent = table_keys.dtype.type(sentinel_val)

    def fold_one(carry, x):
        tk, tc, dropped = carry
        key, w, slot0 = x
        valid = (key != sent) & (w > 0)

        def probing(state):
            j, _, st = state
            return valid & (st == 0) & (j < cap)

        def probe(state):
            j, slot, _ = state
            cur = tk[slot]
            st = jnp.where(cur == sent, 1, jnp.where(cur == key, 2, 0))
            nxt = jnp.where(slot + 1 == cap, 0, slot + 1)
            return (j + jnp.int32(1), jnp.where(st == 0, nxt, slot),
                    st.astype(jnp.int32))

        _, slot, st = jax.lax.while_loop(
            probing, probe, (jnp.int32(0), slot0, jnp.int32(0)))
        hit = (st == 1) | (st == 2)
        tk = tk.at[slot].set(jnp.where(st == 1, key, tk[slot]))
        tc = tc.at[slot].add(jnp.where(hit, w, jnp.int32(0)))
        dropped = dropped + jnp.where(valid & (st == 0),
                                      jnp.int32(1), jnp.int32(0))
        return (tk, tc, dropped), None

    xs = (keys, weights.astype(jnp.int32), slots.astype(jnp.int32))
    init = (table_keys, table_counts.astype(jnp.int32), jnp.int32(0))
    if not group:
        (tk, tc, dropped), _ = jax.lax.scan(fold_one, init, xs)
        return tk, tc, dropped, jnp.zeros((2,), jnp.int32)

    lanes = 128
    lane = jnp.arange(lanes, dtype=jnp.int32)
    pair = ~jnp.eye(group, dtype=bool)

    def group_stats(tk, gk, gw, gs):
        live = (gk != sent) & (gw > 0)
        rows = gs // lanes
        home = jnp.stack([jax.lax.dynamic_slice(tk, (r * lanes,), (lanes,))
                          for r in rows])
        hit = (lane >= (gs % lanes)[:, None]) & (
            (home == sent) | (home == gk[:, None]))
        landed = jnp.all(hit.any(axis=1) | ~live)
        clash = jnp.any((rows[:, None] == rows[None, :]) & pair
                        & live[:, None] & live[None, :])
        any_live = live.any()
        return jnp.stack([any_live, any_live & (clash | ~landed)])

    def fold_item(carry, x):
        # one flat scan that judges each next group right after the item
        # that ends the group before it (reading the table before an
        # item's update, or in a scan nested per group, copies it)
        tk, tc, dropped, stats = carry
        item, last, gx = x
        (tk, tc, dropped), _ = fold_one((tk, tc, dropped), item)
        stats = stats + jnp.where(last, group_stats(tk, *gx), False)
        return (tk, tc, dropped, stats), None

    n = keys.shape[0]
    grouped = tuple(x.reshape(-1, group) for x in xs)
    nxt = tuple(jnp.repeat(jnp.roll(x, -1, axis=0), group, axis=0)
                for x in grouped)
    last = (jnp.arange(n) % group == group - 1) & (jnp.arange(n) < n - group)
    stats = group_stats(table_keys, *(x[0] for x in grouped))
    (tk, tc, dropped, stats), _ = jax.lax.scan(
        fold_item, init + (stats.astype(jnp.int32),), (xs, last, nxt))
    return tk, tc, dropped, stats


def hash_lookup_ref(table_keys: jax.Array, table_counts: jax.Array,
                    keys: jax.Array, slots: jax.Array, sentinel_val: int):
    """Read-only probe oracle: per-key counts from the committed table.

    The same probe walk as `hash_insert_ref` (linear from `slots[i]`, wrap
    modulo capacity, stop at empty or match) but never writing: a match
    reads the slot's count, an empty slot or an exhausted sweep is a miss
    (count 0); sentinel keys (batch padding) skip with count 0. Semantic
    ground truth for `hash_lookup_pallas` -- (counts, probes) must match
    bit-for-bit, probe step counts included.
    Returns (counts, probes), both (n,) int32.
    """
    cap = table_keys.shape[0]
    sent = table_keys.dtype.type(sentinel_val)
    tc = table_counts.astype(jnp.int32)

    def probe_one(_, x):
        key, slot0 = x
        valid = key != sent

        def probing(state):
            j, _, st = state
            return valid & (st == 0) & (j < cap)

        def probe(state):
            j, slot, _ = state
            cur = table_keys[slot]
            st = jnp.where(cur == sent, 1, jnp.where(cur == key, 2, 0))
            nxt = jnp.where(slot + 1 == cap, 0, slot + 1)
            return (j + jnp.int32(1), jnp.where(st == 0, nxt, slot),
                    st.astype(jnp.int32))

        j, slot, st = jax.lax.while_loop(
            probing, probe, (jnp.int32(0), slot0, jnp.int32(0)))
        cnt = jnp.where((st == 2) & valid, tc[slot], jnp.int32(0))
        prb = jnp.where(valid, j, jnp.int32(0))
        return 0, (cnt, prb)

    _, (counts, probes) = jax.lax.scan(
        probe_one, 0, (keys, slots.astype(jnp.int32)))
    return counts, probes


# --- flash_attention --------------------------------------------------------

def flash_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              window: Optional[int] = None,
              softcap: Optional[float] = None,
              scale: Optional[float] = None,
              q_offset: int = 0,
              block_q: int = 1024, block_k: int = 1024) -> jax.Array:
    """Blockwise online-softmax attention in pure jnp (differentiable).

    The XLA-level twin of the Pallas kernel: a scan over q blocks with an
    inner scan over kv blocks keeps only (block_q, block_k) logits live, so
    32k-token prefill never materializes the (S, S) score matrix (36 GB ->
    ~2 GB temp on the prefill_32k cells -- EXPERIMENTS.md §Perf). Blocks
    fully outside the causal/window band are skipped via lax.cond, so SWA
    archs also keep their FLOP advantage. Used by models/attention.py for
    long sequences; gradients flow through the scans (remat-friendly).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    sq_pad, skv_pad = (-sq) % bq, (-skv) % bk
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
    if skv_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
    nq, nk = (sq + sq_pad) // bq, (skv + skv_pad) // bk
    kb = jnp.moveaxis(k.reshape(b, hkv, nk, bk, d), 2, 0)  # (nk,B,Hkv,bk,D)
    vb = jnp.moveaxis(v.reshape(b, hkv, nk, bk, d), 2, 0)
    qb = jnp.moveaxis(q.reshape(b, hq, nq, bq, d), 2, 0)   # (nq,B,Hq,bq,D)
    kq = jnp.repeat(kb, group, axis=2)                     # GQA broadcast
    vq = jnp.repeat(vb, group, axis=2)

    def q_block(qi, q_blk):
        q32 = q_blk.astype(jnp.float32)
        rows = q_offset + qi * bq + jnp.arange(bq)

        def kv_block(carry, inp):
            kj, k_blk, v_blk = inp
            m_prev, l_prev, acc = carry

            def update(_):
                s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                               k_blk.astype(jnp.float32)) * scale
                if softcap is not None:
                    s = softcap * jnp.tanh(s / softcap)
                cols = kj * bk + jnp.arange(bk)
                mask = (cols < skv)[None, :]
                if causal:
                    mask = mask & (rows[:, None] >= cols[None, :])
                if window is not None:
                    mask = mask & ((rows[:, None] - cols[None, :]) < window)
                s = jnp.where(mask[None, None], s, -jnp.inf)
                m_new = jnp.maximum(m_prev, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                p = jnp.where(jnp.isnan(p), 0.0, p)
                alpha = jnp.exp(m_prev - m_new)
                alpha = jnp.where(jnp.isnan(alpha), 0.0, alpha)
                l_new = alpha * l_prev + p.sum(axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
                return m_new, l_new, acc_new

            # Static band check is impossible (kj traced), so use cond to
            # skip fully-masked blocks without spending MXU flops on them.
            lo = kj * bk
            needed = lo < skv
            if causal:
                needed = needed & (lo <= rows[-1])
            if window is not None:
                needed = needed & (lo + bk - 1 >= rows[0] - window + 1)
            return jax.lax.cond(needed, update,
                                lambda _: (m_prev, l_prev, acc), None), None

        init = (jnp.full((b, hq, bq), -jnp.inf, jnp.float32),
                jnp.zeros((b, hq, bq), jnp.float32),
                jnp.zeros((b, hq, bq, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_block, init, (jnp.arange(nk), kq, vq))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        return (acc / l_safe[..., None]).astype(q_blk.dtype)

    out = jax.lax.map(lambda inp: q_block(*inp), (jnp.arange(nq), qb))
    out = jnp.moveaxis(out, 0, 2).reshape(b, hq, sq + sq_pad, d)
    return out[:, :, :sq, :]


def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
            causal: bool = True,
            window: Optional[int] = None,
            softcap: Optional[float] = None,
            scale: Optional[float] = None,
            q_offset: int = 0) -> jax.Array:
    """Reference attention. q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).

    GQA: Hq must be a multiple of Hkv; query head h attends kv head
    h // (Hq // Hkv). `window`: only keys with (q_pos - k_pos) < window
    attend (sliding window, causal side). `softcap`: logits squashed to
    cap * tanh(logits / cap) (gemma2). `q_offset`: absolute position of
    q[0] (decode steps attend a longer KV cache).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    # f32 via matmul accumulation (preferred_element_type), NOT input casts:
    # .astype(f32) on a 32k-token KV cache materializes a 2x-sized copy per
    # layer -- decode_32k bytes-accessed drops ~40% without it (§Perf).
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kq,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vq.dtype), vq,
                      preferred_element_type=jnp.float32).astype(q.dtype)
