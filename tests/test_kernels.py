"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("k", [3, 9, 15])
@pytest.mark.parametrize("n_reads,m", [(8, 64), (32, 100), (16, 151)])
def test_kmer_extract_sweep(k, n_reads, m):
    reads = jnp.asarray(RNG.integers(0, 4, (n_reads, m), dtype=np.uint8))
    out = ops.kmer_extract(reads, k)
    exp = ref.kmer_extract_ref(reads, k)
    assert out.dtype == exp.dtype
    assert (out == exp).all()


@pytest.mark.parametrize("k", [3, 9, 15])
@pytest.mark.parametrize("n_reads,m", [(8, 64), (16, 151)])
def test_kmer_extract_canonical_sweep(k, n_reads, m):
    """Fused in-loop canonicalization == pack-then-revcomp-sweep oracle."""
    reads = jnp.asarray(RNG.integers(0, 4, (n_reads, m), dtype=np.uint8))
    out = ops.kmer_extract(reads, k, canonical=True)
    exp = ref.kmer_extract_ref(reads, k, canonical=True)
    assert out.dtype == exp.dtype
    assert (out == exp).all()


@pytest.mark.parametrize("window", [1, 7, 25, 64])
@pytest.mark.parametrize("n_rows,n_pos", [(8, 88), (16, 600), (1, 64)])
def test_sliding_min_sweep(window, n_rows, n_pos):
    """Sliding-window-minimum kernel == ref across window/tiling shapes,
    including window == n_pos (a single output column)."""
    window = min(window, n_pos)
    vals = jnp.asarray(RNG.integers(0, 1 << 30, (n_rows, n_pos),
                                    dtype=np.uint32))
    out = ops.sliding_min(vals, window)
    exp = ref.sliding_min_ref(vals, window)
    assert out.dtype == exp.dtype
    assert (out == exp).all()


def test_sliding_min_tie_and_plateau():
    """Repeated minimum values (the poly-A regime) keep the windowed min
    constant -- the kernel must match ref through long plateaus."""
    vals = np.full((4, 200), 5, np.uint32)
    vals[:, ::17] = 1                               # periodic equal minima
    vals = jnp.asarray(vals)
    out = ops.sliding_min(vals, 13)
    exp = ref.sliding_min_ref(vals, 13)
    assert (out == exp).all()


@pytest.mark.parametrize("tile", [128, 512, 1024])
@pytest.mark.parametrize("frac_pad", [0.0, 0.3])
def test_segment_accumulate_sweep(tile, frac_pad):
    """Fused boundary+segment-sum kernel == ref, incl. runs spanning tiles
    (few distinct keys -> long runs) and sentinel-padded tails."""
    sent = int(np.iinfo(np.uint32).max)
    n = 2048
    keys = np.sort(RNG.integers(0, 37, n).astype(np.uint32))
    pad = int(n * frac_pad)
    if pad:
        keys[-pad:] = sent
    w = RNG.integers(1, 9, n, dtype=np.int32)
    keys, w = jnp.asarray(keys), jnp.asarray(w)
    got = ops.segment_accumulate(keys, w, sentinel_val=sent, tile=tile)
    exp = ref.segment_accumulate_ref(keys, w, sent)
    for g, e in zip(got, exp):
        assert (g == e).all()


@pytest.mark.parametrize("capacity", [17, 64, 256])
@pytest.mark.parametrize("tile", [32, 128])
def test_hash_insert_sweep(capacity, tile):
    """Insert-or-add kernel == sequential ref, bit-for-bit (slot layout
    included), across collision-heavy keys, sentinel padding, and non-tile
    batch lengths; the surviving table is the exact weighted histogram."""
    from repro.core import countstore
    sent = int(np.iinfo(np.uint32).max)
    n = 500                                        # not a tile multiple
    keys = RNG.integers(0, 3 * capacity, n).astype(np.uint32)
    keys[RNG.random(n) < 0.25] = sent
    w = RNG.integers(1, 6, n, dtype=np.int32)
    slots = countstore.store_slots(jnp.asarray(keys), capacity)
    tk = jnp.full((capacity,), sent, jnp.uint32)
    tc = jnp.zeros((capacity,), jnp.int32)
    got = ops.hash_insert(tk, tc, jnp.asarray(keys), jnp.asarray(w), slots,
                          sentinel_val=sent, tile=tile, impl="pallas")
    exp = ops.hash_insert(tk, tc, jnp.asarray(keys), jnp.asarray(w), slots,
                          sentinel_val=sent, tile=tile, impl="ref")
    for g, e in zip(got, exp):
        assert (g == e).all()
    gk, gc, dropped = got
    want = {}
    for kk, ww in zip(keys, w):
        if kk != sent:
            want[int(kk)] = want.get(int(kk), 0) + int(ww)
    have = {int(a): int(b)
            for a, b in zip(np.asarray(gk), np.asarray(gc)) if a != sent}
    if int(dropped) == 0:
        assert have == want
    else:                   # full table: what survived is still consistent
        assert all(have[kk] == want[kk] for kk in have)
        assert int((np.asarray(gk) != sent).sum()) == capacity


def test_hash_insert_full_table_drops_and_counts():
    """A table with no free slot drops new keys (counted), while existing
    keys keep accumulating -- the signal for the rehash round."""
    sent = int(np.iinfo(np.uint32).max)
    cap = 8
    keys = jnp.asarray(np.arange(24, dtype=np.uint32))
    w = jnp.ones((24,), jnp.int32)
    from repro.core import countstore
    slots = countstore.store_slots(keys, cap)
    tk = jnp.full((cap,), sent, jnp.uint32)
    tc = jnp.zeros((cap,), jnp.int32)
    gk, gc, dropped = ops.hash_insert(tk, tc, keys, w, slots,
                                      sentinel_val=sent, tile=8,
                                      impl="pallas")
    assert int(dropped) == 24 - cap
    assert int((np.asarray(gk) != sent).sum()) == cap
    # re-inserting the surviving keys adds, drops nothing
    gk2, gc2, d2 = ops.hash_insert(gk, gc, gk, gc,
                                   countstore.store_slots(gk, cap),
                                   sentinel_val=sent, tile=8)
    assert int(d2) == 0
    assert (gk2 == gk).all() and (gc2 == 2 * gc).all()


@pytest.mark.parametrize("digit_bits", [2, 4, 8])
@pytest.mark.parametrize("shift", [0, 8, 24])
def test_radix_hist_sweep(digit_bits, shift):
    keys = jnp.asarray(RNG.integers(0, 1 << 31, 4096, dtype=np.uint32))
    out = ops.radix_hist(keys, shift, digit_bits, tile=512)
    exp = ref.radix_hist_ref(keys, shift, digit_bits, 512)
    assert (out == exp).all()
    assert int(out.sum()) == 4096  # every key lands in one bucket per tile


@pytest.mark.parametrize("tile", [128, 1024])
@pytest.mark.parametrize("frac_pad", [0.0, 0.3])
def test_segment_boundaries_sweep(tile, frac_pad):
    sent = int(np.iinfo(np.uint32).max)
    n = 2048
    keys = np.sort(RNG.integers(0, 300, n).astype(np.uint32))
    pad = int(n * frac_pad)
    if pad:
        keys[-pad:] = sent
    keys = jnp.asarray(keys)
    out = ops.segment_boundaries(keys, sentinel_val=sent, tile=tile)
    exp = ref.segment_boundaries_ref(keys, sent)
    assert (out == exp).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "hq,hkv,sq,skv,causal,window,softcap",
    [(4, 4, 128, 128, True, None, None),
     (8, 2, 64, 64, True, None, None),
     (4, 1, 128, 128, True, 32, None),
     (2, 2, 64, 64, True, None, 20.0),
     (2, 2, 96, 96, False, None, None)])
def test_flash_attention_sweep(dtype, hq, hkv, sq, skv, causal, window,
                               softcap):
    q = jnp.asarray(RNG.normal(size=(2, hq, sq, 32)), dtype)
    k = jnp.asarray(RNG.normal(size=(2, hkv, skv, 32)), dtype)
    v = jnp.asarray(RNG.normal(size=(2, hkv, skv, 32)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=32, block_k=32)
    exp = ref.mha_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.abs(out.astype(jnp.float32)
                         - exp.astype(jnp.float32)).max()) < tol


def test_flash_attention_decode_offset():
    """Decode: 1 query at position 255 against a 256-long cache."""
    q = jnp.asarray(RNG.normal(size=(1, 4, 1, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 4, 256, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 4, 256, 32)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, q_offset=255,
                              block_q=32, block_k=64)
    exp = ref.mha_ref(q, k, v, causal=True, q_offset=255)
    assert float(jnp.abs(out - exp).max()) < 2e-5


def test_flash_blocks_do_not_change_result():
    q = jnp.asarray(RNG.normal(size=(1, 2, 256, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 256, 32)), jnp.float32)
    a = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    b = ops.flash_attention(q, k, v, block_q=128, block_k=64)
    assert float(jnp.abs(a - b).max()) < 2e-5


def test_wide_word_refused_on_compiled_path(monkeypatch):
    """On the TPU a 64-bit word (k >= 16) raises the typed error naming
    the two-lane-word roadmap item; it is never routed to the jnp oracle
    or to interpret mode."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with pytest.raises(ops.WideWordUnsupported, match="Reach item 3"):
        ops._interpret_for(np.zeros(4, np.uint32), np.zeros(4, np.uint64))
    assert ops._interpret_for(np.zeros(4, np.uint32)) is False
    monkeypatch.setattr(ops, "_interpret", lambda: True)
    assert ops._interpret_for(np.zeros(4, np.uint64)) is True
