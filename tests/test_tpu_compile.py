"""Compile rehearsal: the main path's Pallas kernels through Mosaic.

Every kernel the default counting and serving path calls on 32-bit words
is compiled here for a described (not attached) TPU v5e, at the sizes the
chip smoke (chip_smoke.py) runs: the compiler refuses here what it would
refuse on the chip -- unaligned blocks, unlowerable primitives, VMEM
overruns -- at no chip time. Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import fabsp
from repro.kernels import hash_table, ops
from repro.kernels.hash_table import hash_insert_pallas, hash_lookup_pallas
from repro.kernels.minimizer import (sliding_min_pair_pallas,
                                     sliding_min_pallas)
from repro.kernels.radix_partition import make_partition_plan
from repro.kernels.segment_count import segment_accumulate_pallas

SENT = int(np.iinfo(np.uint32).max)
CEILING = hash_table.max_vmem_capacity()
BATCH = 1 << 16           # one update's routed tile scale


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_path(monkeypatch):
    """Kernels compile (not interpret), the persistent cache stays off (a
    described-chip compile cannot be read back here), and no trace made
    under this steering outlives the test."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    fabsp.clear_executable_cache()
    yield
    jax.clear_caches()
    fabsp.clear_executable_cache()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("cap", [1 << 21, CEILING])
def test_hash_insert_compiles(one_chip, cap):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    c = _compile(functools.partial(hash_insert_pallas, sentinel_val=SENT),
                 s((cap,), jnp.uint32), s((cap,), jnp.int32),
                 s((BATCH,), jnp.uint32), s((BATCH,), jnp.int32),
                 s((BATCH,), jnp.int32))
    assert _custom_calls(c) == 1


def test_hash_insert_past_ceiling_is_refused(one_chip):
    """The store ceiling is where the compiler stops: twice the ceiling's
    table does not fit one core's VMEM."""
    cap = 2 * CEILING
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    with pytest.raises(Exception, match="vmem"):
        _compile(functools.partial(hash_insert_pallas, sentinel_val=SENT),
                 s((cap,), jnp.uint32), s((cap,), jnp.int32),
                 s((BATCH,), jnp.uint32), s((BATCH,), jnp.int32),
                 s((BATCH,), jnp.int32))


def test_hash_insert_at_the_ceiling_matches_the_oracle_on_the_chip():
    """On the chip only: the compiled kernel at 2^24 slots, half full, folds
    one receive batch of the count cell's shape (104,448 NORMAL + 52,224
    HEAVY entries, live entries first, padded by `ops.hash_insert` to a
    multiple of 1,024) exactly as the oracle does on the host's CPU:
    table, drops and group counts."""
    if jax.default_backend() != "tpu":
        pytest.skip("runs the compiled kernel: needs a TPU")
    from repro.core import countstore
    rng = np.random.default_rng(15)
    cap = CEILING
    tk = np.full(cap, SENT, np.uint32)
    filled = rng.random(cap) < 0.5
    tk[filled] = rng.integers(0, 1 << 30, int(filled.sum()), dtype=np.uint32)
    tc = np.where(filled, rng.integers(1, 9, cap), 0).astype(np.int32)
    parts = []
    for n, live, wmax in ((104_448, 34_000, 1), (52_224, 800, 9)):
        k = np.full(n, SENT, np.uint32)
        k[:live] = np.where(rng.random(live) < 0.6,
                            rng.choice(tk[filled], live),
                            rng.integers(0, 1 << 30, live, dtype=np.uint32))
        w = np.zeros(n, np.int32)
        w[:live] = rng.integers(1, wmax + 1, live)
        parts.append((k, w))
    keys = np.concatenate([p[0] for p in parts])
    w = np.concatenate([p[1] for p in parts])
    slots = np.asarray(countstore.store_slots(jnp.asarray(keys), cap))
    args = (tk, tc, keys, w, slots)
    got = ops.hash_insert_counted(*map(jnp.asarray, args),
                                  sentinel_val=SENT, impl="pallas")
    cpu = jax.devices("cpu")[0]
    exp = ops.hash_insert_counted(*(jax.device_put(a, cpu) for a in args),
                                  sentinel_val=SENT, impl="ref")
    for g, e in zip(got, exp):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
    live_groups, fallbacks = (int(x) for x in got[3])
    assert live_groups == (34_000 + 800) // 8 and 0 < fallbacks < live_groups


@pytest.mark.parametrize("cap", [1 << 21, CEILING])
def test_hash_lookup_compiles(one_chip, cap):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    c = _compile(functools.partial(hash_lookup_pallas, sentinel_val=SENT),
                 s((cap,), jnp.uint32), s((cap,), jnp.int32),
                 s((16384,), jnp.uint32), s((16384,), jnp.int32))
    assert _custom_calls(c) == 1


def test_segment_accumulate_compiles(one_chip):
    n = 1 << 21
    c = _compile(
        functools.partial(segment_accumulate_pallas, sentinel_val=SENT),
        jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))
    assert _custom_calls(c) == 1


@pytest.mark.parametrize("num_buckets", [2, 5, 257])
def test_partition_plan_compiles(one_chip, num_buckets):
    """Histogram + positions: 2 = compaction, P + 1 = routing, 257 = a
    radix digit pass with its sentinel bucket."""
    c = _compile(lambda b: make_partition_plan(b, num_buckets),
                 jax.ShapeDtypeStruct((BATCH * 4,), jnp.int32,
                                      sharding=one_chip))
    assert _custom_calls(c) == 2


def test_sliding_min_compiles(one_chip):
    c = _compile(lambda v: sliding_min_pallas(v, 9),
                 jax.ShapeDtypeStruct((4096, 144), jnp.uint32,
                                      sharding=one_chip))
    assert _custom_calls(c) == 1


def test_sliding_min_pair_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 144), jnp.uint32, sharding=one_chip)
    c = _compile(lambda k, v: sliding_min_pair_pallas(k, v, 9), x, x)
    assert _custom_calls(c) == 1


def test_counting_executable_compiles_on_one_chip(topo):
    """The whole per-batch count (extract, L3, route, fold, compaction) at
    the chip smoke's batch size: every kernel in it goes through Mosaic."""
    mesh = Mesh(np.asarray(topo.devices[:1]), ("pe",))
    cfg = fabsp.DAKCConfig(k=15, canonical=True)
    reads = jax.ShapeDtypeStruct((51_200, 150), jnp.uint8,
                                 sharding=NamedSharding(mesh, P("pe")))
    fn = fabsp._counting_executable(cfg, mesh, ("pe",), reads.shape, "uint8",
                                    cfg.slack, store_cap=1 << 21)
    c = fn.lower(reads).compile()
    assert _custom_calls(c) >= 5


@pytest.mark.parametrize("topology", ["1d", "2d"])
def test_four_chip_count_compiles(topo, topology):
    """The sharded count across a 2x2 mesh: the `all_to_all` exchange and
    the per-chip kernels compile together."""
    devs = np.asarray(topo.devices)
    axes = ("pe",) if topology == "1d" else ("row", "col")
    mesh = Mesh(devs if topology == "1d" else devs.reshape(2, 2), axes)
    cfg = fabsp.DAKCConfig(k=15, canonical=True, topology=topology)
    spec = P(axes if len(axes) > 1 else axes[0])
    reads = jax.ShapeDtypeStruct((204_800, 150), jnp.uint8,
                                 sharding=NamedSharding(mesh, spec))
    fn = fabsp._counting_executable(cfg, mesh, axes, reads.shape, "uint8",
                                    cfg.slack, store_cap=1 << 19)
    text = fn.lower(reads).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text
