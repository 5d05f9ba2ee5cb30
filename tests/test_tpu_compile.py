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
