"""Resilience layer: the one retry engine, deterministic fault injection,
and the durable counter (save / kill / restore / elastic reshard).

The recovery invariant under test everywhere: a run whose fault stops
firing recovers a histogram identical to the fault-free run -- bit-
identical arrays for routing faults (capacity growth only pads
sentinels, preserving per-destination stream order), merged (kmer,
count)-set equality for store faults (the rehash changes the layout but
never the contents). Persistent faults drive the typed give-up errors,
which must carry the full round history.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import fabsp, resilience, serial
from repro.core.resilience import (CapacityExhausted, FaultPlan,
                                   InjectedFault, RetryBudgetExceeded,
                                   RetryController, RetryPolicy)
from repro.data import genome


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:1]), ("pe",))


@pytest.fixture(scope="module")
def mesh2d():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("row", "col"))


@pytest.fixture(scope="module")
def reads():
    spec = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=52,
                              heavy_hitter_frac=0.3, seed=7)
    return jnp.asarray(genome.sample_reads(spec))


def _merge(res):
    out = {}
    nsh = res.num_unique.shape[0]
    L = res.unique.shape[0] // nsh
    u = np.asarray(res.unique).reshape(nsh, L)
    c = np.asarray(res.counts).reshape(nsh, L)
    nu = np.asarray(res.num_unique)
    for s in range(nsh):
        for i in range(nu[s]):
            out[int(u[s, i])] = int(c[s, i])
    return out


# --- policy / plan validation ------------------------------------------------


def test_policy_validation():
    RetryPolicy()  # defaults are valid
    with pytest.raises(ValueError):
        RetryPolicy(max_slack=0)
    with pytest.raises(ValueError):
        RetryPolicy(slack_growth=1.0)
    with pytest.raises(ValueError):
        RetryPolicy(store_growth=1)
    with pytest.raises(ValueError):
        RetryPolicy(max_rounds=0)


def test_fault_plan_validation():
    FaultPlan(site="route_drop")
    with pytest.raises(ValueError):
        FaultPlan(site="nonsense")
    with pytest.raises(ValueError):
        FaultPlan(site="route_drop", frac=0.0)
    with pytest.raises(ValueError):
        FaultPlan(site="store_drop", fill=1.0)
    with pytest.raises(ValueError):
        FaultPlan(site="route_drop", rounds=0)


def test_fault_plan_must_be_hashable():
    """Plans and policies ride DAKCConfig into executable-cache keys."""
    assert hash(FaultPlan(site="route_drop", seed=3)) == hash(
        FaultPlan(site="route_drop", seed=3))
    assert hash(RetryPolicy()) == hash(RetryPolicy())


def test_fault_mask_deterministic_and_chunk_gated():
    plan = FaultPlan(site="route_drop", seed=5, chunk=2, frac=0.25)
    a = np.asarray(resilience.fault_mask(512, plan, jnp.int32(2)))
    b = np.asarray(resilience.fault_mask(512, plan, jnp.int32(2)))
    assert (a == b).all()
    assert 0 < a.sum() < 512                  # frac is neither 0 nor 1
    off = np.asarray(resilience.fault_mask(512, plan, jnp.int32(1)))
    assert off.sum() == 0                     # wrong chunk: mask is silent
    every = FaultPlan(site="route_drop", seed=5, chunk=-1, frac=0.25)
    assert np.asarray(
        resilience.fault_mask(512, every, jnp.int32(1))).sum() > 0


# --- RetryController unit behaviour ------------------------------------------


def test_controller_clean_round_records_nothing():
    ctrl = RetryController(RetryPolicy(), slack=1.5, store_cap=64)
    assert ctrl.observe() == ()
    assert ctrl.rounds == [] and ctrl.attempts == 1
    assert all(v == 0 for v in ctrl.counts.values())


def test_controller_grows_per_cause_and_records_history():
    ctrl = RetryController(RetryPolicy(), slack=1.5, store_cap=64,
                           hop2_padded=False)
    causes = ctrl.observe(route_dropped=3, store_dropped=2, hop2_dropped=1)
    assert set(causes) == {resilience.ROUTE_SLACK, resilience.STORE_REHASH,
                           resilience.HOP2_FALLBACK}
    assert ctrl.slack == 3.0 and ctrl.store_cap == 128 and ctrl.hop2_padded
    (r,) = ctrl.rounds
    assert r.round == 0 and r.slack == 1.5 and r.store_cap == 64
    assert (r.route_dropped, r.store_dropped, r.hop2_dropped) == (3, 2, 1)
    assert ctrl.counts[resilience.ROUTE_SLACK] == 1
    assert ctrl.observe() == ()               # clean follow-up round


def test_controller_capacity_exhausted_carries_cause_and_history():
    ctrl = RetryController(RetryPolicy(max_slack=2.0), slack=1.0,
                           store_cap=64)
    ctrl.observe(route_dropped=1)             # 1.0 -> 2.0
    ctrl.observe(route_dropped=1)             # 2.0 -> 4.0
    with pytest.raises(CapacityExhausted) as ei:
        ctrl.observe(route_dropped=7)         # 4.0 > max_slack: give up
    assert ei.value.cause == resilience.ROUTE_SLACK
    assert len(ei.value.rounds) == 3
    assert ei.value.rounds[-1].route_dropped == 7
    assert isinstance(ei.value, RuntimeError)  # legacy catch still works


def test_controller_store_ceiling():
    ctrl = RetryController(RetryPolicy(store_cap_ceiling=128), slack=1.0,
                           store_cap=64)
    ctrl.observe(store_dropped=1)             # 64 -> 128
    with pytest.raises(CapacityExhausted) as ei:
        ctrl.observe(store_dropped=1)
        ctrl.observe(store_dropped=1)         # 256 > ceiling
    assert ei.value.cause == resilience.STORE_REHASH


def test_controller_never_grows_past_ceiling():
    """A rehash round that would ask for more than the ceiling gives up
    instead: no executable is ever built for a table past it."""
    ctrl = RetryController(RetryPolicy(store_cap_ceiling=128), slack=1.0,
                           store_cap=128)
    with pytest.raises(CapacityExhausted) as ei:
        ctrl.observe(store_dropped=1)
    assert ctrl.store_cap == 128
    assert ei.value.rounds[-1].store_cap == 128


def test_default_store_ceiling_follows_backend(monkeypatch):
    from repro.core import countstore
    from repro.kernels import hash_table, ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert RetryPolicy().store_ceiling() == hash_table.max_vmem_capacity()
    assert RetryPolicy().store_ceiling() == 1 << 24
    assert RetryPolicy(store_cap_ceiling=64).store_ceiling() == 64
    monkeypatch.setattr(ops, "_interpret", lambda: True)
    assert countstore.max_store_capacity() == 1 << 28


def test_planned_store_stops_at_ceiling(mesh, reads):
    """A sampled store size past the ceiling starts AT the ceiling."""
    cfg = fabsp.DAKCConfig(k=11, chunk_reads=16, store_sizing="bound",
                           retry=RetryPolicy(store_cap_ceiling=64))
    assert fabsp._resolve_store_capacity(reads, cfg, 1) == 64


def test_controller_round_budget():
    ctrl = RetryController(RetryPolicy(max_slack=1e9, max_rounds=2),
                           slack=1.0, store_cap=64)
    ctrl.observe(route_dropped=1)
    with pytest.raises(RetryBudgetExceeded) as ei:
        ctrl.observe(route_dropped=1)
    assert len(ei.value.rounds) == 2


# --- recovery: injected fault, then a histogram identical to fault-free ------


def test_route_drop_recovers_bit_identical(mesh, reads):
    cfg = fabsp.DAKCConfig(k=11, chunk_reads=16)
    clean, cstats = fabsp.count_kmers(reads, mesh, cfg)
    assert cstats.retry_route_slack == 0
    cfg_f = fabsp.DAKCConfig(
        k=11, chunk_reads=16,
        faults=FaultPlan(site="route_drop", seed=1, chunk=0, frac=0.3))
    got, stats = fabsp.count_kmers(reads, mesh, cfg_f)
    assert stats.retry_route_slack >= 1
    assert int(stats.overflow) == 0           # final round is clean
    # routing recovery is BIT-identical: slack growth only pads sentinels,
    # so the replay folds the same per-destination streams in the same
    # order into the same store layout.
    assert (got.unique == clean.unique).all()
    assert (got.counts == clean.counts).all()
    assert (got.num_unique == clean.num_unique).all()


def test_store_drop_recovers_same_histogram(mesh, reads):
    base = dict(k=11, chunk_reads=16, store_capacity=256)
    clean, _ = fabsp.count_kmers(reads, mesh, fabsp.DAKCConfig(**base))
    cfg_f = fabsp.DAKCConfig(
        **base, faults=FaultPlan(site="store_drop", seed=2, chunk=0,
                                 frac=0.25))
    got, stats = fabsp.count_kmers(reads, mesh, cfg_f)
    assert stats.retry_store_rehash >= 1
    assert int(stats.store_overflow) == 0
    # the rehash changes the store layout, so compare contents not arrays
    assert _merge(got) == _merge(clean)
    assert _merge(got) == serial.count_kmers_python(np.asarray(reads), 11)


def test_store_drop_at_fill_level(mesh, reads):
    """The storm-at-fill variant only fires once the store is loaded."""
    base = dict(k=11, chunk_reads=16, store_capacity=2048)
    clean, _ = fabsp.count_kmers(reads, mesh, fabsp.DAKCConfig(**base))
    cfg_f = fabsp.DAKCConfig(
        **base, faults=FaultPlan(site="store_drop", seed=3, chunk=-1,
                                 frac=0.5, fill=0.3))
    got, stats = fabsp.count_kmers(reads, mesh, cfg_f)
    assert _merge(got) == _merge(clean)
    assert stats.retry_store_rehash >= 1


def test_hop2_misfit_falls_back_to_padded(mesh2d, reads):
    base = dict(k=11, chunk_reads=16, topology="2d", hop2_impl="compact",
                use_l3=False)
    clean, _ = fabsp.count_kmers(reads, mesh2d, fabsp.DAKCConfig(**base),
                                 axis_names=("row", "col"))
    cfg_f = fabsp.DAKCConfig(**base, faults=FaultPlan(site="hop2_misfit"))
    got, stats = fabsp.count_kmers(reads, mesh2d, cfg_f,
                                   axis_names=("row", "col"))
    assert stats.retry_hop2_fallback >= 1
    assert int(stats.hop2_dropped) == 0
    assert _merge(got) == _merge(clean)


def test_route_drop_recovery_superkmer_transport(mesh, reads):
    base = dict(k=11, chunk_reads=16, transport_impl="superkmer",
                minimizer_len=7)
    clean, _ = fabsp.count_kmers(reads, mesh, fabsp.DAKCConfig(**base))
    cfg_f = fabsp.DAKCConfig(
        **base, faults=FaultPlan(site="route_drop", seed=4, chunk=0,
                                 frac=0.3))
    got, stats = fabsp.count_kmers(reads, mesh, cfg_f)
    assert stats.retry_route_slack >= 1
    assert _merge(got) == _merge(clean)


# --- give-up paths (previously unreachable by any test) ----------------------


def test_persistent_route_drop_raises_capacity_exhausted(mesh, reads):
    cfg = fabsp.DAKCConfig(
        k=11, chunk_reads=16, retry=RetryPolicy(max_slack=2.0),
        faults=FaultPlan(site="route_drop", seed=1, chunk=-1, frac=0.5,
                         rounds=99))
    with pytest.raises(CapacityExhausted) as ei:
        fabsp.count_kmers(reads, mesh, cfg)
    assert ei.value.cause == resilience.ROUTE_SLACK
    assert len(ei.value.rounds) >= 1
    assert all(r.route_dropped > 0 for r in ei.value.rounds)
    # the history shows the slack ladder actually climbed
    slacks = [r.slack for r in ei.value.rounds]
    assert slacks == sorted(slacks)


def test_persistent_store_drop_raises_capacity_exhausted(mesh, reads):
    cfg = fabsp.DAKCConfig(
        k=11, chunk_reads=16, store_capacity=64,
        retry=RetryPolicy(store_cap_ceiling=128),
        faults=FaultPlan(site="store_drop", seed=2, chunk=-1, frac=0.5,
                         rounds=99))
    with pytest.raises(CapacityExhausted) as ei:
        fabsp.count_kmers(reads, mesh, cfg)
    assert ei.value.cause == resilience.STORE_REHASH
    assert ei.value.rounds[-1].store_cap > 64


def test_retry_budget_exceeded(mesh, reads):
    cfg = fabsp.DAKCConfig(
        k=11, chunk_reads=16, retry=RetryPolicy(max_slack=1e9, max_rounds=2),
        faults=FaultPlan(site="route_drop", seed=1, chunk=-1, frac=0.5,
                         rounds=99))
    with pytest.raises(RetryBudgetExceeded) as ei:
        fabsp.count_kmers(reads, mesh, cfg)
    assert len(ei.value.rounds) == 2


def test_config_rejects_misplaced_fault_sites(mesh):
    with pytest.raises(ValueError):
        fabsp.DAKCConfig(k=11, receiver_impl="stack",
                         faults=FaultPlan(site="store_drop"))
    with pytest.raises(ValueError):
        # hop2_misfit needs an engaged compact hop-2 (2d + compact)
        fabsp.DAKCConfig(k=11, faults=FaultPlan(site="hop2_misfit"))


# --- KmerCounter: injected update failure + per-batch retry stats ------------


def test_update_fail_is_a_clean_preemption(mesh, reads):
    cfg = fabsp.DAKCConfig(k=11, chunk_reads=16,
                           faults=FaultPlan(site="update_fail", update_n=1))
    kc = fabsp.KmerCounter(mesh, cfg)
    kc.update(reads[:32])
    with pytest.raises(InjectedFault):
        kc.update(reads[32:])
    # the failed call never committed: counter still holds exactly batch 0
    assert kc._n_updates == 1
    clean = fabsp.KmerCounter(mesh, fabsp.DAKCConfig(k=11, chunk_reads=16))
    clean.update(reads[:32])
    assert _merge(kc.finalize()[0]) == _merge(clean.finalize()[0])


def test_counter_store_drop_recovery_and_lifetime_counters(mesh, reads):
    base = dict(k=11, chunk_reads=16, store_capacity=256)
    clean = fabsp.KmerCounter(mesh, fabsp.DAKCConfig(**base))
    clean.update(reads[:32])
    clean.update(reads[32:])
    cfg_f = fabsp.DAKCConfig(
        **base, faults=FaultPlan(site="store_drop", seed=2, chunk=0,
                                 frac=0.25))
    kc = fabsp.KmerCounter(mesh, cfg_f)
    s0 = kc.update(reads[:32])
    assert s0.retry_store_rehash >= 1         # per-batch replay count
    s1 = kc.update(reads[32:])
    assert _merge(kc.finalize()[0]) == _merge(clean.finalize()[0])
    # finalize's stats carry the lifetime totals across both batches
    _, fstats = kc.finalize()
    assert fstats.retry_store_rehash == (s0.retry_store_rehash
                                         + s1.retry_store_rehash)


# --- durability: save / restore / kill-mid-write -----------------------------


def test_save_restore_roundtrip_same_mesh(mesh, reads, tmp_path):
    cfg = fabsp.DAKCConfig(k=11, chunk_reads=16)
    kc = fabsp.KmerCounter(mesh, cfg)
    kc.update(reads[:32])
    kc.update(reads[32:])
    kc.save(str(tmp_path), step=5)
    kc2 = fabsp.KmerCounter.restore(str(tmp_path), mesh, cfg)
    assert kc2._n_updates == 2
    assert kc2.store_capacity == kc.store_capacity
    r1, s1 = kc.finalize()
    r2, s2 = kc2.finalize()
    assert (r1.unique == r2.unique).all()
    assert (r1.counts == r2.counts).all()
    assert int(s1.raw_kmers) == int(s2.raw_kmers)
    assert int(s1.wire_bytes) == int(s2.wire_bytes)


def test_restore_rejects_incompatible_fingerprint(mesh, reads, tmp_path):
    kc = fabsp.KmerCounter(mesh, fabsp.DAKCConfig(k=11, chunk_reads=16))
    kc.update(reads)
    kc.save(str(tmp_path), step=0)
    with pytest.raises(ValueError, match="fingerprint"):
        fabsp.KmerCounter.restore(str(tmp_path), mesh,
                                  fabsp.DAKCConfig(k=13, chunk_reads=16))


def test_restore_onto_new_ownership_is_a_reshard(mesh, reads, tmp_path):
    """Same PE count but a different ownership family (kmer-hash owners vs
    minimizer owners) must re-route every live entry -- the single-device
    version of the elastic reshard, checkable without a multi-PE mesh."""
    cfg = fabsp.DAKCConfig(k=11, chunk_reads=16)
    kc = fabsp.KmerCounter(mesh, cfg)
    kc.update(reads)
    expect = _merge(kc.finalize()[0])
    kc.save(str(tmp_path), step=0)
    cfg_sk = fabsp.DAKCConfig(k=11, chunk_reads=16,
                              transport_impl="superkmer", minimizer_len=7)
    kc2 = fabsp.KmerCounter.restore(str(tmp_path), mesh, cfg_sk)
    assert _merge(kc2.finalize()[0]) == expect
    # and the resharded counter keeps counting
    kc2.update(reads[:16])
    total = sum(_merge(kc2.finalize()[0]).values())
    assert total == sum(expect.values()) + sum(
        serial.count_kmers_python(np.asarray(reads[:16]), 11).values())


def test_ckpt_write_fault_preserves_last_complete_checkpoint(
        mesh, reads, tmp_path):
    from repro.train import checkpoint as ckpt_lib
    cfg = fabsp.DAKCConfig(k=11, chunk_reads=16)
    kc = fabsp.KmerCounter(mesh, cfg)
    kc.update(reads[:32])
    kc.save(str(tmp_path), step=0)            # complete checkpoint
    kc.update(reads[32:])
    kc_f = fabsp.KmerCounter(mesh, fabsp.DAKCConfig(
        k=11, chunk_reads=16,
        faults=FaultPlan(site="ckpt_write", fail_after=1)))
    kc_f._skeys, kc_f._scounts = kc._skeys, kc._scounts
    kc_f._store_cap, kc_f._n_updates = kc._store_cap, kc._n_updates
    with pytest.raises(InjectedFault):
        kc_f.save(str(tmp_path), step=1)      # dies mid-file, pre-rename
    assert ckpt_lib.latest_step(str(tmp_path)) == 0
    restored = fabsp.KmerCounter.restore(str(tmp_path), mesh, cfg)
    assert restored._n_updates == 1           # step-0 state, replay batch 1
    restored.update(reads[32:])
    assert _merge(restored.finalize()[0]) == _merge(kc.finalize()[0])


def test_save_requires_exactly_one_destination(mesh, reads, tmp_path):
    kc = fabsp.KmerCounter(mesh, fabsp.DAKCConfig(k=11, chunk_reads=16))
    kc.update(reads[:16])
    with pytest.raises(ValueError):
        kc.save()
    with pytest.raises(ValueError):
        from repro.train.checkpoint import AsyncSaver
        kc.save(str(tmp_path), saver=AsyncSaver(str(tmp_path)))


# --- the full drill: save / kill / restore onto FEWER PEs --------------------


_RESHARD_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import fabsp, serial
from repro.core.resilience import FaultPlan, InjectedFault
from repro.data import genome

# 128 reads split 64/64: divisible by 8 and 4 PEs x chunk_reads=4
spec = genome.ReadSetSpec(genome_bases=4096, n_reads=128, read_len=52,
                          heavy_hitter_frac=0.3, seed=11)
reads = jnp.asarray(genome.sample_reads(spec))
ckpt = os.environ["CKPT_DIR"]
CFG = dict(k=11, chunk_reads=4{extra_cfg})

def merged(res):
    out = {{}}
    nsh = res.num_unique.shape[0]
    L = res.unique.shape[0] // nsh
    u = np.asarray(res.unique).reshape(nsh, L)
    c = np.asarray(res.counts).reshape(nsh, L)
    for s in range(nsh):
        for i in range(int(res.num_unique[s])):
            out[int(u[s, i])] = int(c[s, i])
    return out

# uninterrupted reference on 8 PEs
mesh8 = Mesh(np.array(jax.devices()[:8]), ("pe",))
ref = fabsp.KmerCounter(mesh8, fabsp.DAKCConfig(**CFG))
ref.update(reads[:64]); ref.update(reads[64:])
expect = merged(ref.finalize()[0])
assert expect == serial.count_kmers_python(np.asarray(reads), 11)

# interrupted stream: batch 0, checkpoint, injected kill at update #1
cfg_f = fabsp.DAKCConfig(**CFG, faults=FaultPlan(site="update_fail",
                                                 update_n=1))
kc = fabsp.KmerCounter(mesh8, cfg_f)
kc.update(reads[:64])
kc.save(ckpt, step=0)
try:
    kc.update(reads[64:])
    raise SystemExit("injected kill did not fire")
except InjectedFault:
    pass

# restore onto 4 PEs (elastic reshard) and replay the lost batch
mesh4 = Mesh(np.array(jax.devices()[:4]), ("pe",))
kc2 = fabsp.KmerCounter.restore(ckpt, mesh4, fabsp.DAKCConfig(**CFG))
assert kc2._num_pes == 4 and kc2._n_updates == 1
kc2.update(reads[64:])
got = merged(kc2.finalize()[0])
assert got == expect, "resumed 4-PE stream diverged from 8-PE reference"
print("OK")
"""


def _run_reshard_drill(tmp_path, extra_cfg=""):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    env["CKPT_DIR"] = str(tmp_path / "ckpt")
    code = _RESHARD_CODE.format(extra_cfg=extra_cfg)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_save_kill_restore_reshard_8_to_4(tmp_path):
    """The acceptance drill: checkpoint mid-stream on 8 PEs, die, restore
    onto 4 PEs, finish -- final histogram equals the uninterrupted run."""
    _run_reshard_drill(tmp_path)


@pytest.mark.slow
def test_save_kill_restore_reshard_superkmer(tmp_path):
    """Same drill under minimizer ownership: the reshard must recompute
    each stored k-mer's minimizer to find its new owner."""
    _run_reshard_drill(
        tmp_path, extra_cfg=", transport_impl='superkmer', minimizer_len=7")


# --- bounded round history (first + ring of last N-1) ------------------------


def test_controller_history_keeps_first_plus_ring():
    """Unbounded fault streams must not grow the history without limit:
    the first round (how the trouble started) is pinned, the ring keeps
    the last max_history - 1 (how it ended)."""
    pol = RetryPolicy(max_history=4, max_rounds=100, max_slack=1e9)
    ctrl = RetryController(pol, slack=1.0, store_cap=64)
    for _ in range(10):
        ctrl.observe(route_dropped=1)
    rounds = ctrl.rounds
    assert len(rounds) == 4
    assert rounds[0].round == 0                   # first round pinned
    assert [r.round for r in rounds[1:]] == [7, 8, 9]
    assert ctrl.own_rounds == 10                  # budget sees them all


def test_policy_rejects_tiny_history():
    with pytest.raises(ValueError):
        RetryPolicy(max_history=1)


def test_seeded_history_rides_payloads_but_not_budget():
    """History seeded from a previous controller (or a checkpoint) must
    appear in give-up payloads yet never consume the replay budget."""
    seed = resilience.RetryRound(
        round=0, causes=(resilience.STORE_REHASH,), slack=1.5,
        store_cap=64, hop2_padded=True, route_dropped=0,
        store_dropped=7, hop2_dropped=0)
    pol = RetryPolicy(max_rounds=3, max_slack=2.0)
    ctrl = RetryController(pol, slack=1.0, store_cap=64,
                           history=[seed])
    assert ctrl.own_rounds == 0                   # seeding is free
    ctrl.observe(route_dropped=1)                 # 1.0 -> 2.0
    ctrl.observe(route_dropped=1)                 # 2.0 -> 4.0
    with pytest.raises(CapacityExhausted) as ei:
        ctrl.observe(route_dropped=1)
    rounds = ei.value.rounds
    assert rounds[0] == seed                      # the imported first round
    assert len(rounds) == 4 and ctrl.own_rounds == 3


def test_rounds_json_roundtrip():
    seed = resilience.RetryRound(
        round=2, causes=(resilience.ROUTE_SLACK, resilience.STORE_REHASH),
        slack=3.0, store_cap=128, hop2_padded=False, route_dropped=4,
        store_dropped=5, hop2_dropped=0)
    back = resilience.rounds_from_json(resilience.rounds_to_json([seed]))
    assert back == [seed]
    assert isinstance(back[0].causes, tuple)
    assert resilience.rounds_from_json(None) == []


# --- retry-counter durability across save/restore ----------------------------


def test_restored_counter_reports_lifetime_retry_totals(
        mesh, reads, tmp_path):
    """finalize() on a restored counter must include pre-checkpoint
    replays in its lifetime retry_* totals."""
    cfg_f = fabsp.DAKCConfig(
        k=11, chunk_reads=16, store_capacity=256,
        faults=FaultPlan(site="store_drop", seed=2, chunk=0, frac=0.25))
    kc = fabsp.KmerCounter(mesh, cfg_f)
    s0 = kc.update(reads[:32])
    assert s0.retry_store_rehash >= 1
    kc.save(str(tmp_path), step=0)
    # restore WITHOUT the fault: the second batch is clean, so any retry
    # totals on finalize can only come from the checkpointed counters
    cfg = fabsp.DAKCConfig(k=11, chunk_reads=16, store_capacity=256)
    kc2 = fabsp.KmerCounter.restore(str(tmp_path), mesh, cfg)
    kc2.update(reads[32:])
    _, fstats = kc2.finalize()
    assert fstats.retry_store_rehash == s0.retry_store_rehash


def test_post_restore_giveup_history_spans_restore_boundary(
        mesh, reads, tmp_path):
    """A CapacityExhausted raised after restore must carry round history
    that includes the pre-checkpoint rounds (the first-round pin)."""
    cfg_f = fabsp.DAKCConfig(
        k=11, chunk_reads=16, store_capacity=256,
        faults=FaultPlan(site="store_drop", seed=2, chunk=0, frac=0.25))
    kc = fabsp.KmerCounter(mesh, cfg_f)
    kc.update(reads[:32])
    kc.save(str(tmp_path), step=0)
    assert kc._rounds, "fault never recorded a round"
    first = kc._rounds[0]
    # restore with a PERSISTENT route fault and a tiny slack cap: the
    # second batch must give up -- with the pre-checkpoint round pinned
    # at the head of the payload
    cfg_p = fabsp.DAKCConfig(
        k=11, chunk_reads=16, store_capacity=256,
        retry=RetryPolicy(max_slack=2.0),
        faults=FaultPlan(site="route_drop", seed=1, chunk=-1, frac=0.5,
                         rounds=99))
    kc2 = fabsp.KmerCounter.restore(str(tmp_path), mesh, cfg_p)
    with pytest.raises(CapacityExhausted) as ei:
        kc2.update(reads[32:])
    rounds = ei.value.rounds
    assert rounds[0] == first                 # spans the restore boundary
    assert any(resilience.ROUTE_SLACK in r.causes for r in rounds[1:])
