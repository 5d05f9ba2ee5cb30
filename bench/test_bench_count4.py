"""The four-chip counting cell on four CPU devices, and the readers of its
exchange.

The cell runs in a child process with four forced host devices (the test
process keeps its one CPU device), at a test's size as
bench/test_bench_runs.py cuts cells: the program's run is correct, the
control is not, a fault that exists only across chips (a k-mer in two
shards) is caught, and the four-shard histogram is the one-chip one.
The exchange readers are pinned on hand-made numbers and on traces
recorded on the chip.
"""

import gzip
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import scopes, trace as xt

ROOT = Path(__file__).resolve().parent.parent
TESTDATA = ROOT / "bench" / "testdata"
CELL = "count-uniform-4chip"
EXCHANGE = ("exchange.device_ms", "exchange_ici_roofline",
            "exchange.slot_fill")


def _swap_a_shard(fabsp):
    """finalize() hands back shard 1's entries in place of shard 0's: shard
    1's k-mers sit in two shards and shard 0's are missing."""
    real = fabsp.KmerCounter.finalize

    def broken(self):
        res, st = real(self)
        u = np.asarray(res.unique).reshape(self._num_pes, -1).copy()
        c = np.asarray(res.counts).reshape(self._num_pes, -1).copy()
        n = np.asarray(res.num_unique).copy()
        u[0], c[0], n[0] = u[1], c[1], n[1]
        return res._replace(unique=u.reshape(-1), counts=c.reshape(-1),
                            num_unique=n), st
    fabsp.KmerCounter.finalize = broken
    return real


def four_chip_runs():
    """Run in the child: prints one JSON line of the runs' results."""
    import jax

    from bench import data, run, workload
    from bench.test_bench_runs import SEED, control_bits, small
    from repro.core import fabsp

    devices = jax.devices()
    assert len(devices) == 4
    cell = small(CELL)

    def one(**kw):
        return run.run_cell(cell, SEED, 0.3, False, devices,
                            t_start=time.perf_counter(), **kw)

    out = {"program": one(), "control": one(control_bits=control_bits(cell))}
    real = _swap_a_shard(fabsp)
    try:
        out["two_shards"] = one()
    finally:
        fabsp.KmerCounter.finalize = real

    cfg = cell.config
    reads = data.sample_reads(data.genome(cfg, SEED), cfg["n_reads"],
                              cfg["read_len"], cfg["error_rate"],
                              data.rng(SEED, data.READS))
    one_chip = dict(cfg, dakc=dict(cfg["dakc"], store_capacity=4 * cfg[
        "dakc"]["store_capacity"]))
    hists = {}
    for name, conf, devs in (("four", cfg, devices),
                             ("one", one_chip, devices[:1])):
        system = workload.ProgramCount(conf, devs, reads)
        result, _ = system.job()
        hists[name] = system.histogram(result)
    want = data.count_kmers(reads, cfg["k"], cfg["canonical"])
    out["histograms"] = {
        "distinct": int(hists["four"][0].size),
        "same_kmers": bool(np.array_equal(hists["four"][0],
                                          hists["one"][0])),
        "same_counts": bool(np.array_equal(hists["four"][1],
                                           hists["one"][1])),
        "reference_mismatch": data.histogram_mismatch(hists["four"], want)}
    print(json.dumps(out))


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    p = subprocess.run(
        [sys.executable, "-c",
         "from bench import test_bench_count4 as t; t.four_chip_runs()"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_program_run_is_correct(runs):
    r = runs["program"]
    assert r["correct"] is True
    assert r["device"]["count"] == 4
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"count_kmers_per_s", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in r["compared"].values())


def test_four_chip_control_is_not_correct(runs):
    r = runs["control"]
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["compared"].values())


def test_a_kmer_in_two_shards_makes_the_run_not_correct(runs):
    r = runs["two_shards"]
    assert r["correct"] is False
    assert r["compared"]["jobs_wrong"]["value"] == r["attempted"]


def test_four_shards_count_what_one_chip_counts(runs):
    h = runs["histograms"]
    assert h["same_kmers"] and h["same_counts"]
    assert h["reference_mismatch"] == 0
    # more k-mers than one shard has slots: as in the cell, the set needs
    # the four stores
    assert h["distinct"] > 1 << 15


# --- the exchange readers ---------------------------------------------------

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", TESTDATA.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


A2A = ("%all_to_all.49 = u32[4,1,26112]{2,1,0:T(1,128)S(1)} all-to-all("
       "u32[4,1,26112]{2,1,0:T(1,128)S(1)} %bitcast.202), channel_id=1, "
       "replica_groups={{0,1,2,3}}, dimensions={0}")


@pytest.mark.parametrize("text,slots", [
    (A2A, 4 * 26112),                                   # result side only
    (A2A.replace("u32", "s32"), 0),                     # a count lane
    ("%t = (u32[1,8]{1,0}, u32[1,8]{1,0}, s32[1,8]{1,0}) all-to-all("
     "u32[1,8]{1,0} %a, u32[1,8]{1,0} %b, s32[1,8]{1,0} %c)", 16),
    ("%copy.3 = u32[4,26112]{1,0} copy(u32[4,26112]{1,0} %x)", 0),
])
def test_slot_fill_counts_the_key_slots_of_all_to_alls(text, slots):
    assert _reader("exchange.slot_fill").key_slots(text) == slots


def test_ici_roofline_bytes_and_share_on_hand_made_numbers(monkeypatch):
    m = _reader("exchange_ici_roofline")
    # 4 chips: a chip holds a quarter of the items and sends 3/4 of them
    assert m.exchange_bytes(1_000_000, 4) == 4 * 1_000_000 * 3 / 16
    assert m.exchange_bytes(1_000_000, 1) == 0
    ops = {("jit_local_update",
            "jit(local_update)/while/body/route/exchange/all_to_all:"):
           4_000_000,                                   # 1 ms a chip
           ("jit_local_update", "jit(local_update)/route/scatter:"): 10**9}
    layers = scopes.Layers((0, 10**9), 4, ops, [], [[]] * 4)
    monkeypatch.setattr(scopes, "of", lambda ctx: layers)
    ctx = SimpleNamespace(trace=SimpleNamespace(n_devices=4),
                          counters={"jobs": 1, "sent_words": 1_000_000},
                          peaks={"ici_bits_per_s": 1600e9})
    # 750,000 B at 200 GB/s is 3.75 us of the 1 ms
    assert m.read(ctx) == pytest.approx(0.375, rel=1e-12)
    assert _reader("exchange.device_ms").read(ctx) == pytest.approx(1.0)


def _unpacked(name, d):
    path = d / f"{name}.xplane.pb"
    with gzip.open(TESTDATA / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.mark.parametrize("metric", EXCHANGE)
def test_exchange_readers_find_nothing_on_one_chip(tmp_path, monkeypatch,
                                                   metric):
    """On one chip the collective compiles to nothing: no op carries the
    `exchange` scope and no all-to-all runs, so each reader reads
    nothing (not zero, and no error)."""
    path = _unpacked("count_scoped", tmp_path)
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    ctx = SimpleNamespace(trace=xt.reduce(xt.load(path)),
                          counters={"jobs": 1, "sent_words": 10**6},
                          peaks={"ici_bits_per_s": 1600e9})
    assert _reader(metric).read(ctx) is None
