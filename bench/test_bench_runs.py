"""Whole benchmark runs at a small size on the CPU: the program passes, and
`correct` comes out false for the control and for each fault the timed
path can have.

These runs skip the harness's look for a chip (`run.main`) and call
`run.run_cell` with the CPU's devices; everything after that look is the
run as the chip makes it, cut to a read set a test can count.
"""

import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run, workload

ROOT = Path(__file__).resolve().parent.parent
SEED = 2**31 + 77


def small(name: str):
    """The cell at a test's size: same shapes of reads and requests, a
    4,096-base genome, 512 reads per chip and a store about as full as on
    the chip (~14 k distinct k-mers in 2**15 slots)."""
    c = workload.load_cell(name)
    c.config.update(genome_bases=4096 * c.chips, n_reads=512 * c.chips,
                    dakc={"chunk_reads": 32, "store_capacity": 1 << 15})
    if c.traffic["driver"] == "serve":
        c.traffic.update(rate_per_s=100, max_requests=3)
    return c


def control_bits(cell) -> int:
    """The control's sketch at a test's size: about as many counters per
    genome base as 2**24 counters give the cells on the chip (3.6)."""
    return int(np.ceil(np.log2(3.6 * cell.config["genome_bases"])))


def one_run(name, seconds=0.3, **kw):
    cell = small(name)
    if kw.pop("control", False):
        kw["control_bits"] = control_bits(cell)
    return run.run_cell(cell, SEED, seconds, False,
                        jax.devices()[:cell.chips],
                        t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("name", ["count-uniform", "serve-reads"])
def test_program_run_is_correct(name):
    r = one_run(name)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in r["compared"].values())
    cell = workload.load_cell(name)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 1


@pytest.mark.parametrize("name", ["count-uniform", "serve-reads"])
def test_control_is_not_correct(name):
    r = one_run(name, control=True)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["compared"].values())


def test_count_warm_up_leaves_nothing_to_trace_in_the_window():
    """After set-up's warm-up a whole job traces none of the program's
    jitted steps anew (the first update of a fresh store and a later one
    are traced apart, so one warm batch is not enough)."""
    from bench import data
    cell = small("count-uniform")
    cfg = cell.config
    reads = data.sample_reads(data.genome(cfg, SEED), cfg["n_reads"],
                              cfg["read_len"], cfg["error_rate"],
                              data.rng(SEED, data.READS))
    system = run.make_system(cell, jax.devices()[:1], reads)
    system.warm()
    traced, active = [], [True]

    def on(event, duration, **kw):
        if active[0] and event.endswith("jaxpr_trace_duration"):
            traced.append(kw.get("fun_name", ""))
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        system.job()
    finally:
        active[0] = False
    assert [n for n in traced if n.startswith("local_")] == []


def _state_unchanged(monkeypatch):
    """Each update runs but hands back the store it was given."""
    from repro.core import fabsp
    real = fabsp._update_executable

    def broken(*a, **kw):
        fn = real(*a, **kw)

        def step(reads, skeys, scounts):
            _, _, stats = fn(reads, skeys, scounts)
            return skeys, scounts, stats
        return step
    monkeypatch.setattr(fabsp, "_update_executable", broken)


def _half_batch(monkeypatch):
    """Each update folds the first half of its batch only."""
    from repro.core import fabsp
    real = fabsp.KmerCounter.update
    monkeypatch.setattr(fabsp.KmerCounter, "update",
                        lambda self, r: real(self, r[:r.shape[0] // 2]))


def _count_altered(monkeypatch):
    """finalize() reports one k-mer's count off by one."""
    from repro.core import fabsp
    real = fabsp.KmerCounter.finalize

    def broken(self):
        res, st = real(self)
        return res._replace(counts=res.counts.at[0].add(1)), st
    monkeypatch.setattr(fabsp.KmerCounter, "finalize", broken)


def _answer_altered(monkeypatch):
    """The lookup answers one query of each batch off by one."""
    from repro.core import fabsp
    real = fabsp.KmerCounter.count

    def broken(self, kmers):
        out = np.array(real(self, kmers))
        out[0] += 1
        return out
    monkeypatch.setattr(fabsp.KmerCounter, "count", broken)


def _lookup_half(monkeypatch):
    """Half of each lookup batch is left out and answered 0."""
    from repro.core import fabsp
    real = fabsp.KmerCounter.count

    def broken(self, kmers):
        kmers = np.asarray(kmers)
        half = kmers.shape[0] // 2
        return np.concatenate([real(self, kmers[:half]),
                               np.zeros(kmers.shape[0] - half, np.int32)])
    monkeypatch.setattr(fabsp.KmerCounter, "count", broken)


@pytest.mark.parametrize("name,fault", [
    ("count-uniform", _state_unchanged),
    ("count-uniform", _half_batch),
    ("count-uniform", _count_altered),
    ("serve-reads", _answer_altered),
    ("serve-reads", _lookup_half),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_the_run_not_correct(name, fault, monkeypatch):
    from repro.core import fabsp
    fabsp.clear_executable_cache()
    fault(monkeypatch)
    try:
        r = one_run(name)
    finally:
        fabsp.clear_executable_cache()
    assert r["correct"] is False


def test_cli_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "count-uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_cli_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program to
    measure: the run exits nonzero and prints no result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "count-uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
