"""Shared benchmark utilities.

Paper protocol (Sec. VI): report the best of 3 consecutive runs; I/O is
excluded (read sets are generated in memory). `BENCH_SCALE` env var scales
the synthetic dataset (1 = CI-quick defaults).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Callable, Tuple

SCALE = float(os.environ.get("BENCH_SCALE", "1"))
# --smoke (benchmarks/run.py): toy sizes, single timing rep, no record files.
SMOKE = os.environ.get("BENCH_SMOKE") == "1"


def best_of(fn: Callable[[], None], n: int = 3) -> float:
    """Best wall time of n runs, seconds (first call may include compile;
    fn must block on its own outputs)."""
    times = []
    for _ in range(1 if SMOKE else n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def timed(fn: Callable[..., object], *args) -> Tuple[float, float]:
    """(compile_seconds, steady_seconds) of a jittable callable.

    The first (tracing + compiling) call is timed separately from the
    best-of steady-state loop, so compile time never pollutes the per-stage
    record (the l3_compress anomaly of BENCH_phase_breakdown.json v1).
    """
    import jax

    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    out = jitted(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    compile_s = time.perf_counter() - t0

    def go():
        r = jitted(*args)
        jax.tree.map(lambda x: x.block_until_ready(), r)
    return compile_s, best_of(go)


def report(name: str, seconds: float, derived: str = "") -> None:
    """The scaffold contract: ``name,us_per_call,derived`` CSV rows."""
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def backend_info() -> dict:
    """The accelerator identity of this run -- stamped into every
    BENCH_*.json so trajectories across machines/backends are comparable
    (a CPU-emulation number and a TPU number must never diff silently)."""
    import jax

    devs = jax.devices()
    return {"jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def write_record(path: str, record: dict) -> None:
    """Write one BENCH_*.json, stamping `record['env']` with
    `backend_info()` (callers that measured in a subprocess with a forced
    device count can pre-set 'env' themselves)."""
    import json

    record.setdefault("env", backend_info())
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def run_subprocess_devices(code: str, num_devices: int,
                           timeout: int = 600) -> str:
    """Run `code` in a fresh python with N forced host devices; returns
    stdout (the code prints its own results). The child is held to the CPU
    platform: those devices are virtual, and on a TPU host the parent
    already holds the chip."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={num_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{proc.stderr[-2000:]}")
    return proc.stdout


KC_SNIPPET = r"""
import time, numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import fabsp
from repro.data import genome

def run(n_reads, read_len, k, chunk_reads, use_l3, topology, heavy, seed=0,
        l3_mode="auto", slack=1.5, repeats=3):
    spec = genome.ReadSetSpec(genome_bases=max(2048, n_reads * 4),
                              n_reads=n_reads, read_len=read_len,
                              heavy_hitter_frac=heavy, seed=seed)
    reads = jnp.asarray(genome.sample_reads(spec))
    devs = np.array(jax.devices())
    if topology == "2d":
        r = int(len(devs) ** 0.5)
        mesh = Mesh(devs.reshape(r, len(devs) // r), ("row", "col"))
        axes = ("row", "col")
    else:
        mesh = Mesh(devs, ("pe",))
        axes = ("pe",)
    cfg = fabsp.DAKCConfig(k=k, chunk_reads=chunk_reads, use_l3=use_l3,
                           l3_mode=l3_mode, topology=topology, slack=slack)
    best, stats = None, None
    for _ in range(repeats):
        t0 = time.perf_counter()
        res, stats = fabsp.count_kmers(reads, mesh, cfg, axes)
        res.unique.block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best, stats
"""
