"""Chip smoke test: count and serve k-mers on a TPU through the entry points.

    python3 chip_smoke.py [--seed N]        # one chip: count, then serve
    python3 chip_smoke.py --chips 4         # four chips: the sharded count

One chip, three phases, all in this one process (a chip belongs to one
process at a time):

1. Device check. Exits nonzero, printing no result, unless JAX's platform
   is `tpu`.
2. Count. 204,800 reads of 150 bp sampled at ~30x from a 2^20-base genome
   made from `--seed` (`genome.ReadSetSpec`), canonical k=15 -- the widest
   k a 32-bit word holds -- folded by `fabsp.KmerCounter.update` in four
   batches, then `finalize()`. The histogram must equal the host NumPy
   oracle (`serial.count_kmers_numpy`) exactly.
3. Serve. `save()` the counter, restore it into a `kc_serve.StoreRegistry`,
   and flush `QueryService` batches of hits, reverse-complemented hits and
   misses; every answer must equal the oracle.

With `--chips 4` it instead runs `fabsp.count_kmers` over a 4-chip mesh in
the 1d topology and in the 2d (2x2) topology, checks that the reads and
the count store are sharded over all four chips, and compares both
histograms with the oracle; no other phase runs.

Timings are printed as bring-up observations (first call, which includes
compilation, and steady calls); they are not benchmark results. The last
line of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

K = 15
READ_LEN = 150
GENOME_BASES = 1 << 20
N_READS = 204_800
BATCHES = 4
FLUSHES = 4
REQUESTS = 4
PER_REQUEST = 4096


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer."""


def log(msg: str) -> None:
    print(msg, flush=True)


def make_reads(seed: int, n_reads: int = N_READS,
               genome_bases: int = GENOME_BASES,
               read_len: int = READ_LEN) -> np.ndarray:
    from repro.data import genome
    return genome.sample_reads(genome.ReadSetSpec(
        genome_bases=genome_bases, n_reads=n_reads, read_len=read_len,
        seed=seed))


def histogram(result, num_pes: int):
    """Host (sorted unique words, counts) of a per-shard AccumResult."""
    u = np.asarray(result.unique).reshape(num_pes, -1)
    c = np.asarray(result.counts).reshape(num_pes, -1)
    nu = np.asarray(result.num_unique).reshape(-1)
    uu = np.concatenate([u[s, :nu[s]] for s in range(num_pes)])
    cc = np.concatenate([c[s, :nu[s]] for s in range(num_pes)])
    order = np.argsort(uu, kind="stable")
    return uu[order], cc[order].astype(np.int64)


def check_histogram(name: str, got, oracle) -> None:
    gu, gc = got
    ou, oc = oracle
    if gu.shape != ou.shape or not (np.array_equal(gu, ou)
                                    and np.array_equal(gc, oc)):
        raise SmokeFailure(
            f"{name}: histogram differs from the oracle ({gu.size} vs "
            f"{ou.size} distinct, {int(gc.sum())} vs {int(oc.sum())} "
            f"instances)")
    log(f"{name}: exact against the oracle ({ou.size} distinct k-mers, "
        f"{int(oc.sum())} instances)")


def revcomp(words: np.ndarray, k: int) -> np.ndarray:
    out = np.zeros_like(words)
    w = words.copy()
    for _ in range(k):
        out = (out << words.dtype.type(2)) | (words.dtype.type(3)
                                              - (w & words.dtype.type(3)))
        w = w >> words.dtype.type(2)
    return out


def lookup(oracle, queries: np.ndarray, k: int) -> np.ndarray:
    """Oracle count of each query word after canonicalization."""
    ou, oc = oracle
    canon = np.minimum(queries, revcomp(queries, k))
    i = np.clip(np.searchsorted(ou, canon), 0, ou.size - 1)
    return np.where(ou[i] == canon, oc[i], 0).astype(np.int32)


def count_phase(mesh, reads_np: np.ndarray, cfg, oracle, workdir: Path,
                batches: int = BATCHES):
    """KmerCounter.update over `batches` batches, finalize, compare."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import fabsp

    kc = fabsp.KmerCounter(mesh, cfg)
    sharding = NamedSharding(mesh, P("pe"))
    times = []
    for part in np.array_split(reads_np, batches):
        batch = jax.device_put(part, sharding)
        t0 = time.perf_counter()
        stats = kc.update(batch)     # returns after the host reads stats
        times.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    result, fstats = kc.finalize()
    jax.block_until_ready(result.unique)
    t_fin = time.perf_counter() - t0
    check_histogram("count (KmerCounter, 1 chip)",
                    histogram(result, kc._num_pes), oracle)
    tier = ("spill tier engaged" if fstats.spilled_bins
            else f"in core, {kc.store_capacity} slots per PE")
    log(f"count: store {tier}; store rehash rounds "
        f"{fstats.retry_store_rehash}, route slack rounds "
        f"{fstats.retry_route_slack}; last batch raw k-mers "
        f"{int(stats.raw_kmers)}")
    steady = times[1:] or times
    log(f"bring-up observation: first update {times[0]:.3f} s (includes "
        f"compilation), steady update {np.mean(steady):.3f} s per "
        f"{reads_np.shape[0] // batches} reads, finalize {t_fin:.3f} s")
    return kc


def serve_phase(mesh, kc, cfg, oracle, workdir: Path, seed: int,
                flushes: int = FLUSHES, requests: int = REQUESTS,
                per_request: int = PER_REQUEST):
    """save -> StoreRegistry.load -> QueryService.flush, exact answers."""
    from repro.launch.kc_serve import QueryService, StoreRegistry

    ckpt = workdir / "ckpt"
    kc.save(str(ckpt))
    registry = StoreRegistry(mesh)
    registry.load("genome", str(ckpt), cfg)
    service = QueryService(registry)
    rng = np.random.default_rng(seed + 7)
    ou = oracle[0]
    dt = ou.dtype
    times = []
    for r in range(flushes):
        batch = []
        for _ in range(requests):
            n3 = per_request // 3
            hits = rng.choice(ou, n3)
            flipped = revcomp(rng.choice(ou, n3), K)
            rand = rng.integers(0, 1 << (2 * K), per_request - 2 * n3,
                                dtype=np.int64).astype(dt)
            q = np.concatenate([hits, flipped, rand])
            rng.shuffle(q)
            batch.append(q)
        for q in batch:
            service.submit("genome", q)
        t0 = time.perf_counter()
        out = service.flush()
        times.append(time.perf_counter() - t0)
        n_hit = n_miss = 0
        for q, ans in zip(batch, out):
            if isinstance(ans, Exception):
                raise SmokeFailure(f"serve: request failed: {ans!r}")
            want = lookup(oracle, q, K)
            if not np.array_equal(np.asarray(ans[0]), want):
                raise SmokeFailure(
                    f"serve: flush {r} answers differ from the oracle "
                    f"({int((np.asarray(ans[0]) != want).sum())} of "
                    f"{q.size})")
            n_hit += int((want > 0).sum())
            n_miss += int((want == 0).sum())
        if n_miss == 0 or n_hit == 0:
            raise SmokeFailure("serve: a flush lacked hits or misses")
    log(f"serve: {len(times)} flushes x {requests} requests x "
        f"{per_request} queries, every answer exact against the oracle")
    log(f"bring-up observation: first flush {times[0]:.3f} s (includes "
        f"restore-side compilation), steady flush "
        f"{np.mean(times[1:]):.3f} s per {requests * per_request} queries")


def mesh_phase(devices, reads_np: np.ndarray, cfg, oracle) -> None:
    """count_kmers on a 4-device mesh, 1d and 2d (2x2), exact and sharded."""
    import dataclasses

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import fabsp

    n = len(devices)
    grid = np.asarray(devices).reshape(2, n // 2)
    for topo, mesh, axes in (
            ("1d", Mesh(np.asarray(devices), ("pe",)), ("pe",)),
            ("2d", Mesh(grid, ("row", "col")), ("row", "col"))):
        spec = P(axes if len(axes) > 1 else axes[0])
        reads = jax.device_put(reads_np, NamedSharding(mesh, spec))
        shard_devs = {s.device for s in reads.addressable_shards}
        rows = {s.data.shape[0] for s in reads.addressable_shards}
        if len(shard_devs) != n or rows != {reads_np.shape[0] // n}:
            raise SmokeFailure(f"{topo}: reads not split over {n} chips")
        tcfg = dataclasses.replace(cfg, topology=topo)
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            result, stats = fabsp.count_kmers(reads, mesh, tcfg, axes)
            jax.block_until_ready(result.unique)
            times.append(time.perf_counter() - t0)
        store_devs = {s.device for s in result.unique.addressable_shards}
        if len(store_devs) != n:
            raise SmokeFailure(f"{topo}: count store not sharded over {n} "
                               f"chips ({len(store_devs)} hold it)")
        check_histogram(f"count_kmers {topo} on {n} chips",
                        histogram(result, n), oracle)
        log(f"{topo}: reads and count store sharded over {n} chips; wire "
            f"bytes {int(stats.wire_bytes)}, load max/mean "
            f"{float(stats.load_max_over_mean):.3f}")
        log(f"bring-up observation: {topo} first count_kmers "
            f"{times[0]:.3f} s (includes compilation), second "
            f"{times[1]:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this check runs on the chip only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from jax.sharding import Mesh

    from repro.core import fabsp, serial

    t0 = time.perf_counter()
    reads_np = make_reads(args.seed)
    oracle = serial.count_kmers_numpy(reads_np, K, canonical=True)
    log(f"data: {reads_np.shape[0]} reads x {READ_LEN} bp from a "
        f"{GENOME_BASES}-base genome, {oracle[0].size} distinct canonical "
        f"{K}-mers; made with the oracle in "
        f"{time.perf_counter() - t0:.1f} s")

    workdir = ROOT / ".chip_smoke"
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir()
    try:
        if args.chips == 4:
            mesh_phase(devices[:4], reads_np,
                       fabsp.DAKCConfig(k=K, canonical=True), oracle)
        else:
            mesh = Mesh(np.asarray(devices[:1]), ("pe",))
            cfg = fabsp.DAKCConfig(k=K, canonical=True, spill="auto",
                                   spill_dir=str(workdir / "spill"))
            kc = count_phase(mesh, reads_np, cfg, oracle, workdir)
            serve_phase(mesh, kc, cfg, oracle, workdir, args.seed)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
