"""k-mer query service harness: multi-tenant named stores + batched serving.

The thin serving layer over the query path (core/query.py), following the
driver/engine split `launch/serve.py` sketches for the LM stack:

- `StoreRegistry` -- named `fabsp.KmerCounter` tenants on one mesh.
  `load()` restores a tenant from a checkpoint directory via
  `KmerCounter.restore` (train/checkpoint.py; elastic across PE counts,
  so a store counted on 8 PEs serves from a 4-PE mesh unchanged).
- `QueryService` -- request intake. `submit()` queues (tenant, kmers)
  requests; `flush()` coalesces every queued request for a tenant into
  ONE device batch (requests share the routed exchange and the pow2
  shape-bucketed executable -- that is the batching win), splits the
  request-ordered answers back per request, and attaches per-request
  `RequestStats` (batch fill, probe depth, route wire bytes, latency).
  `query()` is the unbatched one-shot.

Serves EVERY store regime: a spill-engaged tenant answers exactly through
the spilled-bin query tier (`query.query_spilled_counts` -- on-demand bin
folds behind a byte-bounded LRU), and a LIVE tenant accepts `update()`
between flushes -- `count()` reads the counter's epoch-pinned committed
snapshot, so each flush answers the last committed prefix exactly.

Typed errors, never silent wrong answers OR silently dropped work: an
unknown tenant raises `UnknownStore` at intake; a tenant opting out of
spilled serving (`spill_query='refuse'`) fails with the typed
`query.QueryUnavailable`. `flush()` isolates failures per tenant: every
submitted request gets an entry aligned with submission order -- either
(counts, RequestStats) or the typed exception instance -- so one tenant
refusing never discards another tenant's computed answers or queued
requests.

  PYTHONPATH=src python -m repro.launch.kc_serve --demo
      # one-shot CI gate: count -> save -> restore into the registry ->
      # serve batched queries (in-core, spilled, strict-refusal, and
      # read-write interleave drills) -> assert exact counts
  PYTHONPATH=src python -m repro.launch.kc_serve --demo --requests 64
      # same, then a small serving loop printing QPS / latency
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class UnknownStore(KeyError):
    """Request named a tenant the registry does not hold."""


@dataclasses.dataclass
class RequestStats:
    """Per-request serving stats (one row per submitted request, even when
    many requests shared a coalesced batch)."""
    tenant: str
    n_queries: int        # this request's queries
    n_hits: int           # this request's queries with count > 0
    batch_queries: int    # live queries in the coalesced batch
    batch_fill: float     # batch occupancy of the padded shape bucket
    n_local: int          # per-PE slot count (the shape bucket served)
    probe_avg: float      # mean probe depth across the batch
    probe_max: int        # deepest probe walk in the batch
    wire_bytes: int       # the batch's exact routed bytes (both hops)
    seconds: float        # wall latency of the batch this request rode


class StoreRegistry:
    """Named `KmerCounter` tenants sharing one device mesh."""

    def __init__(self, mesh, axis_names: Sequence[str] = ("pe",)):
        self._mesh = mesh
        self._axes = tuple(axis_names)
        self._stores: Dict[str, object] = {}

    def register(self, name: str, counter) -> None:
        self._stores[name] = counter

    def load(self, name: str, ckpt_dir: str, cfg,
             step: Optional[int] = None) -> None:
        """Restore a tenant from its checkpoint directory
        (`KmerCounter.restore`: fingerprint-checked, elastically resharded
        if this mesh's PE count differs from the saved one)."""
        from repro.core import fabsp
        self.register(name, fabsp.KmerCounter.restore(
            ckpt_dir, self._mesh, cfg, self._axes, step=step))

    def get(self, name: str):
        try:
            return self._stores[name]
        except KeyError:
            raise UnknownStore(
                f"no store named {name!r} (have: {sorted(self._stores)})"
            ) from None

    def names(self) -> List[str]:
        return sorted(self._stores)


class QueryService:
    """Request intake over a registry: queue, coalesce per tenant, serve."""

    def __init__(self, registry: StoreRegistry):
        self._registry = registry
        self._pending: List[Tuple[str, np.ndarray]] = []

    def submit(self, tenant: str, kmers) -> int:
        """Queue one request; returns its index into the next `flush()`.
        Unknown tenants fail here, at intake, not at serve time."""
        self._registry.get(tenant)
        self._pending.append((tenant, np.asarray(kmers)))
        return len(self._pending) - 1

    def query(self, tenant: str, kmers):
        """One-shot unbatched request: (counts, RequestStats)."""
        counter = self._registry.get(tenant)
        t0 = time.perf_counter()
        counts = counter.count(kmers)
        dt = time.perf_counter() - t0
        qs = counter.last_query_stats
        return counts, self._request_stats(tenant, qs, len(counts), dt,
                                           n_hits=int((counts > 0).sum()))

    def flush(self):
        """Serve every queued request: one coalesced device batch per
        tenant (concatenated queries ride one routed exchange and one
        shape-bucketed executable), answers split back in request order.

        Returns a list aligned with submission order; each entry is
        (counts, RequestStats) on success, or the typed exception
        instance (`query.QueryUnavailable`, `UnknownStore`) when that
        request's tenant failed to serve. Failures are isolated per
        tenant -- one tenant refusing never throws away another tenant's
        computed answers or drops its queued requests. Zero-query
        requests short-circuit with an empty answer and zeroed stats, no
        device round-trip; the coalesced batch carries the tenant's own
        packed-word dtype (`_batch_dtype`), never a hardcoded uint32."""
        from jax.profiler import TraceAnnotation

        from repro.core import query as query_lib
        pending, self._pending = self._pending, []
        with TraceAnnotation("serve.flush", requests=len(pending),
                             queries=sum(len(q) for _, q in pending)):
            by_tenant: Dict[str, List[int]] = {}
            for i, (tenant, _) in enumerate(pending):
                by_tenant.setdefault(tenant, []).append(i)
            results: List[object] = [None] * len(pending)
            for tenant, idxs in by_tenant.items():
                try:
                    with TraceAnnotation("serve.coalesce"):
                        counter = self._registry.get(tenant)
                        for i in idxs:
                            if len(pending[i][1]) == 0:
                                results[i] = (np.zeros((0,), np.int32),
                                              self._zero_stats(tenant))
                        live = [i for i in idxs if len(pending[i][1])]
                        if not live:
                            continue
                        dt_word = self._batch_dtype(counter)
                        batch = np.concatenate(
                            [pending[i][1] if pending[i][1].ndim != 1
                             else pending[i][1].astype(dt_word, copy=False)
                             for i in live])
                    t0 = time.perf_counter()
                    counts = counter.count(batch)
                    dt = time.perf_counter() - t0
                except (query_lib.QueryUnavailable, UnknownStore) as e:
                    for i in idxs:
                        results[i] = e
                    continue
                with TraceAnnotation("serve.split"):
                    qs = counter.last_query_stats
                    off = 0
                    for i in live:
                        n = len(pending[i][1])
                        part = counts[off:off + n]
                        off += n
                        results[i] = (part, self._request_stats(
                            tenant, qs, n, dt,
                            n_hits=int((part > 0).sum())))
        return results

    @staticmethod
    def _batch_dtype(counter) -> np.dtype:
        """The tenant's packed-word dtype (uint32, or uint64 once k
        outgrows one 32-bit word) -- derived from its cfg, so empty and
        mixed-dtype requests coalesce to the store's own word width."""
        from repro.core import encoding
        cfg = counter._cfg
        return np.dtype(encoding.kmer_dtype(cfg.k, cfg.bits_per_symbol))

    @staticmethod
    def _zero_stats(tenant: str) -> RequestStats:
        return RequestStats(tenant=tenant, n_queries=0, n_hits=0,
                            batch_queries=0, batch_fill=0.0, n_local=0,
                            probe_avg=0.0, probe_max=0, wire_bytes=0,
                            seconds=0.0)

    @staticmethod
    def _request_stats(tenant: str, qs, n: int, seconds: float, *,
                       n_hits: int) -> RequestStats:
        return RequestStats(
            tenant=tenant, n_queries=n, n_hits=n_hits,
            batch_queries=qs.n_queries, batch_fill=qs.batch_fill,
            n_local=qs.n_local, probe_avg=qs.probe_avg,
            probe_max=qs.probe_max, wire_bytes=qs.wire_bytes,
            seconds=seconds)


def run_demo(n_requests: int = 0) -> None:
    """The CI one-shot: count a known read set, checkpoint it, restore it
    into the registry under two tenant names, serve batched queries with
    known answers (hits AND misses), and assert exact counts against the
    finalize() histogram. Exits nonzero on any mismatch."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core import fabsp, query
    from repro.data import genome

    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:min(4, len(jax.devices()))]), ("pe",))
    cfg = fabsp.DAKCConfig(k=13, chunk_reads=64)
    spec = genome.ReadSetSpec(genome_bases=4096, n_reads=256, read_len=64,
                              heavy_hitter_frac=0.3, seed=11)
    reads = jnp.asarray(genome.sample_reads(spec))
    kc = fabsp.KmerCounter(mesh, cfg)
    kc.update(reads)
    res, _ = kc.finalize()
    nsh, L = kc._num_pes, res.unique.shape[0] // kc._num_pes
    u = np.asarray(res.unique).reshape(nsh, L)
    c = np.asarray(res.counts).reshape(nsh, L)
    nu = np.asarray(res.num_unique)
    oracle = {int(u[s, i]): int(c[s, i])
              for s in range(nsh) for i in range(int(nu[s]))}

    with tempfile.TemporaryDirectory() as ckpt_dir:
        kc.save(ckpt_dir)
        registry = StoreRegistry(mesh)
        registry.load("human", ckpt_dir, cfg)
        registry.load("mouse", ckpt_dir, cfg)     # second tenant, same bins
        service = QueryService(registry)

        rng = np.random.default_rng(0)
        uniq = np.asarray(sorted(oracle), dtype=u.dtype)
        misses: List[int] = []
        while len(misses) < 64:
            x = int(rng.integers(0, 1 << 26))
            if x not in oracle:
                misses.append(x)
        q = np.concatenate([uniq, np.asarray(misses, dtype=u.dtype)])
        rng.shuffle(q)

        # batched intake: several requests per tenant, one flush
        parts = np.array_split(q, 5)
        order = []
        for j, part in enumerate(parts):
            order.append(service.submit("human" if j % 2 else "mouse", part))
        out = service.flush()
        for j, part in enumerate(parts):
            counts, st = out[order[j]]
            want = np.asarray([oracle.get(int(x), 0) for x in part],
                              np.int32)
            if not np.array_equal(counts, want):
                raise SystemExit(f"FAIL: request {j} counts diverged from "
                                 f"the finalize() histogram")
            print(f"  req[{j}] tenant={st.tenant:5s} n={st.n_queries:4d} "
                  f"hits={st.n_hits:4d} fill={st.batch_fill:.2f} "
                  f"probe_avg={st.probe_avg:.2f} max={st.probe_max} "
                  f"wire={st.wire_bytes}")

        # typed-error path: unknown tenants fail at intake
        try:
            service.submit("yeast", q[:4])
            raise SystemExit("FAIL: unknown tenant did not raise")
        except UnknownStore:
            pass

        # spilled-tenant serve drill: a spill-engaged counter answers
        # EXACTLY through the spilled-bin query tier (default 'fold')
        spilled = fabsp.KmerCounter(mesh, dataclasses.replace(
            cfg, spill="always", spill_dir=ckpt_dir + "/spill"))
        spilled.update(reads)
        registry.register("spilled", spilled)
        sq = q[:256]
        counts, st = service.query("spilled", sq)
        want = np.asarray([oracle.get(int(x), 0) for x in sq], np.int32)
        if not np.array_equal(counts, want):
            raise SystemExit("FAIL: spilled tenant counts diverged from "
                             "the finalize() histogram")
        sqs = spilled.last_query_stats
        print(f"  spilled tenant served exactly: n={st.n_queries} "
              f"bins_probed={sqs.bins_probed} bin_folds={sqs.bin_folds}")

        # strict-refusal drill THROUGH flush: the refusing tenant's
        # requests come back as typed errors; the other tenant's queued
        # answers survive untouched (the partial-failure bugfix)
        strict = fabsp.KmerCounter(mesh, dataclasses.replace(
            cfg, spill="always", spill_dir=ckpt_dir + "/strict",
            spill_query="refuse"))
        strict.update(reads)
        registry.register("strict", strict)
        i0 = service.submit("human", q[:32])
        i1 = service.submit("strict", q[:32])
        i2 = service.submit("human", q[32:64])
        i3 = service.submit("human", np.zeros((0,), u.dtype))
        out = service.flush()
        if not (isinstance(out[i1], query.QueryUnavailable)
                and isinstance(out[i0], tuple)
                and isinstance(out[i2], tuple)):
            raise SystemExit("FAIL: flush did not isolate the refusing "
                             "tenant")
        for i, lo, hi in ((i0, 0, 32), (i2, 32, 64)):
            want = np.asarray([oracle.get(int(x), 0) for x in q[lo:hi]],
                              np.int32)
            if not np.array_equal(out[i][0], want):
                raise SystemExit("FAIL: surviving tenant's flush answers "
                                 "diverged")
        if out[i3][0].size != 0 or out[i3][1].n_queries != 0:
            raise SystemExit("FAIL: empty request did not short-circuit")
        print("  strict tenant refused (typed, per-request); other "
              "tenant's answers survived the flush")

        # read-write interleave: a LIVE tenant takes update() between
        # flushes, and every flush answers the committed prefix exactly
        from repro.core import serial
        live = fabsp.KmerCounter(mesh,
                                 dataclasses.replace(cfg, chunk_reads=16))
        registry.register("live", live)
        running: Dict[int, int] = {}
        qset = q[:128]
        for batch in np.array_split(np.asarray(reads), 4):
            live.update(jnp.asarray(batch))
            for w, n in serial.count_kmers_python(batch, cfg.k).items():
                running[w] = running.get(w, 0) + n
            service.submit("live", qset)
            (counts, _st), = service.flush()
            want = np.asarray([running.get(int(x), 0) for x in qset],
                              np.int32)
            if not np.array_equal(counts, want):
                raise SystemExit("FAIL: interleaved flush diverged from "
                                 "the committed prefix")
        print("  read-write interleave: 4 update/flush rounds, each "
              "flush exact against the committed prefix")

        if n_requests > 0:
            lat = []
            for _ in range(n_requests):
                sub = rng.choice(q, size=min(256, q.size), replace=True)
                _, st = service.query("human", sub.astype(u.dtype))
                lat.append(st.seconds)
            lat = np.asarray(sorted(lat))
            total_q = n_requests * min(256, q.size)
            print(f"  serving loop: {n_requests} requests, "
                  f"{total_q / lat.sum():.0f} queries/s, "
                  f"p50={lat[len(lat) // 2] * 1e3:.1f}ms "
                  f"p99={lat[int(len(lat) * 0.99)] * 1e3:.1f}ms")
    print("kc_serve demo OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--demo", action="store_true",
                    help="one-shot count -> save -> restore -> serve gate "
                         "(asserts exact counts; the CI query gate)")
    ap.add_argument("--requests", type=int, default=0,
                    help="with --demo: also run a serving loop of N "
                         "single-tenant requests and print QPS/latency")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    if args.demo:
        run_demo(args.requests)
        return
    ap.error("this harness is library-first: use --demo, or build a "
             "StoreRegistry/QueryService from your own driver")


if __name__ == "__main__":
    main()
