"""The general traffic generator and the systems it drives.

A cell (`BENCHMARK.json` `workloads`) names a configuration
(`bench/configs/<config>.json`: the deployment's data and layout) and a
traffic mix (`bench/traffic/<traffic>.json`: how the data is driven).
Both are data; this module reads them and knows no cell by name. A mix's
`driver` picks one of two loops:

- `count`: whole counting jobs back to back. A job is a fresh count of
  the read set, already in device memory, to its committed histogram:
  `KmerCounter.update` over the config's batches, then `finalize()`.
  Jobs start until the window's seconds have passed; the last one ends.
- `serve`: an open loop of lookup requests against a store counted from
  the read set in set-up. Request i is due at a fixed time; when the loop
  is free it submits every request already due (at most `max_requests`
  of them) and flushes them as one `QueryService` batch. A request's
  latency runs from its due time to the flush that answered it.

Each loop drives a *system*: the program (`ProgramCount`,
`ProgramServe`) or, for the control, the plain reference with its keys
cut to a hash (`SketchCount`, `SketchServe`), through the same calls.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from bench import data

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
TENANT = "genome"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]          # manifest entries this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: Optional[dict] = None) -> Cell:
    """The cell `name` with its configuration, traffic and metrics, found
    by the names in the manifest."""
    m = manifest if manifest is not None else json.loads(
        MANIFEST.read_text())
    cells = {w["name"]: w for w in m["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {MANIFEST.name} "
                       f"(have: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in m["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [x for x in m["end_to_end"] if _reports(x, name)]
    names = {x["name"] for x in e2e}
    layer = [x for x in m["per_layer"]
             if (name in x["workloads"] if "workloads" in x
                 else x["moves"] in names)]
    return Cell(name, w["chips"], config, traffic, e2e, layer)


# --- spans ------------------------------------------------------------------

def no_span(name: str):
    return contextlib.nullcontext()


def trace_span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# --- the program -------------------------------------------------------------

AXES = ("pe",)


def mesh_for(devices):
    """The cell's chips as one 1-D `pe` axis."""
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices), AXES)


def dakc_config(config: dict):
    from repro.core import fabsp
    return fabsp.DAKCConfig(k=config["k"], canonical=config["canonical"],
                            **config.get("dakc", {}))


def _sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(AXES[0]))


def shard_histogram(result, num_pes: int):
    """Host (k-mers, counts) of a per-shard `AccumResult`, in shard order
    (each shard owns a disjoint set, so a k-mer seen twice is a fault)."""
    u = np.asarray(result.unique).reshape(num_pes, -1)
    c = np.asarray(result.counts).reshape(num_pes, -1)
    nu = np.asarray(result.num_unique).reshape(-1)
    uu = np.concatenate([u[s, :nu[s]] for s in range(num_pes)])
    cc = np.concatenate([c[s, :nu[s]] for s in range(num_pes)])
    order = np.argsort(uu, kind="stable")
    return uu[order], cc[order].astype(np.int64)


class ProgramCount:
    """One counting job of the program per `job()` call."""

    def __init__(self, config: dict, devices, reads: np.ndarray):
        import jax
        self.mesh = mesh_for(devices)
        self.num_pes = len(devices)
        self.cfg = dakc_config(config)
        sharding = _sharding(self.mesh)
        self.inputs = [jax.device_put(p, sharding)
                       for p in np.array_split(reads, config["batches"])]
        jax.block_until_ready(self.inputs)

    def job(self, span: Callable = no_span, batches: Optional[int] = None):
        """Count the read set (or its first `batches` batches) to its
        histogram; returns (result, counters)."""
        import jax
        from repro.core import fabsp
        kc = fabsp.KmerCounter(self.mesh, self.cfg, AXES)
        sent = 0
        for batch in self.inputs[:batches]:
            with span("update"):
                sent += int(kc.update(batch).sent_words)
        with span("finalize"):
            result, _ = kc.finalize()
            jax.block_until_ready(result.unique)
        return result, {"sent_words": sent}

    def warm(self) -> None:
        """Every program a job runs, once: batches are alike in shape and
        the store's size is fixed, so two batches and the finalize do (JAX
        traces the update apart for a fresh store and an updated one)."""
        self.job(batches=2)

    def histogram(self, result):
        return shard_histogram(result, self.num_pes)


class ProgramServe:
    """The program's query service over a store counted from the reads."""

    def __init__(self, config: dict, devices, reads: np.ndarray):
        import jax
        from repro.core import fabsp
        from repro.launch.kc_serve import QueryService, StoreRegistry
        mesh = mesh_for(devices)
        sharding = _sharding(mesh)
        kc = fabsp.KmerCounter(mesh, dakc_config(config), AXES)
        for part in np.array_split(reads, config["batches"]):
            kc.update(jax.device_put(part, sharding))
        registry = StoreRegistry(mesh, AXES)
        registry.register(TENANT, kc)
        self.service = QueryService(registry)

    def submit(self, requests: List[np.ndarray]) -> None:
        for r in requests:
            self.service.submit(TENANT, r)

    def flush(self) -> list:
        return [a if isinstance(a, Exception) else np.asarray(a[0])
                for a in self.service.flush()]


# --- the control -------------------------------------------------------------

class SketchCount:
    """The control for `count`: the reference histogram, with each k-mer's
    count read from a `data.Sketch` of 2**bits counters."""

    def __init__(self, config: dict, reads: np.ndarray, bits: int):
        self.k = config["k"]
        hist = data.count_kmers(reads, self.k, config["canonical"])
        self.uniq = hist[0]
        self.sketch = data.Sketch(hist, bits)

    def job(self, span: Callable = no_span):
        # the histogram's k-mers are canonical already
        return (self.uniq, self.sketch.counts(self.uniq, self.k, False)), {}

    def warm(self) -> None:
        """Nothing to compile: the sketch is NumPy."""

    def histogram(self, result):
        return result


class SketchServe:
    """The control for `serve`: lookups answered from a `data.Sketch`."""

    def __init__(self, config: dict, reads: np.ndarray, bits: int):
        self.k, self.canon = config["k"], config["canonical"]
        self.sketch = data.Sketch(data.count_kmers(reads, self.k,
                                                   self.canon), bits)
        self.pending: List[np.ndarray] = []

    def submit(self, requests: List[np.ndarray]) -> None:
        self.pending += requests

    def flush(self) -> list:
        out = [self.sketch.counts(r, self.k, self.canon)
               for r in self.pending]
        self.pending = []
        return out


# --- traffic -----------------------------------------------------------------

def instances(config: dict) -> int:
    """k-mer instances in one count of the read set."""
    return config["n_reads"] * (config["read_len"] - config["k"] + 1)


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times of an open loop at `rate` requests/s over `seconds`.

    The gaps are the round(rate * seconds) quantiles of the exponential
    distribution (a Poisson process's gaps), in an order drawn from the
    seed: every seed offers the same set of gaps, so seeds change the
    order of arrivals and not the amount of work."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = data.rng(seed, data.ARRIVALS).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


def query_requests(config: dict, traffic: dict, gen: np.ndarray, n: int,
                   seed: int) -> np.ndarray:
    """(n, words) forward-strand k-mer words of n fresh reads of the
    genome, one read per request."""
    reads = data.sample_reads(gen, n, traffic["query_read_len"],
                              traffic["query_error_rate"],
                              data.rng(seed, data.QUERIES))
    return data.forward_words(reads, config["k"])


def percentile(lat: np.ndarray, q: float) -> float:
    """Nearest-rank q-th percentile of all requests; a failed request is
    +inf, so failures push the tail past any limit."""
    s = np.sort(lat)
    return float(s[max(int(np.ceil(q / 100.0 * s.size)) - 1, 0)])


def run_count(system, seconds: float, span: Callable = no_span) -> dict:
    """Jobs back to back until `seconds` have passed; the last one ends."""
    jobs = []
    t0 = time.perf_counter()
    with span("window"):
        while not jobs or time.perf_counter() - t0 < seconds:
            with span("job"):
                start = time.perf_counter()
                result, counters = system.job(span)
                end = time.perf_counter()
            with span("record"):
                jobs.append({"start": start, "end": end, "result": result,
                             **counters})
    return {"jobs": jobs, "window_s": jobs[-1]["end"] - jobs[0]["start"]}


def run_serve(system, requests: np.ndarray, due: np.ndarray,
              max_requests: int, span: Callable = no_span) -> dict:
    """The open loop: returns per-request latency, answers and failures."""
    n = len(due)
    lat = np.full(n, np.inf)
    # one array for all answers: a list of per-request arrays would grow
    # the heap's tracked objects and with them the interpreter's pauses
    answers = np.zeros(requests.shape, np.int64)
    failed = np.zeros(n, bool)
    flush_s, sizes, late = [], [], []
    i = 0
    t0 = time.perf_counter()
    with span("window"):
        while i < n:
            now = time.perf_counter() - t0
            if now < due[i]:
                # spin, not sleep: a sleeping thread can wake tens of ms
                # late, which would read as the service's latency
                with span("wait"):
                    while now < due[i]:
                        now = time.perf_counter() - t0
                late.append(now - due[i])
            j = min(int(np.searchsorted(due, now, side="right")),
                    i + max_requests, n)
            j = max(j, i + 1)
            with span("submit"):
                system.submit(list(requests[i:j]))
            with span("flush"):
                f0 = time.perf_counter()
                out = system.flush()
                f1 = time.perf_counter()
            with span("record"):
                lat[i:j] = (f1 - t0) - due[i:j]
                for r, a in zip(range(i, j), out):
                    if isinstance(a, Exception):
                        failed[r] = True
                        lat[r] = np.inf
                    else:
                        answers[r] = a
                flush_s.append(f1 - f0)
                sizes.append(j - i)
            i = j
    return {"lat": lat, "answers": answers, "failed": failed,
            "flush_s": np.asarray(flush_s), "sizes": np.asarray(sizes),
            "late": np.asarray(late) if late else np.zeros(1),
            "window_s": time.perf_counter() - t0}
