"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name
from `BENCHMARK.json` (see bench/workload.py). One run:

1. set-up: checks that JAX sees a TPU with the chips the cell asks for
   (else exits 2, printing no result), makes the data from `--seed`,
   builds the system and runs the window's shapes once (`setup_s` is the
   time from process start to the end of this step, compilation
   included; the persistent compile cache is `repro.launch.compile_cache`'s);
2. the window: `--seconds` of the cell's traffic, with the profiler on
   when `--trace 1`; compilations inside it are counted and printed;
3. the check: device memory peak read, the program's state freed, then
   every answer of the window compared with the plain reference
   (bench/data.py) and each compared number printed beside its limit.

The last line of standard output is the result object: `correct`,
`attempted`, `failed`, `metrics` (end-to-end with `--trace 0`, per-layer
with `--trace 1`), `device`, with `--trace 1` a `breakdown`, and last the
numbers compared, under `compared`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    # run as a script: import the harness as the `bench` package and the
    # program from src/, never bench/'s modules as top-level names
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import data, trace as xtrace, workload  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
CONTROL_BITS = 24     # the control's sketch: 2**24 counters, the store ceiling


class CompileCounter:
    """Counts jaxpr traces and backend compiles while `active`."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event in self.counts:
            self.counts[event] += 1

    def total(self) -> int:
        return sum(self.counts.values())


def load_metric(name: str):
    """The reader of per-layer metric `name`: bench/metrics/<name>.py."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have: {sorted(table)})")
    return table[kind]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _profiled(enabled: bool, log_dir: Path):
    import contextlib

    import jax
    if not enabled:
        return contextlib.nullcontext()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def make_system(cell, devices, reads, control_bits: int = 0):
    """The program on `devices`, or with `control_bits` the control: the
    reference over a sketch of 2**control_bits counters."""
    drv = cell.traffic["driver"]
    if drv == "count":
        return (workload.SketchCount(cell.config, reads, control_bits)
                if control_bits else workload.ProgramCount(
                    cell.config, devices, reads))
    if drv == "serve":
        return (workload.SketchServe(cell.config, reads, control_bits)
                if control_bits else workload.ProgramServe(
                    cell.config, devices, reads))
    raise ValueError(f"unknown traffic driver {drv!r}")


def run_cell(cell, seed: int, seconds: float, traced: bool, devices, *,
             t_start: float, control_bits: int = 0,
             trace_dir: Path = TRACE_DIR) -> dict:
    """One run of `cell`; returns the result object (see module doc)."""
    cfg, traffic = cell.config, cell.traffic
    gen = data.genome(cfg, seed)
    reads = data.sample_reads(gen, cfg["n_reads"], cfg["read_len"],
                              cfg.get("error_rate", 0.0),
                              data.rng(seed, data.READS))
    system = make_system(cell, devices, reads, control_bits)
    span = workload.trace_span if traced else workload.no_span
    serve = traffic["driver"] == "serve"
    if serve:
        due = workload.arrivals(traffic["rate_per_s"], seconds, seed)
        queries = workload.query_requests(cfg, traffic, gen, len(due), seed)
        cap = traffic["max_requests"]
        warm = workload.query_requests(cfg, traffic, gen, cap, seed + 1)
        for r in range(1, cap + 1):          # every batch length the loop
            system.submit(list(warm[:r]))    # can send
            system.flush()
    else:
        system.warm()
    setup_s = time.perf_counter() - t_start

    counter = CompileCounter()
    shutil.rmtree(trace_dir, ignore_errors=True)
    counter.active = True
    with _profiled(traced, trace_dir):
        out = (workload.run_serve(system, queries, due, cap, span) if serve
               else workload.run_count(system, seconds, span))
    counter.active = False
    kinds = ", ".join(f"{e.rsplit('/', 1)[-1]}={n}"
                      for e, n in counter.counts.items())
    print(f"compilations in the window: {counter.total()} ({kinds})",
          file=sys.stderr, flush=True)
    peak = memory_peak(devices)
    summary = None
    if traced:
        summary = xtrace.reduce(xtrace.load(xtrace.find_xplane(trace_dir)))

    # the check: the program's state goes first, then the reference runs
    k, canon = cfg["k"], cfg["canonical"]
    if serve:
        system = None
        gc.collect()
        ref = data.count_kmers(reads, k, canon)
        done = ~out["failed"]
        want = data.lookup(ref, queries[done], k, canon)
        wrong = int(np.count_nonzero(
            np.any(out["answers"][done] != want, axis=1)))
        n_failed = int(out["failed"].sum())
        attempted = len(due)
        compared = {"answers_wrong": (wrong, 0),
                    "requests_failed": (n_failed, 0)}
    else:
        hists = [system.histogram(j["result"]) for j in out["jobs"]]
        for j in out["jobs"]:
            j["result"] = None
        system = None
        gc.collect()
        ref = data.count_kmers(reads, k, canon)
        bad = [data.histogram_mismatch(h, ref) for h in hists]
        n_failed = sum(1 for b in bad if b)
        attempted = len(hists)
        compared = {"kmers_wrong": (int(sum(bad)), 0),
                    "jobs_wrong": (n_failed, 0)}
    correct = all(v <= lim for v, lim in compared.values())

    if traced:
        counters = _counters(cell, out, serve)
        ctx = SimpleNamespace(trace=summary, counters=counters,
                              peaks=load_peaks(devices[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = _end_to_end(cell, out, serve, setup_s)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(n_failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = xtrace.breakdown(summary)
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, (v, lim) in compared.items()}
    _report_side(cell, out, serve, setup_s)
    return result


def _counters(cell, out: dict, serve: bool) -> dict:
    if serve:
        words = cell.traffic["query_read_len"] - cell.config["k"] + 1
        return {"flush_s": out["flush_s"],
                "live_queries": len(out["lat"]) * words}
    jobs = out["jobs"]
    return {"jobs": len(jobs),
            "sent_words": sum(j["sent_words"] for j in jobs)}


def _end_to_end(cell, out: dict, serve: bool, setup_s: float) -> dict:
    values = {"setup_s": setup_s}
    if serve:
        # serve_p<q>_ms: the q-th percentile of every request's latency
        lat_ms = out["lat"] * 1e3
        for m in cell.end_to_end:
            q = re.fullmatch(r"serve_p(\d+)_ms", m["name"])
            if q:
                values[m["name"]] = workload.percentile(lat_ms, int(q[1]))
    else:
        values["count_kmers_per_s"] = (
            len(out["jobs"]) * workload.instances(cell.config)
            / out["window_s"])
    metrics = {}
    for m in cell.end_to_end:
        v = values[m["name"]]
        metrics[m["name"]] = {"value": v if np.isfinite(v) else None,
                              "unit": m["unit"]}
    return metrics


def _report_side(cell, out: dict, serve: bool, setup_s: float) -> None:
    """Plain-text observations on standard error (not metrics)."""
    p = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    p(f"set-up {setup_s:.3f} s")
    if serve:
        sizes = out["sizes"]
        lat_ms = out["lat"] * 1e3
        p("latency ms: " + ", ".join(
            f"p{q} {workload.percentile(lat_ms, q):.3f}"
            for q in (50, 90, 95, 99, 99.9, 100))
          + f"; flushes over 50 ms: {int((out['flush_s'] > 0.05).sum())}")
        p(f"requests {len(out['lat'])}, flushes {len(sizes)}, requests per "
          f"flush mean {sizes.mean():.2f} max {sizes.max()}, flush median "
          f"{np.median(out['flush_s']) * 1e3:.3f} ms, generator late "
          f"median {np.median(out['late']) * 1e3:.3f} ms, window "
          f"{out['window_s']:.3f} s")
    else:
        d = [j["end"] - j["start"] for j in out["jobs"]]
        p(f"jobs {len(d)}, job seconds {', '.join(f'{x:.4f}' for x in d)}, "
          f"window {out['window_s']:.3f} s")


def print_result(result: dict) -> None:
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = workload.load_cell(args.workload)
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    print(f"bench: {args.workload} seed {args.seed} on {cell.chips} x "
          f"{devices[0].device_kind}; compile cache {cache}",
          file=sys.stderr, flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], t_start=T_START)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
