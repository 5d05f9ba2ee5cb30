"""Find a serving cell's knee: the highest offered rate whose backlog stays
bounded over a window (the benchmark's own runs never run this).

    python3 bench/sweep.py --workload <serve cell> --seconds <s> \
        --seed <n> --rates 1000,2000,4000

One process counts the store and warms every batch length once, then
offers each rate in turn for `--seconds`. Per rate it prints one JSON
line: the latency median and 99th percentile, the median latency of the
first and the last quarter of the requests (a backlog that grows shows
as a last quarter far above the first), the mean and largest requests
per flush, and the window's length. Needs the chip the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import data, run, workload  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = workload.load_cell(args.workload)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"sweep: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    cfg, traffic = cell.config, cell.traffic
    gen = data.genome(cfg, args.seed)
    reads = data.sample_reads(gen, cfg["n_reads"], cfg["read_len"],
                              cfg.get("error_rate", 0.0),
                              data.rng(args.seed, data.READS))
    system = run.make_system(cell, devices[:cell.chips], reads)
    cap = traffic["max_requests"]
    warm = workload.query_requests(cfg, traffic, gen, cap, args.seed + 1)
    for r in range(1, cap + 1):
        system.submit(list(warm[:r]))
        system.flush()
    print(f"set-up {time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    for rate in [float(x) for x in args.rates.split(",")]:
        due = workload.arrivals(rate, args.seconds, args.seed)
        queries = workload.query_requests(cfg, traffic, gen, len(due),
                                          args.seed)
        out = workload.run_serve(system, queries, due, cap)
        lat = out["lat"] * 1e3
        q = len(lat) // 4
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "p50_ms": workload.percentile(lat, 50),
            "p99_ms": workload.percentile(lat, 99),
            "first_quarter_p50_ms": float(np.median(lat[:q])),
            "last_quarter_p50_ms": float(np.median(lat[-q:])),
            "per_flush_mean": float(out["sizes"].mean()),
            "per_flush_max": int(out["sizes"].max()),
            "flush_ms_median": float(np.median(out["flush_s"]) * 1e3),
            "window_s": out["window_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
