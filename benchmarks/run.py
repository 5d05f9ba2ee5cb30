"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).

  PYTHONPATH=src python -m benchmarks.run            # all benchmarks
  PYTHONPATH=src python -m benchmarks.run fig12 tab3 # substring filter
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: toy size, 1 rep
  BENCH_SCALE=4 ... for bigger datasets

--smoke runs every registered benchmark at toy size (BENCH_SCALE=0.125
unless already set), with single timing reps and record-file writes
suppressed (common.SMOKE) -- a fast does-it-still-run gate, not a perf
measurement. Composes with substring filters.
"""

import os
import sys
import time
import traceback
from typing import List, Tuple

MODULES = [
    ("fig6+fig9.shared_memory", "benchmarks.shared_memory"),
    ("fig7+fig8.strong_scaling", "benchmarks.strong_scaling"),
    ("fig10.weak_scaling", "benchmarks.weak_scaling"),
    ("fig11.topology", "benchmarks.topology"),
    ("fig12.aggregation_ablation", "benchmarks.aggregation_ablation"),
    ("perf.stream_receiver", "benchmarks.stream_receiver"),
    ("perf.superkmer_transport", "benchmarks.superkmer_transport"),
    ("perf.route_lanes", "benchmarks.route_lanes"),
    ("perf.spill_tier", "benchmarks.spill_tier"),
    ("perf.query_service", "benchmarks.query_service"),
    ("perf.load_balance", "benchmarks.load_balance"),
    ("fig13.tuning", "benchmarks.tuning"),
    ("tab3+fig2.memory_overhead", "benchmarks.memory_overhead"),
    ("fig3+fig4+fig5.model_validation", "benchmarks.model_validation"),
    ("lm.roofline", "benchmarks.lm_roofline"),
]


def parse_args(argv: List[str]) -> Tuple[List[str], bool]:
    """(substring filters, smoke flag); unknown --flags are an error."""
    filters, smoke = [], False
    for a in argv:
        if a == "--smoke":
            smoke = True
        elif a.startswith("--"):
            raise SystemExit(f"unknown flag {a!r} (only --smoke)")
        else:
            filters.append(a)
    return filters, smoke


def main() -> None:
    import importlib

    from repro.launch import compile_cache
    compile_cache.enable()
    filters, smoke = parse_args(sys.argv[1:])
    if smoke:
        # Before any benchmark module (hence benchmarks.common) imports:
        # subprocess-based benchmarks inherit these via os.environ.
        os.environ.setdefault("BENCH_SCALE", "0.125")
        os.environ["BENCH_SMOKE"] = "1"
        print("# smoke mode: toy sizes, 1 rep, records suppressed",
              flush=True)
    print("name,us_per_call,derived")
    failures = []
    for name, modname in MODULES:
        if filters and not any(f in name for f in filters):
            continue
        t0 = time.time()
        try:
            importlib.import_module(modname).run()
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
