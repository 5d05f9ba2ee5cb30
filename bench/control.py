"""Read a cell's compared numbers for the program and for the control, on
many seeds in one process (the benchmark's own runs never run this).

    python3 bench/control.py --workload <cell> --seconds <s> \
        --program-seeds 1,2,3 --control-seeds 4,5,6

The control is the plain reference put in the program's place with one
guarantee broken: its store keeps a 24-bit hash of each k-mer instead of
the k-mer (a one-row count-min sketch of 2**24 counters, the program's
store ceiling), so k-mers that share a counter merge. It runs through the
same window, at the cell's own size and load, and must come out not
correct. Each run prints one JSON line: who ran, the seed, `correct` and
the compared numbers. Needs the chip the cell asks for, as run.py does.
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

from bench import run, workload  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)
    cell = workload.load_cell(args.workload)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    runs = ([("program", s, 0) for s in args.program_seeds]
            + [("control", s, run.CONTROL_BITS) for s in args.control_seeds])
    for who, seed, bits in runs:
        t0 = time.perf_counter()
        r = run.run_cell(workload.load_cell(args.workload), seed,
                         args.seconds, False, devices[:cell.chips],
                         t_start=t0, control_bits=bits)
        print(json.dumps({"who": who, "seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()},
                          "compared": r["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
