"""Benchmark harness: one entry per paper figure + protocol microbenches.

Prints ``name,us_per_call,derived`` CSV rows (figure benches report the
final-loss / error-term values as ``derived``); writes the full per-figure
curves to benchmarks/out/<figure>.csv.

Every experimental figure (and the ``section7_sweep`` grid) is generated by
the scenario registry through the scan-compiled engine — see
``repro.core.scenarios``.  ``--only`` selects a subset of figures by name;
``--no-kernels`` skips the kernel microbenches.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# runnable as `python benchmarks/run.py` from the repo root: the script dir
# is on sys.path but the repo root (the `benchmarks` package parent) is not
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from benchmarks.paper_figures import FIGURES
    from repro.compile_cache import enable_compile_cache

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--only", nargs="*", default=None, metavar="FIG",
        help=f"figure subset to run (default: all of {sorted(FIGURES)})",
    )
    parser.add_argument(
        "--no-kernels", action="store_true", help="skip the kernel microbenches"
    )
    args = parser.parse_args()

    selected = sorted(FIGURES) if args.only is None else args.only
    if not selected:
        parser.error("--only given without figure names")
    unknown = [n for n in selected if n not in FIGURES]
    if unknown:
        parser.error(f"unknown figures {unknown}; have {sorted(FIGURES)}")

    enable_compile_cache()
    os.makedirs("benchmarks/out", exist_ok=True)
    print("name,us_per_call,derived")

    for name in selected:
        t0 = time.perf_counter()
        rows = FIGURES[name]()
        elapsed_us = (time.perf_counter() - t0) * 1e6
        path = f"benchmarks/out/{name}.csv"
        with open(path, "w") as f:
            f.write("label,x,value\n")
            for label, x, v in rows:
                f.write(f"{label},{x},{v}\n")
        # derived: the last value of the last curve (final loss / error term)
        print(f"{name},{elapsed_us:.0f},{rows[-1][2]:.6g}")

    # drop every figure's pinned compiled programs (and the device buffers
    # their closures hold) before the kernel phase: the microbenches should
    # time kernels, not fight the figures for memory
    from repro.core import engine

    dropped = engine.clear_program_caches()
    print(f"# cleared program caches: {dropped}", file=sys.stderr)

    if args.no_kernels:
        return

    from benchmarks.kernel_bench import (
        aggregator_bench,
        compression_bench,
        kernel_vs_ref_bench,
        lane_batched_bench,
        write_kernel_json,
    )

    from repro.launch.tuner import get_store

    kernel_rows = []
    for rows in (
        aggregator_bench(),
        compression_bench(),
        kernel_vs_ref_bench(),
        # the real-shape batched-vs-loop pairs feed the crossover table the
        # ops wrappers dispatch from (repro.launch.tuner.lane_dispatch)
        lane_batched_bench(store=get_store()),
    ):
        kernel_rows += rows
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived:.4g}")
    # machine-readable perf trajectory, next to the figure CSVs
    write_kernel_json(kernel_rows, "benchmarks/out/BENCH_kernels.json")


if __name__ == "__main__":
    main()
