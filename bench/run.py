"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic and metrics are named in
``BENCHMARK.json`` at the checkout's root (see ``bench/harness/manifest.py``).
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last the
``checks``: each number compared with its limit, also printed as the last
lines of stderr.  Without a TPU, or with fewer chips than the cell asks for,
it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere on disk


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from harness import cell, device

    try:
        result = cell.run(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    except device.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    cell.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
