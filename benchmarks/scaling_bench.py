"""Speedup-vs-device-count scaling curves for the sharded grid engine.

JAX fixes its device count at first backend init, so one process cannot
sweep it: the parent re-executes this module as a ``--child`` subprocess
per point with ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` for
K in {1, 2, 4, 8}, each child runs the same auto-tuned warm synthetic
sweep (``scenarios.run_grid(..., shard="shard_map",
max_lanes_per_device="auto")``) and prints one JSON row; the parent
assembles ``benchmarks/out/BENCH_scaling.json`` (schema below, validated
in tier-1 by scripts/bench_smoke.py) with speedup-vs-1-device columns.

Each row carries the roofline wiring next to the wall clock: the chunk
program's optimized HLO (``scenarios.grid_compiled_hlo``) analyzed by
``launch.roofline.analyze_compiled`` gives a predicted runtime at the
device's peaks, and ``pct_of_peak`` = predicted / measured — the
relative-efficiency number ``scripts/perf_gate.py`` tracks across PRs
alongside warm seconds.

Every point is a CPU simulation: the children run with ``JAX_PLATFORMS=cpu``
even on a host with a TPU (one process per chip), and the parent never
touches jax.  Forced host devices share the same physical cores, so on a
small CI box the *absolute* speedups hover near 1; what the curve certifies is that sharding
never falls off a cliff (monotonicity within tolerance) and that warm time
does not regress vs the committed baseline — see scripts/perf_gate.py.

Standalone:

    PYTHONPATH=src:. python benchmarks/scaling_bench.py
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO_ROOT, os.path.join(REPO_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SCALING_SCHEMA_VERSION = 1
DEVICE_COUNTS = (1, 2, 4, 8)

# the default sweep: one synthetic_sweep compile bucket, big enough that 8
# devices have >= several lanes each, small enough that 4 child processes
# (each paying its own jax init + compile) finish in CI minutes
DEFAULTS = dict(lanes=64, steps=6, n_devices=10, dim=16)


def scaling_row(
    lanes: int = DEFAULTS["lanes"],
    steps: int = DEFAULTS["steps"],
    n_devices: int = DEFAULTS["n_devices"],
    dim: int = DEFAULTS["dim"],
    max_lanes_per_device="auto",
    shard: str = "shard_map",
) -> dict:
    """One scaling point at the CURRENT process's device count.

    Runs the sweep cold (program caches cleared first — an honest
    compile-included time) then warm, asserts the warm run made zero
    program-cache misses, and attaches the tuned chunk capacity
    (``engine.last_grid_chunk_info``) and the roofline %-of-peak of the
    warm time.
    """
    import jax

    from repro.core import engine, scenarios
    from repro.launch import roofline
    from repro.timing import wallclock

    scns = scenarios.synthetic_sweep(lanes, n_devices=n_devices, n_byz=3)
    kw = dict(dim=dim, shard=shard, max_lanes_per_device=max_lanes_per_device)

    def timed():
        t0 = wallclock()
        res = scenarios.run_grid(scns, steps, **kw)
        jax.block_until_ready([r.x for r in res.values()])
        return wallclock() - t0

    engine.clear_program_caches()  # cold time includes every compile
    cold_s = timed()
    misses0 = engine._grid_program.cache_info().misses
    warm_s = timed()
    assert engine._grid_program.cache_info().misses == misses0, (
        "warm scaling sweep missed the grid-program cache"
    )
    chunk = engine.last_grid_chunk_info()

    hlo = scenarios.grid_compiled_hlo(scns, steps, **kw)
    analysis = roofline.analyze_compiled(hlo)
    n_calls = -(-chunk["n_lanes"] // chunk["chunk"])  # chunks per sweep
    pct = roofline.percent_of_peak(analysis, warm_s, calls=n_calls)

    return {
        "devices": int(jax.device_count()),
        "platform": str(jax.default_backend()),
        "lanes": int(lanes),
        "steps": int(steps),
        "cold_s": float(cold_s),
        "warm_s": float(warm_s),
        "lanes_per_s": float(lanes / warm_s),
        "chunk": int(chunk["chunk"]),
        "max_lanes_per_device": int(chunk["max_lanes_per_device"]),
        "auto": bool(chunk["auto"]),
        "predicted_s": float(analysis["predicted_s"] * n_calls),
        "pct_of_peak": float(pct),
        "dominant_term": str(analysis["dominant"]),
    }


def _child_env(n_devices: int) -> dict:
    """Subprocess env forcing ``n_devices`` host devices before jax init.

    The children are a CPU simulation (forced host devices), so they run with
    ``JAX_PLATFORMS=cpu``: on a host with a TPU they must not contend for the
    chip, which only one process may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT, os.path.join(REPO_ROOT, "src"),
         env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    return env


def scaling_curve(
    device_counts=DEVICE_COUNTS,
    lanes: int = DEFAULTS["lanes"],
    steps: int = DEFAULTS["steps"],
    n_devices: int = DEFAULTS["n_devices"],
    dim: int = DEFAULTS["dim"],
    out_path: str = "benchmarks/out/BENCH_scaling.json",
) -> dict:
    """Run one ``scaling_row`` child per forced device count and write the
    assembled ``BENCH_scaling.json``.

    Schema (validated by scripts/bench_smoke.py):
      {"schema_version": 1, "lanes": int, "steps": int, "n_devices": int,
       "dim": int,
       "rows": [{"devices", "platform", "lanes", "steps", "cold_s",
                 "warm_s", "lanes_per_s", "chunk", "max_lanes_per_device",
                 "auto", "predicted_s", "pct_of_peak", "dominant_term",
                 "speedup_vs_1"}, ...]}   # rows sorted by devices
    """
    rows = []
    for k in device_counts:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             "--lanes", str(lanes), "--steps", str(steps),
             "--n-devices", str(n_devices), "--dim", str(dim)],
            env=_child_env(k), cwd=REPO_ROOT,
            capture_output=True, text=True,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"scaling child (devices={k}) failed:\n{proc.stderr[-4000:]}"
            )
        # the row is the LAST stdout line: jax/absl may chat above it
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        assert row["devices"] == k, (row["devices"], k)
        rows.append(row)
        print(
            f"devices={k}: warm {row['warm_s']:.3f}s, "
            f"chunk {row['chunk']}, {row['pct_of_peak']:.2f}% of peak",
            file=sys.stderr,
        )

    rows.sort(key=lambda r: r["devices"])
    base = rows[0]["warm_s"]
    for r in rows:
        r["speedup_vs_1"] = float(base / r["warm_s"])
    payload = {
        "schema_version": SCALING_SCHEMA_VERSION,
        "lanes": int(lanes),
        "steps": int(steps),
        "n_devices": int(n_devices),
        "dim": int(dim),
        "rows": rows,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return payload


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--child", action="store_true",
                        help="run ONE row at the current device count and "
                             "print it as JSON (internal)")
    parser.add_argument("--device-counts", type=int, nargs="*",
                        default=list(DEVICE_COUNTS))
    parser.add_argument("--lanes", type=int, default=DEFAULTS["lanes"])
    parser.add_argument("--steps", type=int, default=DEFAULTS["steps"])
    parser.add_argument("--n-devices", type=int, default=DEFAULTS["n_devices"])
    parser.add_argument("--dim", type=int, default=DEFAULTS["dim"])
    parser.add_argument("--out", default="benchmarks/out/BENCH_scaling.json")
    args = parser.parse_args(argv)

    if args.child:
        row = scaling_row(lanes=args.lanes, steps=args.steps,
                          n_devices=args.n_devices, dim=args.dim)
        print(json.dumps(row))
        return 0

    payload = scaling_curve(
        device_counts=tuple(args.device_counts), lanes=args.lanes,
        steps=args.steps, n_devices=args.n_devices, dim=args.dim,
        out_path=args.out,
    )
    for r in payload["rows"]:
        print(f"{r['devices']},{r['warm_s']:.4f},{r['speedup_vs_1']:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
