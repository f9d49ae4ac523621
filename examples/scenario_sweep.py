"""Sweep the Section-VII scenario matrix — whole-grid on-device.

One declarative registry call generates the paper's comparison grid —
method x attack x compressor (x aggregator x heterogeneity) — and the
*entire grid* runs as a handful of vmapped ``lax.scan`` programs (one per
compile bucket; the attack axis is a traced ``lax.switch``), with zero
per-scenario Python dispatch:

    PYTHONPATH=src python examples/scenario_sweep.py
    PYTHONPATH=src python examples/scenario_sweep.py --steps 400 \
        --attacks sign_flip alie ipm --backend interpret

``--backend interpret`` routes the server/device hot path through the Pallas
kernels (interpret mode on CPU; ``pallas`` compiles them on TPU) — kernel
backends ride the same vmapped one-program-per-bucket grid path as XLA: the
lane-batched kernels map the scenario axis onto their 2-D ``(lane, q_tile)``
grid (see ``kernels/ops.py``), bitwise-equal per lane to the standalone run.
``--per-scenario`` forces the PR-1 dispatch loop (the bit-exactness
reference; useful for timing the vmapped path against it).

``--shard shard_map`` partitions every compile bucket's scenario-lane axis
over the visible devices (pad-to-device-count semantics; see README "Engine
guarantees"), and ``--max-lanes-per-device`` streams large sweeps through
equal-shaped chunks of one compiled program:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python examples/scenario_sweep.py --shard shard_map --steps 100
"""
import argparse
import dataclasses
import time

import jax

from repro.core import scenarios
from repro.data.synthetic import linear_regression_problem


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument("--attacks", nargs="*", default=["sign_flip", "alie", "ipm"])
    parser.add_argument("--compressors", nargs="*", default=["none", "rand_sparse"])
    parser.add_argument("--sigma", type=float, nargs="*", default=[0.3])
    parser.add_argument("--backend", default="xla", choices=["xla", "interpret", "pallas"])
    parser.add_argument("--per-scenario", action="store_true",
                        help="run the PR-1 per-scenario dispatch loop instead "
                             "of the vmapped whole-grid engine")
    parser.add_argument("--shard", default="none",
                        choices=["none", "shard_map"],
                        help="partition each bucket's scenario-lane axis over "
                             "all visible devices")
    parser.add_argument("--max-lanes-per-device", default=None,
                        type=lambda v: v if v == "auto" else int(v),
                        help="stream the sweep in chunks of this many lanes "
                             "per device (memory-bounded 1000+-row sweeps), "
                             "or 'auto' to probe-tune the capacity per bucket "
                             "(cached across runs in the tuner store)")
    args = parser.parse_args()

    grid = scenarios.section7_grid(
        attacks=args.attacks, compressors=args.compressors, sigma_levels=args.sigma
    )
    grid = [dataclasses.replace(s, backend=args.backend) for s in grid]
    # one shared problem so final losses are comparable across the grid —
    # only when a single heterogeneity level is swept; with several sigmas
    # each scenario must generate its own sigma_h-matched problem
    problem = None
    if len(args.sigma) == 1:
        problem = linear_regression_problem(jax.random.PRNGKey(0), n=100, dim=100,
                                            sigma_h=args.sigma[0])

    mode = "scan" if args.per_scenario else "grid"
    print(f"{len(grid)} scenarios x {args.steps} rounds "
          f"(backend={args.backend}, mode={mode}, shard={args.shard}, "
          f"{jax.device_count()} device(s))\n")
    print(f"{'scenario':44s} {'final loss':>12s} {'agg dist':>10s}")
    t0 = time.perf_counter()
    results = scenarios.grid_finals(
        scenarios.run_grid(grid, args.steps, problem=problem, mode=mode,
                           shard=args.shard,
                           max_lanes_per_device=args.max_lanes_per_device)
    )
    elapsed = time.perf_counter() - t0
    for name, m in results.items():
        print(f"{name:44s} {m['final_loss']:12.4g} {m['final_agg_dist']:10.4g}")
    print(f"\nswept {len(grid)} scenarios in {elapsed:.2f}s ({mode})")

    # the paper's headline: under every attack, LAD improves on the plain
    # robust baseline at the same aggregator (redundancy tightens the error)
    for attack in args.attacks:
        for comp in args.compressors:
            for sigma in args.sigma:
                lad = results.get(scenarios.scenario_name("lad", 10, "cwtm", attack, comp, sigma))
                plain = results.get(scenarios.scenario_name("plain", 1, "cwtm", attack, comp, sigma))
                if lad and plain:
                    verdict = "OK " if lad["final_loss"] <= plain["final_loss"] else "?? "
                    print(f"{verdict} lad-d10 vs plain under {attack}/{comp}/s{sigma:g}: "
                          f"{lad['final_loss']:.4g} vs {plain['final_loss']:.4g}")


if __name__ == "__main__":
    main()
