"""The readings a cell's correctness limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1-12 --control-seeds 13-15 \\
        [--faults half_batch,no_exchange,frozen]

For every seed of ``--seeds``: the program's checked steps (``Trainer`` built
and driven exactly as a benchmark run does, without the window) against the
float32 reference, both of the configuration's ``model_type``.  For every
seed of ``--control-seeds``: the control, the reference computed with float8
products, against the float32 reference; and each planted fault of
``--faults`` (``harness/reference.py``) likewise.  One JSON line per reading
on stdout, with each leaf's gaps and whether the reading passes the cell's
limits (``compare.checks`` and ``compare.passed``, as a benchmark run applies
them), then a summary line: the largest program reading and the smallest
control and fault readings of each number, and which kinds of reading
passed.  The benchmark's own runs never run this; it needs the cell's chips.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _seeds(text: str) -> list[int]:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=_seeds, default=[])
    parser.add_argument("--control-seeds", type=_seeds, default=[])
    parser.add_argument("--faults", default="")
    parser.add_argument("--root", type=pathlib.Path, default=BENCH.parent)
    parser.add_argument("--any-device", action="store_true",
                        help="run on whatever JAX finds (CPU rehearsal at a tiny size)")
    args = parser.parse_args(argv)

    import jax

    from harness import cell as cell_lib
    from harness import compare, device, manifest, reference, traffic

    cell = manifest.load_cell(args.workload, args.root)
    model = manifest.load_model(cell.config["model_type"], args.root)
    cell_lib.enable_cache(args.root)
    if not args.any_device:
        device.require_tpu(jax.devices(), cell.chips)
    rows = []

    def emit(kind, seed, prog, ref):
        values = compare.readings(prog, ref, model.reference.APART)
        row = {"kind": kind, "seed": seed, **values,
               "passed": compare.passed(compare.checks(values, cell.limits))}
        rows.append(row)
        # each leaf's gaps, for a look at which leaf sets a worst-leaf number
        look = {"grad": compare.leaf_gaps(prog["first_grad"], ref["first_grad"],
                                          ref["first_grad"]),
                "change": compare.leaf_gaps(prog["change"], ref["change"],
                                            compare.moving_leaves(ref)),
                "losses": [prog["losses"], ref["losses"]]}
        print(json.dumps(dict(row, leaves=look)), flush=True)

    def ref_run(seed, pool, **kw):
        return reference.run(model, seed, cell.config, cell.traffic,
                             pool[:cell_lib.CHECKED_STEPS], cell_lib.CHECKED_STEPS, **kw)

    for seed in args.seeds:
        tr, pool, prog = cell_lib.checked_steps(cell, model, args.workload, seed, T_START)
        del tr
        gc.collect()
        emit("program", seed, prog, ref_run(seed, pool))
    faults = [f for f in args.faults.split(",") if f]
    for seed in args.control_seeds:
        pool = traffic.batch_pool(seed, cell.config["vocab_size"], cell.traffic)
        ref = ref_run(seed, pool)
        emit("control_fp8", seed, ref_run(seed, pool, mode="fp8"), ref)
        for fault in faults:
            emit(f"fault_{fault}", seed, ref_run(seed, pool, fault=fault), ref)

    summary = {"summary": args.workload}
    for kind in sorted({r["kind"] for r in rows}):
        pick = max if kind == "program" else min
        mine = [r for r in rows if r["kind"] == kind]
        summary[kind] = {k: pick(r[k] for r in mine) for k in compare.NUMBERS}
        summary[kind]["passed"] = f"{sum(r['passed'] for r in mine)} of {len(mine)}"
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
