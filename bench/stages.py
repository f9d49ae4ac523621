"""Run one cell traced and split its trace by the program's stages and steps.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s> \\
        [--out DIR] [--fixture-steps K] [--xplane]

Runs the cell as ``bench/run.py --trace 1`` does (``harness/cell.py``, set-up,
window, check and readers unchanged) and prints its result line.  The events
that run read from its ``.xplane.pb`` (``harness/stages.py``) are kept, and
one more JSON line follows: per step and averaged over the chips, each stage's
device self time, the op time under no stage inside the round program, the
host time in each ``lad.*`` span, the traced rate, the first chip's longest
idle gaps named by ``lad.*`` span, and on more than one chip whether any op
runs inside the gradient stack's all-gather.  With ``--out`` the window's
events (with scopes) are written there, those of its first ``K`` steps too
with ``--fixture-steps`` (a test fixture), and the raw profile with
``--xplane``.  It needs the cell's chips; the benchmark's own runs never run
it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from unittest import mock  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import stages  # noqa: E402

ROUND = r"jit_round_"
COLLECTIVE = ("%async-collective-start", "%async-collective-done")


def collective_overlap(t, device: str) -> tuple[float, float]:
    """Seconds from each stack all-gather's start op to its done op, and the
    seconds of other ops inside those intervals, on ``device``."""
    ops = sorted((s, e, x.name) for x, s, e in t._of(device, "op"))
    starts = [s for s, _, n in ops if n.startswith(COLLECTIVE[0])]
    dones = [e for _, e, n in ops if n.startswith(COLLECTIVE[1])]
    spans = list(zip(starts, dones))
    others = [o for o in ops if not o[2].startswith(COLLECTIVE)]
    inside = sum(hi - lo for lo, hi, _ in stages._inside(others, spans))
    return sum(hi - lo for lo, hi in spans), inside


def summary(t, steps: int, tokens_per_step: int) -> dict:
    per_step = {s: 1e3 * t.mean_scope_s(s) / steps for s in stages.STAGES}
    round_ops = sum(t.mean_scope_s(s, ROUND) for s in (None, *stages.STAGES))
    out = {
        "steps": steps,
        "stage_ms": {k: v for k, v in per_step.items() if v > 0},
        "unscoped_ms": 1e3 * t.mean_scope_s(None) / steps,
        "round_unscoped_share": 100.0 * t.mean_scope_s(None, ROUND) / max(round_ops, 1e-12),
        "busy_ms": 1e3 * t.mean_busy_s() / steps,
        "span_ms": {s: 1e3 * t.span_s(s) / steps for s in stages.SPANS},
        "traced_tokens_per_s": steps * tokens_per_step / t.window_s,
        "idle_gaps": [t.idle_gaps(d, 10) for d in list(t.devices)[:1]],
    }
    if len(t.devices) > 1:
        out["allgather_overlap"] = {d: collective_overlap(t, d) for d in t.devices}
    return out


def run(workload: str, seed: int, seconds: float, *, xplane_to=None, **kw):
    """``cell.run`` of a traced window, and its profile as ``stages.load``
    reads it: ``(result, events)``.  ``kw`` goes to ``cell.run``."""
    from harness import cell

    kept = {}

    def keep(path):  # read the profile before cell.run deletes it
        if xplane_to is not None:
            shutil.copy(path, xplane_to)
        kept["events"] = stages.load(path)
        return kept["events"]

    # cell.run sees the stages module with this one function replaced
    with mock.patch.object(cell, "stages", types.SimpleNamespace(
            **{**vars(stages), "load": keep})):
        result = cell.run(workload, seed, seconds, True, **kw)
    return result, kept["events"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--out", type=pathlib.Path)
    parser.add_argument("--fixture-steps", type=int)
    parser.add_argument("--xplane", action="store_true")
    args = parser.parse_args(argv)

    from harness import cell, traffic

    if args.out:
        args.out.mkdir(parents=True, exist_ok=True)
    result, events = run(args.workload, args.seed, args.seconds, t_start=T_START,
                         xplane_to=args.out / "window.xplane.pb" if args.xplane else None)
    cell.emit(result)
    if args.out:
        stages.save_events(events, args.out / "events.json.gz")
        if args.fixture_steps:
            stages.save_events(stages.cut(events, args.fixture_steps),
                               args.out / f"first{args.fixture_steps}.events.json.gz")
    mix = cell.manifest.load_cell(args.workload).traffic
    t = stages.StageTrace(events)
    print(json.dumps({"stages": summary(t, result["attempted"], traffic.tokens_per_step(mix))}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
