"""Chaos + Com-LAD conformance bench for the self-healing fleet.

Two suites over the real multi-process fleet (``python -m repro.launch.fleet``,
3 OS processes per case), both driven by :class:`repro.launch.fleet.FleetConfig`
objects (the subprocess argv is ``cfg.to_argv()`` — nothing is hand-synthesized):

**chaos** (``scenarios.fleet_chaos_cases``): every seeded fault schedule —
duplicate frames, corrupted frames, dropped frames, delays, a
partition-then-rejoin — plus a no-chaos baseline, asserting the self-healing
contract on each:

  * the server process exits 0 under every schedule (unkillable by payload);
  * the ``healthy`` (empty) chaos schedule produces a RESULT line
    **byte-identical** to the plain fleet (the chaos layer is a true
    pass-through);
  * every within-margin case's final loss stays inside the erasure-decode
    envelope (``rel_dev <= ENVELOPE_RTOL`` vs the baseline): per-round
    erasures up to ``erasure_margin(d)`` are *recovered*, not averaged
    around, so faults within the margin cannot move the trajectory beyond
    decode-order float noise.

**comlad** (``scenarios.fleet_comlad_cases``): one case per uplink
``CompressionSpec`` at the comlad geometry (dim=64 so payloads dominate frame
overhead), measuring the loss-vs-bytes frontier from *observed* traffic
(``RESULT["wire"]["recv"]``), and asserting:

  * ``--compress identity`` RESULT is byte-identical to the plain fleet
    (the dense ROWS wire path is untouched);
  * ``quant:4`` cuts measured uplink bytes/round by >= 4x vs identity while
    the final loss stays inside the erasure-decode envelope;
  * measured frame bytes == schema-predicted frame bytes for the
    deterministic codecs (identity / quant);
  * chaos ``byz_payload`` + ``corrupt`` faults against compressed frames
    land as tallied per-round erasures (codec-level validation, not just
    CRC), server still exits 0.

The fleet is a CPU simulation: every child runs with ``JAX_PLATFORMS=cpu``,
so on a host with a TPU none of them takes the chip (one process per chip).

Machine-readable results: ``benchmarks/out/BENCH_fleet_chaos.json`` and
``benchmarks/out/BENCH_fleet_comlad.json`` (validated in tier-1 by
``scripts/bench_smoke.py``; regenerated + uploaded by the CI ``fleet-chaos``
job every push).

Standalone:

    PYTHONPATH=src:. python benchmarks/fleet_bench.py [--suite chaos|comlad|all]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO_ROOT, os.path.join(REPO_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

FLEET_CHAOS_SCHEMA_VERSION = 2  # v2: wire = {faults, sent, recv}
FLEET_COMLAD_SCHEMA_VERSION = 1

# the recovery envelope: within-margin erasures are decoded exactly in real
# arithmetic; the decode's offset-class selection reorders a handful of f32
# adds, so the observed deviation is float noise (measured ~5e-7 at the
# bench geometry) — 1e-3 is the claim "recovered, not degraded".  The comlad
# suite reuses it as the unbiased-compression envelope at its lr.
ENVELOPE_RTOL = 1e-3

DEFAULTS = dict(procs=3, n_devices=6, d=3, dim=8, steps=8,
                lr=1e-5, seed=0, round_timeout=2.5)
# comlad geometry: dim=64 so the payload dominates the ~30 B frame overhead
# (at dim=8 the overhead caps any measured ratio near 2x regardless of codec),
# lr=1e-6 so quant:4's unbiased rounding noise stays inside ENVELOPE_RTOL
COMLAD_DEFAULTS = dict(procs=3, n_devices=6, d=3, dim=64, steps=8,
                       lr=1e-6, seed=0, round_timeout=2.5)


def _base_config(overrides: dict):
    from repro.launch.fleet import FleetConfig

    return FleetConfig(distributed=False, **overrides)


def _run_fleet(cfg, *, chaos: dict | None = None, extra_argv: list[str] = (),
               timeout_s: float = 300.0):
    """One fleet run from a FleetConfig; returns (server RESULT, line, rcs)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # a CPU fleet: no process may take the chip
    children = []
    for pid in range(cfg.procs):
        c = dataclasses.replace(cfg, proc_id=pid)
        if pid:
            c = dataclasses.replace(c, rejoin_timeout=30.0)
            if chaos is not None:
                c = dataclasses.replace(c, chaos=json.dumps(chaos, sort_keys=True))
        children.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.fleet", *c.to_argv(), *extra_argv],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = [c.communicate(timeout=timeout_s) for c in children]
    rcs = [c.returncode for c in children]
    server_out, server_err = outs[0]
    lines = [l for l in server_out.splitlines() if l.startswith("RESULT::")]
    assert lines, (rcs, server_err[-3000:])
    return json.loads(lines[0][len("RESULT::"):]), lines[0], rcs


def fleet_chaos_bench(
    *,
    port_base: int = 57520,
    cases: list[dict] | None = None,
    out_path: str = os.path.join(REPO_ROOT, "benchmarks", "out",
                                 "BENCH_fleet_chaos.json"),
    **overrides,
) -> dict:
    from repro.core import scenarios
    from repro.core.coding import erasure_margin

    geo = {**DEFAULTS, **overrides}
    cfg = _base_config(geo)
    if cases is None:
        cases = scenarios.fleet_chaos_cases(cfg.procs, steps=cfg.steps)

    plain, plain_line, plain_rcs = _run_fleet(
        dataclasses.replace(cfg, port=port_base))
    assert plain_rcs[0] == 0, plain_rcs
    baseline_final = plain["final_loss"]

    rows = []
    healthy_identical = False
    for i, case in enumerate(cases):
        res, line, rcs = _run_fleet(
            dataclasses.replace(cfg, port=port_base + 1 + i), chaos=case["chaos"])
        assert rcs[0] == 0, (case["name"], rcs)  # the server never crashes
        rel_dev = abs(res["final_loss"] - baseline_final) / abs(baseline_final)
        if case["name"] == "healthy":
            healthy_identical = line == plain_line
            assert healthy_identical, "empty chaos schedule is not a pass-through"
        if case["within_margin"]:
            assert res["stats"]["max_erasures"] <= res["stats"]["margin"], res["stats"]
            assert rel_dev <= ENVELOPE_RTOL, (case["name"], rel_dev)
        rows.append({
            "name": case["name"],
            "final_loss": res["final_loss"],
            "rel_dev": rel_dev,
            "server_rc": rcs[0],
            "dead": res["dead"],
            "rejoins": res["rejoins"],
            "wire": res["wire"],
            "n_report_min": min(res["n_report"]),
            "within_margin": case["within_margin"],
        })
        faults = {k: v for k, v in res["wire"]["faults"].items() if v}
        print(f"fleet chaos [{case['name']}]: final={res['final_loss']:.6g} "
              f"rel_dev={rel_dev:.2e} rejoins={res['rejoins']} faults={faults}")

    payload = {
        "schema_version": FLEET_CHAOS_SCHEMA_VERSION,
        "procs": cfg.procs,
        "n_devices": cfg.n_devices,
        "d": cfg.d,
        "margin": int(erasure_margin(cfg.d)),
        "dim": cfg.dim,
        "steps": cfg.steps,
        "round_timeout": cfg.round_timeout,
        "baseline_final_loss": baseline_final,
        "healthy_identical": healthy_identical,
        "rows": rows,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path} ({len(rows)} chaos cases, "
          f"healthy_identical={healthy_identical})")
    return payload


def fleet_comlad_bench(
    *,
    port_base: int = 57560,
    cases: list[dict] | None = None,
    out_path: str = os.path.join(REPO_ROOT, "benchmarks", "out",
                                 "BENCH_fleet_comlad.json"),
    **overrides,
) -> dict:
    from repro.core import scenarios

    geo = {**COMLAD_DEFAULTS, **overrides}
    cfg = _base_config(geo)
    if cases is None:
        cases = scenarios.fleet_comlad_cases(cfg.procs, steps=cfg.steps)

    # plain fleet (no --compress flag at all): the identity byte-identity ref
    plain, plain_line, plain_rcs = _run_fleet(
        dataclasses.replace(cfg, port=port_base))
    assert plain_rcs[0] == 0, plain_rcs
    baseline_final = plain["final_loss"]
    baseline_bpr = plain["comlad"]["uplink_bytes_per_round"]

    rows = []
    identity_identical = False
    for i, case in enumerate(cases):
        res, line, rcs = _run_fleet(
            dataclasses.replace(cfg, port=port_base + 1 + i),
            chaos=case["chaos"],
            # always pass the flag explicitly so the CLI path is exercised
            # even for the default spec
            extra_argv=["--compress", case["compress"]],
        )
        assert rcs[0] == 0, (case["name"], rcs)  # the server never crashes
        com = res["comlad"]
        rel_dev = abs(res["final_loss"] - baseline_final) / abs(baseline_final)
        ratio = (baseline_bpr / com["uplink_bytes_per_round"]
                 if com["uplink_bytes_per_round"] else 0.0)
        if case["name"] == "identity":
            identity_identical = line == plain_line
            assert identity_identical, "--compress identity is not a pass-through"
        if case["chaos"] is None:
            # clean runs: observed traffic must equal the schema's prediction
            assert com["uplink_frames"] == (cfg.procs - 1) * cfg.steps, com
            if com["spec"].startswith(("identity", "quant")):
                assert com["frame_bytes_measured"] == com["frame_bytes_predicted"], com
            assert ratio >= case["min_ratio"], (case["name"], ratio)
        else:
            # compressed frames under byz_payload/corrupt chaos: the faults
            # must land as tallied erasures (codec validation, not a crash)
            faults = res["wire"]["faults"]
            n_injected = sum(len(f["rounds"]) for f in case["chaos"]["faults"])
            assert sum(faults.values()) >= n_injected, (faults, n_injected)
            # byz_payload re-seals the CRC, so at least one rejection must
            # come from codec-level structural validation
            assert faults["wrong_shape"] + faults["bad_payload"] >= 1, faults
            assert min(res["n_report"]) < cfg.n_devices, res["n_report"]
        if case["within_envelope"]:
            assert rel_dev <= ENVELOPE_RTOL, (case["name"], rel_dev)
        rows.append({
            "name": case["name"],
            "spec": com["spec"],
            "final_loss": res["final_loss"],
            "rel_dev": rel_dev,
            "uplink_bytes_per_round": com["uplink_bytes_per_round"],
            "uplink_frames": com["uplink_frames"],
            "uplink_bytes": com["uplink_bytes"],
            "ratio_vs_identity": ratio,
            "frame_bytes_predicted": com["frame_bytes_predicted"],
            "frame_bytes_measured": com["frame_bytes_measured"],
            "wire_bits_predicted": com["wire_bits_predicted"],
            "wire_bits_measured": com["wire_bits_measured"],
            "server_rc": rcs[0],
            "faults": res["wire"]["faults"],
            "within_envelope": case["within_envelope"],
            "min_ratio": case["min_ratio"],
        })
        print(f"fleet comlad [{case['name']}]: spec={com['spec']} "
              f"bytes/round={com['uplink_bytes_per_round']:.0f} "
              f"ratio={ratio:.2f}x rel_dev={rel_dev:.2e}")

    quant4 = next(r for r in rows if r["name"] == "quant4")
    payload = {
        "schema_version": FLEET_COMLAD_SCHEMA_VERSION,
        "procs": cfg.procs,
        "n_devices": cfg.n_devices,
        "d": cfg.d,
        "dim": cfg.dim,
        "steps": cfg.steps,
        "lr": cfg.lr,
        "round_timeout": cfg.round_timeout,
        "baseline_final_loss": baseline_final,
        "baseline_uplink_bytes_per_round": baseline_bpr,
        "identity_identical": identity_identical,
        "quant4_ratio": quant4["ratio_vs_identity"],
        "rows": rows,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path} ({len(rows)} comlad cases, "
          f"quant4_ratio={quant4['ratio_vs_identity']:.2f}x, "
          f"identity_identical={identity_identical})")
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--suite", choices=("chaos", "comlad", "all"), default="all")
    ap.add_argument("--out", default=os.path.join(REPO_ROOT, "benchmarks",
                                                  "out", "BENCH_fleet_chaos.json"),
                    help="chaos-suite output path")
    ap.add_argument("--out-comlad",
                    default=os.path.join(REPO_ROOT, "benchmarks", "out",
                                         "BENCH_fleet_comlad.json"),
                    help="comlad-suite output path")
    ap.add_argument("--steps", type=int, default=DEFAULTS["steps"])
    ap.add_argument("--round-timeout", type=float, default=DEFAULTS["round_timeout"])
    ap.add_argument("--port-base", type=int, default=57520)
    args = ap.parse_args(argv)
    if args.suite in ("chaos", "all"):
        fleet_chaos_bench(steps=args.steps, round_timeout=args.round_timeout,
                          port_base=args.port_base, out_path=args.out)
    if args.suite in ("comlad", "all"):
        fleet_comlad_bench(steps=args.steps, round_timeout=args.round_timeout,
                           port_base=args.port_base + 40, out_path=args.out_comlad)
    return 0


if __name__ == "__main__":
    sys.exit(main())
