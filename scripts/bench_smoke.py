"""Tiny-shape smoke run of the benchmark drivers + BENCH_*.json schema
validation.

Benchmark code rots silently: it only runs when someone benchmarks.  This
script executes the kernel microbenches, a miniature grid-timing sweep, a
miniature device-sharded sweep (``shard="shard_map"``, chunked) and a
miniature sharded LM-engine sweep (transformer lanes) at toy shapes
(seconds, not minutes) and validates the machine-readable
``BENCH_kernels.json`` / ``BENCH_grid_sharded.json`` / ``BENCH_lm_engine.
json`` the real drivers emit, so a drifting bench driver or schema fails
tier-1 (tests/test_bench_smoke.py) instead of the next perf investigation.

Standalone:

    PYTHONPATH=src:. python scripts/bench_smoke.py
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO_ROOT, os.path.join(REPO_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def validate_kernel_json(payload: dict) -> None:
    """Assert the BENCH_kernels.json schema (see kernel_bench.SCHEMA_VERSION)."""
    from benchmarks.kernel_bench import SCHEMA_VERSION

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == SCHEMA_VERSION, payload.get("schema_version")
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    names = set()
    for row in rows:
        assert set(row) == {"name", "us_per_call", "derived"}, sorted(row)
        assert isinstance(row["name"], str) and row["name"], row
        assert isinstance(row["us_per_call"], float) and row["us_per_call"] > 0, row
        assert isinstance(row["derived"], float), row
        names.add(row["name"])
    assert len(names) == len(rows), "duplicate row names"


def smoke_kernel_bench() -> dict:
    """Run every kernel-bench family at tiny shapes and round-trip the JSON."""
    from benchmarks.kernel_bench import (
        aggregator_bench,
        compression_bench,
        kernel_vs_ref_bench,
        lane_batched_bench,
        write_kernel_json,
    )

    rows = []
    rows += aggregator_bench(n=8, q=512, iters=1, names=("mean", "cwtm", "tgn"))
    rows += compression_bench(q=2048, iters=1)
    rows += kernel_vs_ref_bench(n=8, q=512, iters=1)
    rows += lane_batched_bench(lanes=3, n=6, d=3, q=256, iters=1)
    lane_names = {r[0] for r in rows}
    for op in ("cwtm", "coded_combine", "quantize", "pairwise_sqdist"):
        assert f"{op}_lanes_batched" in lane_names, f"missing lane row for {op}"
        assert f"{op}_per_lane_loop" in lane_names, f"missing loop row for {op}"
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "BENCH_kernels.json")
        write_kernel_json(rows, path)
        with open(path) as f:
            payload = json.load(f)
    validate_kernel_json(payload)
    return payload


def validate_grid_sharded_json(payload: dict) -> None:
    """Assert the BENCH_grid_sharded.json schema (see
    paper_figures.GRID_SHARDED_SCHEMA_VERSION)."""
    from benchmarks.paper_figures import GRID_SHARDED_SCHEMA_VERSION

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == GRID_SHARDED_SCHEMA_VERSION, (
        payload.get("schema_version")
    )
    assert payload.get("shard") == "shard_map", payload.get("shard")
    for field in ("device_count", "lanes", "max_lanes_per_device", "steps",
                  "n_devices", "dim"):
        v = payload.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    names = set()
    for row in rows:
        assert set(row) == {"name", "lanes", "value"}, sorted(row)
        assert isinstance(row["name"], str) and row["name"], row
        assert isinstance(row["lanes"], int) and row["lanes"] >= 1, row
        assert isinstance(row["value"], float) and row["value"] > 0, row
        names.add(row["name"])
    assert len(names) == len(rows), "duplicate row names"
    for req in ("unsharded_warm", "sharded_warm", "sharded_chunked_warm",
                "speedup_warm_sharded_vs_unsharded"):
        assert any(n.endswith(req) for n in names), f"missing {req} row"


def smoke_grid_sharded() -> dict:
    """Run the device-sharded sweep bench (``shard="shard_map"``, chunked
    streaming) at tiny shapes — including its bitwise sharded-vs-unsharded
    and zero-compile-warm assertions — and round-trip + validate the JSON."""
    from benchmarks.paper_figures import grid_sharded

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "BENCH_grid_sharded.json")
        rows = grid_sharded(
            lanes=6, steps=3, n_devices=10, dim=12,
            max_lanes_per_device=2, out_path=path,
        )
        with open(path) as f:
            payload = json.load(f)
    assert len(rows) == 6, [r[0] for r in rows]
    validate_grid_sharded_json(payload)
    return payload


def validate_lm_engine_json(payload: dict) -> None:
    """Assert the BENCH_lm_engine.json schema (see
    paper_figures.LM_ENGINE_SCHEMA_VERSION)."""
    from benchmarks.paper_figures import LM_ENGINE_SCHEMA_VERSION

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == LM_ENGINE_SCHEMA_VERSION, (
        payload.get("schema_version")
    )
    assert payload.get("shard") == "shard_map", payload.get("shard")
    for field in ("device_count", "lanes", "max_lanes_per_device", "steps",
                  "n_devices", "per_subset", "seq_len", "params"):
        v = payload.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    arch = payload.get("arch")
    assert isinstance(arch, dict), type(arch)
    assert isinstance(arch.get("name"), str) and arch["name"], arch
    for field in ("n_layers", "d_model", "vocab"):
        v = arch.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    names = set()
    for row in rows:
        assert set(row) == {"name", "lanes", "value"}, sorted(row)
        assert isinstance(row["name"], str) and row["name"], row
        assert isinstance(row["lanes"], int) and row["lanes"] >= 1, row
        assert isinstance(row["value"], float) and row["value"] > 0, row
        names.add(row["name"])
    assert len(names) == len(rows), "duplicate row names"
    for req in ("unsharded_warm", "sharded_warm", "sharded_chunked_warm",
                "per_scenario_warm", "speedup_warm_sharded_vs_unsharded"):
        assert any(n.endswith(req) for n in names), f"missing {req} row"


def smoke_lm_engine() -> dict:
    """Run the sharded LM-engine sweep bench at tiny shapes — including its
    bitwise sharded-vs-unsharded, grid-vs-standalone and zero-compile-warm
    assertions — and round-trip + validate the JSON."""
    from benchmarks.paper_figures import lm_engine
    from repro.core import scenarios

    rows_scn = scenarios.lm_sweep(
        methods=(("lad", 2),), attacks=("sign_flip", "alie"),
        compressors=("none",),
    )
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "BENCH_lm_engine.json")
        rows = lm_engine(
            steps=2, max_lanes_per_device=1, per_subset=1, seq_len=8,
            out_path=path, rows_scn=rows_scn,
        )
        with open(path) as f:
            payload = json.load(f)
    assert len(rows) == 7, [r[0] for r in rows]
    validate_lm_engine_json(payload)
    return payload


def validate_participation_json(payload: dict) -> None:
    """Assert the BENCH_participation.json schema (see
    paper_figures.PARTICIPATION_SCHEMA_VERSION)."""
    from benchmarks.paper_figures import PARTICIPATION_SCHEMA_VERSION

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == PARTICIPATION_SCHEMA_VERSION, (
        payload.get("schema_version")
    )
    for field in ("device_count", "n_devices", "d", "steps", "dim"):
        v = payload.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    margin = payload.get("margin")
    assert margin == payload["d"] - 1, (margin, payload.get("d"))
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    names = set()
    aggs = set()
    for row in rows:
        assert set(row) == {"name", "erasures", "k_of_n", "aggregator",
                            "final_loss"}, sorted(row)
        assert isinstance(row["name"], str) and row["name"], row
        assert isinstance(row["erasures"], int) and 0 <= row["erasures"] <= margin, row
        assert row["k_of_n"] == payload["n_devices"] - row["erasures"], row
        assert isinstance(row["final_loss"], float) and row["final_loss"] > 0, row
        names.add(row["name"])
        aggs.add(row["aggregator"])
    assert len(names) == len(rows), "duplicate row names"
    assert aggs == {"decode", "mean"}, aggs
    assert {r["erasures"] for r in rows} == set(range(margin + 1)), rows
    timings = payload.get("timings")
    assert isinstance(timings, list) and timings, "timings must be non-empty"
    tnames = {t["name"] for t in timings}
    assert {"grid_cold", "grid_warm"} <= tnames, tnames
    for t in timings:
        assert set(t) == {"name", "seconds"}, sorted(t)
        assert isinstance(t["seconds"], float) and t["seconds"] > 0, t
    spread = payload.get("rel_spread")
    assert isinstance(spread, dict) and set(spread) == {"decode", "mean"}, spread
    # the recovery claim, schema-level: the decode curve is erasure-invariant
    assert 0.0 <= spread["decode"] <= 1e-4, spread


def smoke_participation() -> dict:
    """Run the K-of-N erasure sweep bench at tiny shapes — including its
    erasure-invariance assertion — and round-trip + validate the JSON."""
    from benchmarks.paper_figures import participation_bench

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "BENCH_participation.json")
        payload_out = participation_bench(
            steps=4, n_devices=8, d=2, dim=12, out_path=path,
        )
        with open(path) as f:
            payload = json.load(f)
    assert payload == json.loads(json.dumps(payload_out)), "round-trip drift"
    validate_participation_json(payload)
    return payload


def validate_scaling_json(payload: dict) -> None:
    """Assert the BENCH_scaling.json schema (see
    scaling_bench.SCALING_SCHEMA_VERSION)."""
    from benchmarks.scaling_bench import SCALING_SCHEMA_VERSION

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == SCALING_SCHEMA_VERSION, (
        payload.get("schema_version")
    )
    for field in ("lanes", "steps", "n_devices", "dim"):
        v = payload.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    int_fields = ("devices", "lanes", "steps", "chunk", "max_lanes_per_device")
    float_fields = ("cold_s", "warm_s", "lanes_per_s", "predicted_s",
                    "pct_of_peak", "speedup_vs_1")
    devices = []
    for row in rows:
        expect = set(int_fields) | set(float_fields) | {
            "platform", "auto", "dominant_term",
        }
        assert set(row) == expect, sorted(set(row) ^ expect)
        for f in int_fields:
            assert isinstance(row[f], int) and row[f] >= 1, (f, row[f])
        for f in float_fields:
            assert isinstance(row[f], float) and row[f] >= 0, (f, row[f])
        for f in ("warm_s", "cold_s", "lanes_per_s", "speedup_vs_1"):
            assert row[f] > 0, (f, row[f])
        assert isinstance(row["platform"], str) and row["platform"], row
        assert isinstance(row["auto"], bool), row
        assert row["dominant_term"] in ("compute", "memory", "collective"), row
        devices.append(row["devices"])
    assert devices == sorted(devices), f"rows not sorted by devices: {devices}"
    assert len(set(devices)) == len(devices), f"duplicate device counts: {devices}"


def smoke_scaling() -> dict:
    """One in-process scaling row at the current device count (the 1/2/4/8
    subprocess fan-out is the CI perf-gate job's work, not tier-1's) +
    schema validation of the committed BENCH_scaling.json baseline."""
    from benchmarks.scaling_bench import SCALING_SCHEMA_VERSION, scaling_row
    from repro.launch import tuner

    tuner.set_store_path(None)  # in-memory store: no disk probes cached
    try:
        row = scaling_row(lanes=6, steps=3, n_devices=8, dim=8)
    finally:
        tuner.reset_store()
    assert row["auto"] is True, row
    assert row["chunk"] >= 1 and row["warm_s"] > 0, row
    # wrap the single row as a 1-point curve and validate the shared schema
    payload = {
        "schema_version": SCALING_SCHEMA_VERSION,
        "lanes": 6, "steps": 3, "n_devices": 8, "dim": 8,
        "rows": [dict(row, speedup_vs_1=1.0)],
    }
    validate_scaling_json(payload)

    baseline = os.path.join(REPO_ROOT, "benchmarks", "out", "BENCH_scaling.json")
    with open(baseline) as f:
        committed = json.load(f)
    validate_scaling_json(committed)
    assert [r["devices"] for r in committed["rows"]] == [1, 2, 4, 8], (
        "committed BENCH_scaling.json must hold the 1/2/4/8-device curve"
    )
    return payload


def _validate_wire(wire: dict) -> None:
    """Assert the v2 nested wire schema: faults per WIRE_KEYS + per-kind
    [frames, bytes] sent/recv tallies."""
    from repro.launch.fleet import KIND_NAMES, WIRE_KEYS

    assert isinstance(wire, dict) and set(wire) == {"faults", "sent", "recv"}, wire
    assert set(wire["faults"]) == set(WIRE_KEYS), sorted(wire["faults"])
    assert all(isinstance(v, int) and v >= 0 for v in wire["faults"].values()), wire
    for d in (wire["sent"], wire["recv"]):
        assert set(d) == set(KIND_NAMES.values()), sorted(d)
        for frames, nbytes in d.values():
            assert isinstance(frames, int) and frames >= 0, d
            assert isinstance(nbytes, int) and nbytes >= 0, d
            assert (frames == 0) == (nbytes == 0), d


def validate_fleet_chaos_json(payload: dict) -> None:
    """Assert the BENCH_fleet_chaos.json schema AND the self-healing claims
    it records (see fleet_bench.FLEET_CHAOS_SCHEMA_VERSION)."""
    from benchmarks.fleet_bench import ENVELOPE_RTOL, FLEET_CHAOS_SCHEMA_VERSION

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == FLEET_CHAOS_SCHEMA_VERSION, (
        payload.get("schema_version")
    )
    for field in ("procs", "n_devices", "d", "dim", "steps"):
        v = payload.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    assert payload["procs"] >= 3, "chaos conformance needs >= 2 workers"
    assert payload["margin"] == payload["d"] - 1, payload.get("margin")
    assert isinstance(payload.get("round_timeout"), float), payload.get("round_timeout")
    base = payload.get("baseline_final_loss")
    assert isinstance(base, float) and base > 0, base
    # the pass-through claim: an empty chaos schedule was byte-identical
    assert payload.get("healthy_identical") is True, payload.get("healthy_identical")
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    names = set()
    for row in rows:
        assert set(row) == {"name", "final_loss", "rel_dev", "server_rc", "dead",
                            "rejoins", "wire", "n_report_min", "within_margin"}, (
            sorted(row)
        )
        assert isinstance(row["name"], str) and row["name"], row
        assert isinstance(row["final_loss"], float) and row["final_loss"] > 0, row
        assert isinstance(row["rel_dev"], float) and row["rel_dev"] >= 0, row
        # the unkillable-server claim: every schedule exited cleanly
        assert row["server_rc"] == 0, row
        assert isinstance(row["dead"], list), row
        assert isinstance(row["rejoins"], int) and row["rejoins"] >= 0, row
        _validate_wire(row["wire"])
        assert isinstance(row["n_report_min"], int) and row["n_report_min"] >= 1, row
        assert isinstance(row["within_margin"], bool), row
        # the recovery claim: within-margin faults stay inside the envelope
        if row["within_margin"]:
            assert row["rel_dev"] <= ENVELOPE_RTOL, row
        names.add(row["name"])
    assert len(names) == len(rows), "duplicate row names"
    for req in ("healthy", "corrupt", "partition_rejoin"):
        assert req in names, f"missing required chaos case {req!r}"


def smoke_fleet_chaos() -> dict:
    """Schema + claims validation of the committed BENCH_fleet_chaos.json
    baseline (the subprocess fan-out itself is the CI fleet-chaos job's
    work, not tier-1's — same split as smoke_scaling)."""
    baseline = os.path.join(REPO_ROOT, "benchmarks", "out",
                            "BENCH_fleet_chaos.json")
    with open(baseline) as f:
        committed = json.load(f)
    validate_fleet_chaos_json(committed)
    return committed


def validate_fleet_comlad_json(payload: dict) -> None:
    """Assert the BENCH_fleet_comlad.json schema AND the Com-LAD-over-the-
    wire claims it records (see fleet_bench.FLEET_COMLAD_SCHEMA_VERSION)."""
    from benchmarks.fleet_bench import ENVELOPE_RTOL, FLEET_COMLAD_SCHEMA_VERSION
    from repro.core.compression import CompressionSpec
    from repro.launch.fleet import WIRE_KEYS

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == FLEET_COMLAD_SCHEMA_VERSION, (
        payload.get("schema_version")
    )
    for field in ("procs", "n_devices", "d", "dim", "steps"):
        v = payload.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    assert payload["procs"] >= 3, "comlad conformance needs >= 2 workers"
    for field in ("lr", "round_timeout", "baseline_final_loss",
                  "baseline_uplink_bytes_per_round", "quant4_ratio"):
        v = payload.get(field)
        assert isinstance(v, float) and v > 0, (field, v)
    # the byte-identity claim: --compress identity matched the plain fleet
    assert payload.get("identity_identical") is True, payload.get("identity_identical")
    # the headline claim: quant:4 cuts measured uplink bytes/round >= 4x
    assert payload["quant4_ratio"] >= 4.0, payload["quant4_ratio"]
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    names = set()
    for row in rows:
        assert set(row) == {"name", "spec", "final_loss", "rel_dev",
                            "uplink_bytes_per_round", "uplink_frames",
                            "uplink_bytes", "ratio_vs_identity",
                            "frame_bytes_predicted", "frame_bytes_measured",
                            "wire_bits_predicted", "wire_bits_measured",
                            "server_rc", "faults", "within_envelope",
                            "min_ratio"}, sorted(row)
        assert isinstance(row["name"], str) and row["name"], row
        # every recorded spec parses under the one registry grammar
        try:
            canonical = CompressionSpec.parse(row["spec"]).canonical()
        except ValueError:
            canonical = None
        assert canonical == row["spec"], row
        assert isinstance(row["final_loss"], float) and row["final_loss"] > 0, row
        assert isinstance(row["rel_dev"], float) and row["rel_dev"] >= 0, row
        assert row["server_rc"] == 0, row
        for f in ("uplink_bytes_per_round", "ratio_vs_identity",
                  "frame_bytes_predicted", "frame_bytes_measured",
                  "wire_bits_predicted", "wire_bits_measured", "min_ratio"):
            assert isinstance(row[f], (int, float)) and row[f] >= 0, (f, row)
        for f in ("uplink_frames", "uplink_bytes"):
            assert isinstance(row[f], int) and row[f] >= 1, (f, row)
        assert isinstance(row["faults"], dict), row
        assert set(row["faults"]) == set(WIRE_KEYS), sorted(row["faults"])
        assert isinstance(row["within_envelope"], bool), row
        # the loss-vs-bytes frontier claims the bench enforced
        assert row["ratio_vs_identity"] >= row["min_ratio"], row
        if row["within_envelope"]:
            assert row["rel_dev"] <= ENVELOPE_RTOL, row
        names.add(row["name"])
    assert len(names) == len(rows), "duplicate row names"
    for req in ("identity", "quant4", "quant4_chaos_byz"):
        assert req in names, f"missing required comlad case {req!r}"
    # the chaos case: compressed-frame faults landed as tallied erasures
    byz = next(r for r in rows if r["name"] == "quant4_chaos_byz")
    assert sum(byz["faults"].values()) >= 1, byz["faults"]
    assert byz["faults"]["wrong_shape"] + byz["faults"]["bad_payload"] >= 1, (
        byz["faults"]
    )


def smoke_fleet_comlad() -> dict:
    """Schema + claims validation of the committed BENCH_fleet_comlad.json
    baseline (the subprocess fan-out itself is the CI fleet-chaos job's
    work, not tier-1's — same split as smoke_fleet_chaos)."""
    baseline = os.path.join(REPO_ROOT, "benchmarks", "out",
                            "BENCH_fleet_comlad.json")
    with open(baseline) as f:
        committed = json.load(f)
    validate_fleet_comlad_json(committed)
    return committed


def validate_zoo_serve_json(payload: dict) -> None:
    """Assert the BENCH_zoo_serve.json schema AND the train-to-serve claims
    it records (see paper_figures.ZOO_SERVE_SCHEMA_VERSION): per zoo family,
    the robust-under-attack checkpoint's eval-NLL delta stays within the
    recorded bound, the undefended delta exceeds it, the checkpoint
    round-trips bitwise into the serving path, and serving moved tokens."""
    import math

    from benchmarks.paper_figures import ZOO_SERVE_SCHEMA_VERSION

    assert isinstance(payload, dict), type(payload)
    assert payload.get("schema_version") == ZOO_SERVE_SCHEMA_VERSION, (
        payload.get("schema_version")
    )
    for field in ("device_count", "steps", "n_subsets", "per_subset",
                  "seq_len", "n_byz", "new_tokens"):
        v = payload.get(field)
        assert isinstance(v, int) and v >= 1, (field, v)
    for field in ("lr", "robust_delta_bound"):
        v = payload.get(field)
        assert isinstance(v, float) and v > 0, (field, v)
    assert isinstance(payload.get("attack"), str) and payload["attack"], payload
    bound = payload["robust_delta_bound"]
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, "rows must be a non-empty list"
    families = set()
    for row in rows:
        assert set(row) == {
            "family", "arch", "n_layers", "params", "nll_clean", "nll_robust",
            "nll_undefended", "robust_delta", "undefended_delta",
            "roundtrip_bitwise", "prefill_tokens_per_s", "decode_tokens_per_s",
            "decoded_tokens",
        }, sorted(row)
        assert isinstance(row["family"], str) and row["family"], row
        assert isinstance(row["arch"], str) and row["arch"], row
        for f in ("n_layers", "params", "decoded_tokens"):
            assert isinstance(row[f], int) and row[f] >= 1, (f, row)
        for f in ("nll_clean", "nll_robust", "nll_undefended",
                  "robust_delta", "undefended_delta"):
            assert isinstance(row[f], float) and math.isfinite(row[f]), (f, row)
        for f in ("nll_clean", "nll_robust", "nll_undefended"):
            assert row[f] > 0, (f, row)
        # the train-to-serve contract, row by row
        assert row["robust_delta"] <= bound, row
        assert row["undefended_delta"] > row["robust_delta"], row
        assert row["roundtrip_bitwise"] is True, row
        for f in ("prefill_tokens_per_s", "decode_tokens_per_s"):
            assert isinstance(row[f], float) and row[f] > 0, (f, row)
        assert row["decoded_tokens"] == payload["new_tokens"], row
        families.add(row["family"])
    assert len(families) == len(rows), "duplicate family rows"


def smoke_zoo_serve() -> dict:
    """Run the train-to-serve loop on two zoo families at tiny step counts —
    including its robust-delta, bitwise-roundtrip and serving assertions —
    then validate the committed full-matrix BENCH_zoo_serve.json baseline
    (>= 4 families; the full matrix itself is nightly work, not tier-1's)."""
    from benchmarks.paper_figures import zoo_serve

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "BENCH_zoo_serve.json")
        payload_out = zoo_serve(
            families=("transformer", "rwkv"), steps=8, out_path=path,
        )
        with open(path) as f:
            payload = json.load(f)
    assert payload == json.loads(json.dumps(payload_out)), "round-trip drift"
    validate_zoo_serve_json(payload)

    baseline = os.path.join(REPO_ROOT, "benchmarks", "out",
                            "BENCH_zoo_serve.json")
    with open(baseline) as f:
        committed = json.load(f)
    validate_zoo_serve_json(committed)
    assert len(committed["rows"]) >= 4, (
        "committed BENCH_zoo_serve.json must cover >= 4 zoo families"
    )
    return payload


def smoke_grid_timing() -> list:
    """Miniature whole-grid-vs-per-scenario timing (with its bitwise check),
    on both the XLA and the kernel backend."""
    from benchmarks.paper_figures import _timed_grid_rows
    from repro.core import scenarios

    tiny = [
        dataclasses.replace(s, n_devices=8, n_byz=2)
        for s in scenarios.section7_grid(
            methods=(("lad", 4),), attacks=("sign_flip",),
            compressors=("none",), lr=1e-5,
        )
    ]
    rows = _timed_grid_rows(tiny, steps=3, prefix="smoke_")
    kernel_tiny = [dataclasses.replace(s, backend="interpret") for s in tiny]
    rows += _timed_grid_rows(kernel_tiny, steps=3, prefix="smoke_kernel_")
    assert len(rows) == 16
    return rows


def main() -> int:
    payload = smoke_kernel_bench()
    print(f"kernel bench smoke: {len(payload['rows'])} rows, schema OK")
    rows = smoke_grid_timing()
    print(f"grid timing smoke: {len(rows)} rows, bitwise check OK")
    sharded = smoke_grid_sharded()
    print(
        f"grid sharded smoke: {len(sharded['rows'])} rows on "
        f"{sharded['device_count']} device(s), schema + bitwise OK"
    )
    lm = smoke_lm_engine()
    print(
        f"lm engine smoke: {len(lm['rows'])} rows, {lm['params']} params on "
        f"{lm['device_count']} device(s), schema + bitwise OK"
    )
    part = smoke_participation()
    print(
        f"participation smoke: {len(part['rows'])} rows (margin "
        f"{part['margin']}), schema + erasure-invariance OK"
    )
    scaling = smoke_scaling()
    print(
        f"scaling smoke: {len(scaling['rows'])} in-process row(s) + committed "
        f"baseline, schema OK"
    )
    chaos = smoke_fleet_chaos()
    print(
        f"fleet chaos smoke: {len(chaos['rows'])} committed cases, "
        f"healthy_identical={chaos['healthy_identical']}, schema + claims OK"
    )
    comlad = smoke_fleet_comlad()
    print(
        f"fleet comlad smoke: {len(comlad['rows'])} committed cases, "
        f"quant4_ratio={comlad['quant4_ratio']:.2f}x, schema + claims OK"
    )
    zoo = smoke_zoo_serve()
    print(
        f"zoo serve smoke: {len(zoo['rows'])} families trained-under-attack, "
        f"restored and served + committed baseline, schema + claims OK"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
