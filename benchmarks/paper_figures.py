"""Reproduction of the paper's experimental section (Figs. 2-6).

One function per figure; each returns rows of (curve label, x, value) and is
asserted against the paper's qualitative claims.  The linear-regression setup
follows Section VII exactly: N=100 subsets of one sample each,
z_k ~ N(0, 100 I_100), per-subset ground truth with variance 1 + k*sigma_H,
sign-flipping attack with coefficient -2.

Every experimental curve comes from the declarative scenario registry
(``repro.core.scenarios.PAPER_FIG4/5/6``) executed through the vmapped grid
engine: each compile bucket of a registry runs as ONE on-device program
(``scenarios.run_grid``), instead of the per-scenario dispatch loop this
file used to hand-wire.  ``grid_timing`` records the wall-clock of the
whole-grid path against that per-scenario loop.

Scale notes: iteration counts are reduced (CPU, one core) but all protocol
parameters (N=100, H, d values, learning rates, trim fraction, Q_hat) match
the paper.
"""
from __future__ import annotations

import jax

from repro.core import scenarios, theory
from repro.data.synthetic import linear_regression_problem

N = 100
DIM = 100
RECORD_EVERY = 10


def _curves(registry, steps, problem, seed=0):
    """Run every scenario of a registry dict on a shared problem — the whole
    registry goes through the vmapped grid engine (one compiled program per
    compile bucket, bit-identical to per-scenario ``run_scenario``)."""
    results = scenarios.run_grid(registry.values(), steps, seed=seed, problem=problem)
    return {label: results[label].curve(every=RECORD_EVERY) for label in registry}


def _rows(curves):
    rows = []
    for label, curve in curves.items():
        rows += [(label, i, v) for i, v in curve]
    return rows


def fig2_error_vs_delta():
    """Error term (eq. 33) as a function of the compression constant delta.

    Paper setting: N=100, H=65, kappa=1.5, beta=1, d=5."""
    rows = []
    for delta in [0.0, 0.25, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0]:
        p = theory.TheoryParams(n=100, h=65, d=5, kappa=1.5, beta=1.0, delta=delta)
        rows.append(("com-lad-error", delta, theory.com_lad_error_order(p)))
    vals = [v for _, _, v in rows]
    assert all(a <= b + 1e-12 for a, b in zip(vals, vals[1:])), "error must grow with delta"
    return rows


def fig3_error_vs_d():
    """Error term as a function of the computational load d.

    Paper setting: N=100, H=65, kappa=1.5, beta=1, delta=0.5."""
    rows = []
    for d in [1, 2, 3, 5, 10, 20, 41, 60, 80, 100]:
        p = theory.TheoryParams(n=100, h=65, d=d, kappa=1.5, beta=1.0, delta=0.5)
        rows.append(("com-lad-error", d, theory.com_lad_error_order(p)))
    vals = [v for _, _, v in rows]
    assert all(a >= b - 1e-12 for a, b in zip(vals, vals[1:])), "error must shrink with d"
    return rows


def fig4_training_loss(steps: int = 800, sigma_h: float = 0.3):
    """Training loss vs iterations: VA / CWTM / CWTM-NNM / DRACO /
    LAD-CWTM(-NNM) at d in {5, 10, 20}.  H=80, sign-flip coeff -2."""
    problem = linear_regression_problem(jax.random.PRNGKey(0), n=N, dim=DIM, sigma_h=sigma_h)
    curves = _curves(scenarios.PAPER_FIG4, steps, problem)
    final = {k: v[-1][1] for k, v in curves.items()}
    # the paper's ordering claims (Fig. 4): redundancy helps per aggregator,
    # more d helps, NNM helps on top of LAD, DRACO (exact recovery) is best,
    # and LAD beats vanilla averaging.
    assert final["LAD-CWTM-d5"] < final["CWTM"], final
    assert final["LAD-CWTM-d20"] <= final["LAD-CWTM-d5"] * 1.05, final
    assert final["LAD-CWTM-NNM-d10"] < final["LAD-CWTM-d10"], final
    assert final["DRACO-d41"] < min(final["LAD-CWTM-d20"], final["CWTM"]), final
    assert final["VA"] > final["LAD-CWTM-d10"], final
    # NOTE (EXPERIMENTS.md §Paper-validation): plain CWTM-NNM at d=1 can
    # underperform CWTM at this heterogeneity/horizon — NNM's mixing pulls
    # in-spread byzantine vectors into the average when the honest spread is
    # large; redundancy (LAD) shrinks the spread and restores NNM's gain,
    # which is exactly the paper's motivation for combining them.
    return _rows(curves)


def fig5_heterogeneity(steps: int = 600):
    """sigma_H in {0, 0.1}: the LAD advantage grows with heterogeneity."""
    rows = []
    finals = {}
    for sigma in [0.0, 0.1]:
        problem = linear_regression_problem(jax.random.PRNGKey(1), n=N, dim=DIM, sigma_h=sigma)
        registry = {
            label: scn
            for label, scn in scenarios.PAPER_FIG5.items()
            if scn.sigma_h == sigma
        }
        curves = _curves(registry, steps, problem)
        rows += _rows(curves)
        finals.update({k: v[-1][1] for k, v in curves.items()})
    gaps = {s: finals[f"CWTM-s{s:g}"] - finals[f"LAD-CWTM-d10-s{s:g}"] for s in (0.0, 0.1)}
    assert gaps[0.1] > 0, gaps
    return rows


def fig6_compressed(steps: int = 700):
    """Compressed-communication setting: Com-VA / Com-CWTM(-NNM) / Com-TGN /
    Com-LAD-CWTM(-NNM); random sparsification Q_hat=30, H=70, d=3."""
    problem = linear_regression_problem(jax.random.PRNGKey(2), n=N, dim=DIM, sigma_h=0.3)
    curves = _curves(scenarios.PAPER_FIG6, steps, problem)
    final = {k: v[-1][1] for k, v in curves.items()}
    # paper claims: encoding-before-compression (Com-LAD) beats the same rule
    # without redundancy, and Com-LAD-CWTM-NNM clearly outperforms Com-TGN
    # (indeed every baseline).  NOTE: Com-VA is not asserted below Com-CWTM —
    # with 30% sign-flip(-2) Byzantine the mean retains a +0.1x gradient
    # component while an under-trimmed CWTM (paper's 0.1 trim vs 30% byz)
    # carries surviving outliers; see EXPERIMENTS.md §Paper-validation.
    assert final["Com-LAD-CWTM"] < final["Com-CWTM"], final
    assert final["Com-LAD-CWTM-NNM"] < final["Com-CWTM-NNM"], final
    assert final["Com-LAD-CWTM-NNM"] < final["Com-TGN"], final
    assert final["Com-LAD-CWTM-NNM"] == min(final.values()), final
    return _rows(curves)


def section7_sweep(steps: int = 200):
    """The full Section-VII comparison matrix (>= 3 methods x >= 3 attacks x
    >= 2 compressors), vmapped: one compiled program per compile bucket."""
    grid = scenarios.section7_grid()
    finals = scenarios.grid_finals(scenarios.run_grid(grid, steps))
    assert len(finals) == len(grid)
    return [("grid", name, m["final_loss"]) for name, m in finals.items()]


def _timed_grid_rows(grid, steps, prefix):
    """cold/warm grid-vs-per-scenario wall clock + bitwise-equality check.

    Three regimes: the vmapped whole-grid path, today's per-scenario scan
    (which since PR 3 hits the cached trajectory programs on warm calls),
    and ``per_scenario_uncached`` — the program caches cleared before every
    sweep, reproducing the pre-cache fallback that re-traced and re-compiled
    every scenario each call (the path kernel backends used to be forced
    onto).
    """
    import time

    import numpy as np

    from repro.core import engine

    def timed(mode, clear_caches=False):
        if clear_caches:
            engine.clear_program_caches()
        t0 = time.perf_counter()
        results = scenarios.run_grid(grid, steps, mode=mode)
        jax.block_until_ready([r.x for r in results.values()])
        return time.perf_counter() - t0, results

    t_grid_cold, res_grid = timed("grid")
    t_grid_warm, _ = timed("grid")
    t_loop_cold, res_loop = timed("scan")
    t_loop_warm, _ = timed("scan")
    t_uncached, _ = timed("scan", clear_caches=True)
    # the two paths must agree bitwise — the timing compares equal work
    for name in res_loop:
        assert np.array_equal(
            np.asarray(res_grid[name].x), np.asarray(res_loop[name].x)
        ), f"{prefix}: grid != per-scenario for {name}"
    return [
        (f"{prefix}grid_vmapped_cold", len(grid), t_grid_cold),
        (f"{prefix}grid_vmapped_warm", len(grid), t_grid_warm),
        (f"{prefix}per_scenario_cold", len(grid), t_loop_cold),
        (f"{prefix}per_scenario_warm", len(grid), t_loop_warm),
        (f"{prefix}per_scenario_uncached", len(grid), t_uncached),
        (f"{prefix}speedup_cold", len(grid), t_loop_cold / t_grid_cold),
        (f"{prefix}speedup_warm", len(grid), t_loop_warm / t_grid_warm),
        (f"{prefix}speedup_warm_vs_uncached", len(grid), t_uncached / t_grid_warm),
    ]


def _timed_sharded_rows(
    runner, n_rows, prefix, *, shard="shard_map", max_lanes_per_device=None,
):
    """Sharded-vs-unsharded grid wall clock + bitwise-equality check.

    ``runner(**kw) -> {name: TrajectoryResult}`` is the sweep under test
    (a ``functools.partial`` of ``scenarios.run_grid`` or
    ``scenarios.run_lm_grid``); ``kw`` carries only the sharding options.
    Times the unsharded vmapped grid against the device-sharded grid (and,
    when ``max_lanes_per_device`` is given, the chunked streaming mode),
    asserting every lane bitwise-equal between all paths before comparing
    times.  On a 1-device host the sharded path degenerates to the unsharded
    math plus partitioning overhead; the CI determinism job re-runs the smoke
    version under 8 forced host devices.
    """
    import time

    import numpy as np

    def timed(**kw):
        t0 = time.perf_counter()
        res = runner(**kw)
        jax.block_until_ready([r.x for r in res.values()])
        return time.perf_counter() - t0, res

    t_single_cold, res_single = timed()
    t_single_warm, _ = timed()
    t_shard_cold, res_shard = timed(shard=shard)
    t_shard_warm, _ = timed(shard=shard)

    def check(res, label):
        for name in res_single:
            ref = res_single[name]
            assert np.array_equal(
                np.asarray(res[name].x), np.asarray(ref.x)
            ), f"{prefix}{label}: sharded != unsharded for {name}"
            for k in ref.metrics:  # every lane bitwise, metrics included
                assert np.array_equal(
                    np.asarray(res[name].metrics[k]), np.asarray(ref.metrics[k])
                ), f"{prefix}{label}: sharded != unsharded for {name}: {k}"

    check(res_shard, "sharded")
    n = n_rows
    rows = [
        (f"{prefix}unsharded_cold", n, t_single_cold),
        (f"{prefix}unsharded_warm", n, t_single_warm),
        (f"{prefix}sharded_cold", n, t_shard_cold),
        (f"{prefix}sharded_warm", n, t_shard_warm),
        (f"{prefix}speedup_warm_sharded_vs_unsharded", n, t_single_warm / t_shard_warm),
    ]
    if max_lanes_per_device is not None:
        from repro.core import engine

        kw = dict(shard=shard, max_lanes_per_device=max_lanes_per_device)
        timed(**kw)  # cold: the chunk shape compiles its own executable
        misses0 = engine._grid_program.cache_info().misses
        t_chunk_warm, res_chunk = timed(**kw)
        # the lru-cached one-program-per-bucket contract extends to the
        # sharded+chunked path: the warm sweep may not miss the program cache
        assert engine._grid_program.cache_info().misses == misses0, (
            f"{prefix}: warm sharded sweep missed the grid-program cache"
        )
        check(res_chunk, "sharded_chunked")
        rows.append((f"{prefix}sharded_chunked_warm", n, t_chunk_warm))
    return rows


GRID_SHARDED_SCHEMA_VERSION = 1
LM_ENGINE_SCHEMA_VERSION = 1
PARTICIPATION_SCHEMA_VERSION = 1
ZOO_SERVE_SCHEMA_VERSION = 1


def _write_json(payload: dict, path: str) -> None:
    import json
    import os

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def write_grid_sharded_json(payload: dict, path: str) -> None:
    _write_json(payload, path)


def write_lm_engine_json(payload: dict, path: str) -> None:
    _write_json(payload, path)


def grid_sharded(
    lanes: int = 1000,
    steps: int = 12,
    n_devices: int = 16,
    dim: int = 32,
    shard: str = "shard_map",
    max_lanes_per_device: int = 64,
    out_path: str = "benchmarks/out/BENCH_grid_sharded.json",
):
    """The 1000-row device-sharded synthetic sweep (the DRACO-scale
    redundancy-study regime): one compile bucket (``scenarios.
    synthetic_sweep``), lane axis partitioned over every visible device and
    streamed in ``max_lanes_per_device``-sized chunks of one cached program.

    Asserts every lane bitwise-equal to the unsharded grid (iterates AND
    metrics) and that the warm sharded sweep makes zero program-cache misses
    (both inside ``_timed_sharded_rows``), then records the timing rows
    machine-readably to ``BENCH_grid_sharded.json`` (schema validated in
    tier-1 by scripts/bench_smoke.py) as well as to the figure CSV.
    """
    import functools

    rows_scn = scenarios.synthetic_sweep(lanes, n_devices=n_devices, n_byz=3)
    rows = _timed_sharded_rows(
        functools.partial(scenarios.run_grid, rows_scn, steps, dim=dim),
        len(rows_scn), "grid1k_", shard=shard,
        max_lanes_per_device=max_lanes_per_device,
    )
    payload = {
        "schema_version": GRID_SHARDED_SCHEMA_VERSION,
        "device_count": jax.device_count(),
        "shard": shard,
        "lanes": lanes,
        "max_lanes_per_device": max_lanes_per_device,
        "steps": steps,
        "n_devices": n_devices,
        "dim": dim,
        "rows": [
            {"name": name, "lanes": n, "value": float(value)}
            for name, n, value in rows
        ],
    }
    write_grid_sharded_json(payload, out_path)
    return rows


def lm_engine(
    steps: int = 8,
    shard: str = "shard_map",
    max_lanes_per_device: int = 2,
    per_subset: int = 2,
    seq_len: int = 16,
    out_path: str = "benchmarks/out/BENCH_lm_engine.json",
    rows_scn=None,
):
    """The LM-scale engine sweep (``scenarios.lm_sweep``: method x attack x
    aggregator x compressor over a small transformer), device-sharded and
    streamed through ``max_lanes_per_device``-sized chunks of one cached
    program — the LM twin of the ``grid_sharded`` figure.

    Asserts (inside ``_timed_sharded_rows``) every lane bitwise-equal between
    the sharded, chunked and unsharded grids with zero program-cache misses
    on the warm sweep, then additionally cross-checks the grid against the
    per-scenario ``mode="scan"`` reference (grid == standalone, bitwise) and
    times it.  Rows land machine-readably in ``BENCH_lm_engine.json``
    (schema validated in tier-1 by scripts/bench_smoke.py) and in the figure
    CSV.
    """
    import functools
    import time

    import numpy as np

    if rows_scn is None:
        rows_scn = scenarios.lm_sweep()
    runner = functools.partial(
        scenarios.run_lm_grid, rows_scn, steps, per_subset=per_subset,
        seq_len=seq_len,
    )
    rows = _timed_sharded_rows(
        runner, len(rows_scn), "lm_engine_", shard=shard,
        max_lanes_per_device=max_lanes_per_device,
    )
    res_grid = runner()  # warm: reuses the cached unsharded program
    runner(mode="scan")  # cold per-scenario pass: compiles trajectory programs
    t0 = time.perf_counter()
    res_scan = runner(mode="scan")
    jax.block_until_ready([r.x for r in res_scan.values()])
    t_scan = time.perf_counter() - t0
    for name in res_scan:  # the conformance claim, asserted in the bench too
        assert np.array_equal(
            np.asarray(res_grid[name].x), np.asarray(res_scan[name].x)
        ), f"lm_engine: grid != standalone scan for {name}"
    rows.append(("lm_engine_per_scenario_warm", len(rows_scn), t_scan))
    arch = scenarios.lm_arch()
    payload = {
        "schema_version": LM_ENGINE_SCHEMA_VERSION,
        "device_count": jax.device_count(),
        "shard": shard,
        "lanes": len(rows_scn),
        "max_lanes_per_device": max_lanes_per_device,
        "steps": steps,
        "n_devices": rows_scn[0].n_devices,
        "per_subset": per_subset,
        "seq_len": seq_len,
        "params": int(scenarios._lm_fns(arch)[0].size),
        "arch": {
            "name": arch.name,
            "n_layers": arch.n_layers,
            "d_model": arch.d_model,
            "vocab": arch.vocab,
        },
        "rows": [
            {"name": name, "lanes": n, "value": float(value)}
            for name, n, value in rows
        ],
    }
    write_lm_engine_json(payload, out_path)
    return rows


def write_participation_json(payload: dict, path: str) -> None:
    _write_json(payload, path)


def participation_bench(
    steps: int = 400,
    n_devices: int = 16,
    d: int = 4,
    dim: int = 32,
    lr: float = 1e-5,
    out_path: str = "benchmarks/out/BENCH_participation.json",
):
    """The K-of-N erasure sweep: recovered vs undefended loss + grid timings.

    For every erasure count ``e`` in ``0..erasure_margin(d)`` (the worst-case
    ``adversarial`` schedule erases the same ``e`` rows every round, so
    ``K = N - e`` devices report) the sweep trains two lanes on identical
    data/keys: ``aggregator="decode"`` (the cyclic K-of-N erasure decode —
    the *recovered* curve) and ``aggregator="mean"`` over the reporting rows
    (the *undefended* reference).  The whole sweep is one vmapped grid.

    Asserted claims (the participation contract, measured):
      * the decode's final loss is erasure-INVARIANT across the margin — it
        recovers the full-participation gradient mean exactly (up to float)
        at every ``e <= d - 1``, so all its lanes follow one trajectory;
      * the undefended mean's final loss varies with ``e`` at least as much —
        survivors-only averaging is erasure-sensitive.

    Rows land in ``BENCH_participation.json`` (schema validated in tier-1 by
    scripts/bench_smoke.py) with cold/warm whole-grid wall clock.
    """
    import dataclasses
    import time

    import numpy as np

    from repro.core.coding import erasure_margin

    margin = erasure_margin(d)
    base = scenarios.synthetic_sweep(1, n_devices=n_devices)[0]
    rows_scn = [
        dataclasses.replace(
            base, name=f"e{e}/{agg}", method="lad", d=d, aggregator=agg,
            attack="none", n_byz=0, lr=lr, sigma_h=0.3,
            participation="adversarial", p_drop_n=e,
        )
        for e in range(margin + 1)
        for agg in ("decode", "mean")
    ]

    def timed():
        t0 = time.perf_counter()
        res = scenarios.run_grid(rows_scn, steps, dim=dim)
        jax.block_until_ready([r.x for r in res.values()])
        return time.perf_counter() - t0, res

    t_cold, res = timed()
    t_warm, _ = timed()

    finals = {name: float(r.metrics["loss"][-1]) for name, r in res.items()}
    assert all(np.isfinite(v) and v > 0 for v in finals.values()), finals
    for e in range(margin + 1):  # K = N - e devices reported, every round
        nr = np.asarray(res[f"e{e}/decode"].metrics["n_report"])
        assert np.all(nr == float(n_devices - e)), (e, nr)

    def rel_spread(agg):
        vals = [finals[f"e{e}/{agg}"] for e in range(margin + 1)]
        return (max(vals) - min(vals)) / max(vals)

    spread_decode, spread_mean = rel_spread("decode"), rel_spread("mean")
    assert spread_decode <= 1e-4, (
        f"decode must be erasure-invariant within the margin: {finals}"
    )
    assert spread_mean >= spread_decode, (spread_mean, spread_decode)

    payload = {
        "schema_version": PARTICIPATION_SCHEMA_VERSION,
        "device_count": jax.device_count(),
        "n_devices": n_devices,
        "d": d,
        "margin": margin,
        "steps": steps,
        "dim": dim,
        "rows": [
            {
                "name": f"e{e}/{agg}",
                "erasures": e,
                "k_of_n": n_devices - e,
                "aggregator": agg,
                "final_loss": finals[f"e{e}/{agg}"],
            }
            for e in range(margin + 1)
            for agg in ("decode", "mean")
        ],
        "timings": [
            {"name": "grid_cold", "seconds": t_cold},
            {"name": "grid_warm", "seconds": t_warm},
        ],
        "rel_spread": {"decode": spread_decode, "mean": spread_mean},
    }
    write_participation_json(payload, out_path)
    return payload


def write_zoo_serve_json(payload: dict, path: str) -> None:
    _write_json(payload, path)


ZOO_SERVE_ROBUST_DELTA_BOUND = 0.25  # nats; robust-vs-clean eval NLL gap


def zoo_serve(
    families=None,
    steps: int = 40,
    n_subsets: int = 8,
    per_subset: int = 2,
    seq_len: int = 16,
    n_byz: int = 3,
    lr: float = 1e-2,
    serve_batch: int = 4,
    new_tokens: int = 8,
    out_path: str = "benchmarks/out/BENCH_zoo_serve.json",
):
    """The train-to-serve loop over the architecture zoo, measured.

    For every zoo family (``scenarios.ZOO_FAMILIES``) three engine-path
    trainers run on identical heterogeneous-LM data through
    ``build_train_step``:

      * **clean**      — ``protocol="none"`` (honest mean, no attack);
      * **robust**     — ``protocol="lad"`` (d=2 cyclic code + CWTM) under a
        ``n_byz``-of-N sign-flip attack — the paper's pipeline at
        whole-model granularity;
      * **undefended** — ``protocol="plain"`` (plain mean) under the SAME
        attack.  At ``n_byz=3`` of 8 the sign-flip (coeff -2) drives the
        mean to ``-g/8``: the undefended run *ascends*.

    Each records eval NLL on a held-out batch; the robust-vs-clean delta is
    asserted within ``ZOO_SERVE_ROBUST_DELTA_BOUND`` while the undefended
    delta is recorded (and must exceed the robust delta).  The robust
    checkpoint then closes the loop: ``Trainer.save`` ->
    ``checkpoint.restore_for_serving`` (asserted bitwise) ->
    ``launch.serve.serve_traffic`` prefill + greedy decode, recording
    tokens/sec.  Rows land in ``BENCH_zoo_serve.json`` (schema validated by
    scripts/bench_smoke.py; drift-tested in tier-1, committed baseline
    checked in CI's determinism job).
    """
    import os
    import tempfile

    import numpy as np

    from repro.checkpoint import restore_for_serving
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import lm_batch_for_devices
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import serve_traffic
    from repro.launch.train import Trainer

    families = list(families if families is not None else scenarios.ZOO_FAMILIES)
    mesh = make_host_mesh(1, 1)
    ckpt_dir = tempfile.mkdtemp(prefix="zoo_serve_")
    rows = []
    for fam in families:
        cfg = scenarios.zoo_arch(fam)

        def flat_batch(seed, cfg=cfg):
            b = lm_batch_for_devices(
                jax.random.PRNGKey(seed), cfg.vocab, n_subsets=n_subsets,
                per_subset=per_subset, seq_len=seq_len, sigma_h=0.5,
            )
            out = {k: v.reshape((-1,) + v.shape[2:]) for k, v in b.items()}
            if cfg.family in ("vlm", "audio"):
                enc = cfg.encoder
                out["frontend"] = jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(seed), 7),
                    (n_subsets * per_subset, enc.n_frontend_tokens, enc.d_frontend),
                )
            return out

        train_b, eval_b = flat_batch(0), flat_batch(1)
        nll = {}
        robust_tr = None
        for label, protocol, agg, byz in (
            ("clean", "none", "mean", 0),
            ("robust", "lad", "cwtm", n_byz),
            ("undefended", "plain", "mean", n_byz),
        ):
            tcfg = TrainConfig(
                arch=cfg.name, protocol=protocol, protocol_impl="engine",
                n_subsets=n_subsets, d=2, aggregator=agg, trim_frac=0.375,
                n_byz=byz, attack="sign_flip", steps=steps, lr=lr,
                remat=False, seed=0,
            )
            tr = Trainer(cfg=cfg, tcfg=tcfg, mesh=mesh)
            tr.run([train_b] * steps, log_every=steps)
            nll[label] = tr.eval_loss(eval_b)
            if label == "robust":
                robust_tr = tr
        robust_delta = nll["robust"] - nll["clean"]
        undefended_delta = nll["undefended"] - nll["clean"]
        assert robust_delta <= ZOO_SERVE_ROBUST_DELTA_BOUND, (
            f"{fam}: robust checkpoint degraded by {robust_delta:.3f} nats "
            f"(> {ZOO_SERVE_ROBUST_DELTA_BOUND}) under the attack"
        )
        assert undefended_delta > robust_delta, (fam, nll)

        # close the loop: checkpoint -> restore -> serve
        path = os.path.join(ckpt_dir, fam)
        robust_tr.save(path)
        params, specs, step = restore_for_serving(path, cfg)
        assert step == steps
        roundtrip = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(robust_tr.params), jax.tree.leaves(params))
        )
        assert roundtrip, f"{fam}: checkpoint roundtrip not bitwise"
        served = serve_traffic(
            cfg, params, specs, mesh,
            eval_b["tokens"][:serve_batch],
            frontend=(eval_b["frontend"][:serve_batch]
                      if "frontend" in eval_b else None),
            new_tokens=new_tokens,
        )
        assert served["pos"] == seq_len + new_tokens, served["pos"]
        rows.append({
            "family": fam,
            "arch": cfg.name,
            "n_layers": cfg.n_layers,
            "params": int(scenarios._lm_fns(cfg)[0].size),
            "nll_clean": float(nll["clean"]),
            "nll_robust": float(nll["robust"]),
            "nll_undefended": float(nll["undefended"]),
            "robust_delta": float(robust_delta),
            "undefended_delta": float(undefended_delta),
            "roundtrip_bitwise": bool(roundtrip),
            "prefill_tokens_per_s": float(served["prefill_tokens_per_s"]),
            "decode_tokens_per_s": float(served["decode_tokens_per_s"]),
            "decoded_tokens": int(served["tokens"].shape[1]),
        })
    payload = {
        "schema_version": ZOO_SERVE_SCHEMA_VERSION,
        "device_count": jax.device_count(),
        "steps": steps,
        "n_subsets": n_subsets,
        "per_subset": per_subset,
        "seq_len": seq_len,
        "n_byz": n_byz,
        "attack": "sign_flip",
        "lr": lr,
        "new_tokens": new_tokens,
        "robust_delta_bound": ZOO_SERVE_ROBUST_DELTA_BOUND,
        "rows": rows,
    }
    write_zoo_serve_json(payload, out_path)
    return payload


def grid_timing(steps: int = 300, kernel_steps: int = 60):
    """End-to-end wall-clock of the whole-grid on-device engine vs the PR-1
    per-scenario dispatch loop, on the full ``section7_grid()`` — for the
    XLA backend AND the Pallas kernel backend (``backend="interpret"``;
    rows prefixed ``kernel_``), which since PR 3 rides the same lru-cached
    one-program-per-bucket path via the lane-batched kernels.

    Two regimes per mode: *cold* (first sweep in the process — compile +
    run + readback) and *warm* (the sweep repeated — the figure-driver /
    notebook / parameter-study regime).  The vmapped engine caches its
    compiled programs across calls, so a warm whole-grid sweep makes zero
    compilations and zero per-scenario Python dispatches; the per-scenario
    loop re-dispatches every scenario each sweep.  Both sections assert the
    two paths agree BITWISE before comparing times.

    Rows: (mode_regime, n_scenarios, seconds) + the cold/warm speedups.
    The kernel section runs fewer steps and N=32 devices: interpret mode is
    CPU-slow, and N=32 is inside the verified bitwise envelope of the
    interpret backend (residual LLVM fma discretion makes a few *other*
    device counts disagree by 1 ulp between program shapes — see
    repro/numerics.py); the relative grid-vs-dispatch shape is what matters.
    """
    import dataclasses

    rows = _timed_grid_rows(scenarios.section7_grid(), steps, "")
    kernel_grid = [
        dataclasses.replace(s, n_devices=32, n_byz=6, backend="interpret")
        for s in scenarios.section7_grid(
            methods=(("plain", 1), ("lad", 10)), attacks=("sign_flip", "alie", "ipm"),
            compressors=("none", "rand_sparse"),
        )
    ]
    rows += _timed_grid_rows(kernel_grid, kernel_steps, "kernel_")
    # device-sharded vs unsharded on a single-bucket synthetic sweep (the
    # sharded rows are the per-machine record; BENCH_grid_sharded.json from
    # the grid_sharded figure is the machine-readable 1000-row version)
    import functools

    sharded_scn = scenarios.synthetic_sweep(48, n_devices=16, n_byz=3)
    rows += _timed_sharded_rows(
        functools.partial(scenarios.run_grid, sharded_scn, 60, dim=32),
        len(sharded_scn), "sharded48_", max_lanes_per_device=8,
    )
    # the sharded LM train path (transformer lanes through the engine): the
    # per-machine cold/warm record; BENCH_lm_engine.json from the lm_engine
    # figure is the machine-readable full-matrix version
    lm_scn = scenarios.lm_sweep(attacks=("sign_flip", "alie"), compressors=("none",))
    rows += _timed_sharded_rows(
        functools.partial(scenarios.run_lm_grid, lm_scn, 10, per_subset=2, seq_len=16),
        len(lm_scn), "lm_sharded_", max_lanes_per_device=2,
    )
    return rows


FIGURES = {
    "fig2_error_vs_delta": fig2_error_vs_delta,
    "fig3_error_vs_d": fig3_error_vs_d,
    "fig4_training_loss": fig4_training_loss,
    "fig5_heterogeneity": fig5_heterogeneity,
    "fig6_compressed": fig6_compressed,
    "section7_sweep": section7_sweep,
    "grid_timing": grid_timing,
    "grid_sharded": grid_sharded,
    "lm_engine": lm_engine,
    "participation": participation_bench,
    "zoo_serve": zoo_serve,
}
