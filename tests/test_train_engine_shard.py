"""Sharded LM-engine train path: the cross-substrate conformance suite.

The claim under test: ``launch/train.py::build_train_step`` with
``TrainConfig.shard="shard_map"`` produces BITWISE-identical training
steps to ``shard="none"`` — parameters, optimizer state, loss and metrics —
at the clean simulation scales of the engine guarantee (N = 10/16/32, see
README "Engine guarantees" and repro/numerics.py), and the LM-scale scenario
grid (``scenarios.run_lm_grid``) keeps the same parity lane-for-lane against
both the unsharded grid and the standalone per-scenario trajectories.

Every test is *device-count generic*: tier-1 runs them on the 1 real CPU
device (the sharded substrates must degenerate to the unsharded math
bitwise), and the CI determinism job re-runs the same tests under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so subset padding
(N=10 on 8 devices), per-device fan-out widths and the all-gather round body
are exercised for real.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs.archs import ARCHS, reduced
from repro.configs.base import TrainConfig
from repro.core import engine, scenarios
from repro.data.synthetic import lm_batch_for_devices
from repro.launch import train as train_lib
from repro.launch.mesh import make_host_mesh

CLEAN_SCALES = (10, 16, 32)
SHARDS = ("shard_map",)
STEPS = 2


def _arch():
    return scenarios.lm_arch()


def _tcfg(n, shard, **kw):
    base = dict(
        arch=_arch().name, protocol="lad", protocol_impl="engine", n_subsets=n,
        d=2, aggregator="cwtm", trim_frac=0.2, n_byz=2, attack="sign_flip",
        optimizer="adamw", lr=3e-3, steps=4, shard=shard,
    )
    base.update(kw)
    return TrainConfig(**base)


def _run_steps(tcfg, *, steps=STEPS, per_subset=1, seq_len=8):
    """``steps`` engine train steps on deterministic batches; returns the
    full end state (params, opt_state, last loss, last metrics)."""
    cfg = _arch()
    n = tcfg.n_subsets
    mesh = make_host_mesh(1, 1)
    params, specs = models.init(jax.random.PRNGKey(0), cfg)
    step, opt = train_lib.build_train_step(cfg, tcfg, mesh, specs)
    opt_state = opt.init(params)
    key = jax.random.PRNGKey(42)
    loss = metrics = None
    for i in range(steps):
        b = lm_batch_for_devices(
            jax.random.fold_in(key, i), cfg.vocab, n_subsets=n,
            per_subset=per_subset * max(1, tcfg.microbatches),
            seq_len=seq_len, sigma_h=0.5,
        )
        batch = {k: v.reshape(-1, v.shape[-1]) for k, v in b.items()}
        params, opt_state, loss, metrics = step(
            params, opt_state, batch, jnp.asarray(i, jnp.int32)
        )
    return jax.device_get((params, opt_state, loss, metrics))


def _assert_trees_equal(got, ref, label):
    ref_leaves, ref_def = jax.tree.flatten(ref)
    got_leaves, got_def = jax.tree.flatten(got)
    assert got_def == ref_def, label
    for i, (g, r) in enumerate(zip(got_leaves, ref_leaves)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(r), err_msg=f"{label}: leaf {i}"
        )


@pytest.mark.parametrize(
    "n",
    [n if n != 16 else pytest.param(n, marks=pytest.mark.slow)
     for n in CLEAN_SCALES],
)
def test_sharded_step_bitwise_vs_unsharded(n):
    """Params, optimizer state, loss and metrics after LAD+CWTM engine steps
    must be bitwise identical between shard="none" and the sharded
    substrate, at every clean scale."""
    ref = _run_steps(_tcfg(n, "none"))
    for shard in SHARDS:
        _assert_trees_equal(_run_steps(_tcfg(n, shard)), ref, f"N={n} {shard}")


def test_sharded_step_bitwise_microbatched_com_lad():
    """microbatches > 1 (per-microbatch robust exchange, fp32 accumulation)
    with Com-LAD compression keeps the substrate parity bitwise."""
    kw = dict(compression="rand_sparse", q_hat_frac=0.5, microbatches=2)
    ref = _run_steps(_tcfg(10, "none", **kw))
    _assert_trees_equal(
        _run_steps(_tcfg(10, "shard_map", **kw)), ref, "micro com-lad shard_map"
    )


def test_warm_sharded_steps_zero_compiles():
    """Warm engine steps — and a second step fn built from an equal config —
    must make zero new program builds and zero trace events, on every
    substrate (the engine-path twin of the grid's zero-retrace contract)."""
    cfg = _arch()
    mesh = make_host_mesh(1, 1)
    params, specs = models.init(jax.random.PRNGKey(0), cfg)
    b = lm_batch_for_devices(jax.random.PRNGKey(7), cfg.vocab, n_subsets=10,
                             per_subset=1, seq_len=8, sigma_h=0.5)
    batch = {k: v.reshape(-1, v.shape[-1]) for k, v in b.items()}
    for shard in ("none",) + SHARDS:
        tcfg = _tcfg(10, shard)
        step, opt = train_lib.build_train_step(cfg, tcfg, mesh, specs)
        opt_state = opt.init(params)
        out = step(params, opt_state, batch, jnp.asarray(0, jnp.int32))
        jax.block_until_ready(out)
        info0 = train_lib.engine_program_cache_info()
        for i in (1, 2):  # warm steps: same shapes, fresh operands
            out = step(params, opt_state, batch, jnp.asarray(i, jnp.int32))
            jax.block_until_ready(out)
        # an equal config must reuse the cached programs outright
        step2, _ = train_lib.build_train_step(cfg, _tcfg(10, shard), mesh, specs)
        out = step2(params, opt_state, batch, jnp.asarray(3, jnp.int32))
        jax.block_until_ready(out)
        assert train_lib.engine_program_cache_info() == info0, shard


@pytest.mark.slow
def test_lm_grid_sharded_bitwise_vs_unsharded_and_standalone():
    """The LM-scale scenario grid: sharded == unsharded == standalone
    per-scenario trajectories, bitwise, lanes and metrics — with a lane
    count (3) not divisible by any multi-device count so the padding path is
    always exercised.  Slow-marked: every push
    still asserts the sharded-LM-grid bitwise + zero-compile contract via
    the CI determinism job's standalone ``scripts/bench_smoke.py``
    (``smoke_lm_engine``); this finer-grained version runs nightly."""
    rows = scenarios.lm_sweep(
        methods=(("lad", 2),), attacks=("sign_flip", "alie", "ipm"),
        compressors=("none",),
    )
    assert len(rows) == 3
    kw = dict(per_subset=1, seq_len=8)
    ref = scenarios.run_lm_grid(rows, 3, **kw)
    # grid-vs-standalone: one-lane spot check here (each scan lane compiles
    # its own trajectory program — test-speed budget); the full-matrix scan
    # parity runs nightly in the slow test below
    scan = scenarios.run_lm_grid(rows[:1], 3, mode="scan", **kw)
    for name in scan:
        _assert_trees_equal(
            (ref[name].x, ref[name].metrics),
            (scan[name].x, scan[name].metrics),
            f"grid vs scan: {name}",
        )
    got = scenarios.run_lm_grid(rows, 3, shard="shard_map", **kw)
    for name in ref:
        _assert_trees_equal(
            (got[name].x, got[name].metrics),
            (ref[name].x, ref[name].metrics),
            f"shard_map: {name}",
        )
    chunked = scenarios.run_lm_grid(
        rows, 3, shard="shard_map", max_lanes_per_device=1, **kw
    )
    misses0 = engine._grid_program.cache_info().misses
    warm = scenarios.run_lm_grid(
        rows, 3, shard="shard_map", max_lanes_per_device=1, **kw
    )
    assert engine._grid_program.cache_info().misses == misses0
    for name in ref:
        _assert_trees_equal(chunked[name].x, ref[name].x, f"chunked: {name}")
        _assert_trees_equal(warm[name].x, ref[name].x, f"warm chunked: {name}")


@pytest.mark.slow
def test_lm_grid_full_matrix_sharded_bitwise():
    """The full default lm_sweep matrix (method x attack x compressor, 12
    rows / 4 compile buckets) at a second clean scale, sharded and chunked —
    the nightly --runslow version of the fast 3-row test."""
    rows = scenarios.lm_sweep(n_devices=16, n_byz=3)
    assert len(rows) == 12
    assert len({scenarios._bucket_signature(s) for s in rows}) == 4
    ref = scenarios.run_lm_grid(rows, 3)
    scan = scenarios.run_lm_grid(rows, 3, mode="scan")
    for shard in SHARDS:
        got = scenarios.run_lm_grid(rows, 3, shard=shard, max_lanes_per_device=2)
        for name in ref:
            _assert_trees_equal(
                (got[name].x, got[name].metrics),
                (ref[name].x, ref[name].metrics),
                f"{shard}: {name}",
            )
    for name in ref:
        _assert_trees_equal(ref[name].x, scan[name].x, f"grid vs scan: {name}")


def test_trainer_drives_sharded_substrates_identically():
    """End-to-end through ``Trainer`` (which commits params/batches to its
    own 1x1 GSPMD mesh — the integration the direct step calls skip): both
    substrates must produce the identical loss history.  Trainer must not
    re-jit the engine step, and the sharded step must re-lay-out the
    mesh-committed inputs onto the engine mesh itself."""
    from repro.launch.train import Trainer

    cfg = _arch()
    key = jax.random.PRNGKey(0)

    def batches(steps):
        for i in range(steps):
            b = lm_batch_for_devices(
                jax.random.fold_in(key, i), cfg.vocab, n_subsets=10,
                per_subset=1, seq_len=8, sigma_h=0.5,
            )
            yield {k: v.reshape(-1, v.shape[-1]) for k, v in b.items()}

    hists = {}
    for shard in ("none",) + SHARDS:
        tcfg = _tcfg(10, shard)  # same config as the step tests: the round
        tr = Trainer(cfg=cfg, tcfg=tcfg, mesh=make_host_mesh(1, 1))  # and
        # apply programs are already cached — this test costs only Trainer
        # integration (GSPMD-committed params/batches), not fresh compiles;
        # one batch suffices (multi-step substrate parity is the step tests')
        hists[shard] = tr.run(batches(1), log_every=1)
    assert hists["shard_map"] == hists["none"], hists


def test_run_lm_grid_validation():
    rows = scenarios.lm_sweep(methods=(("lad", 2),), attacks=("sign_flip",),
                              compressors=("none",))
    with pytest.raises(ValueError, match="at least one scenario"):
        scenarios.run_lm_grid([], 2)
    with pytest.raises(ValueError, match="sigma_h"):
        import dataclasses

        mixed = rows + [dataclasses.replace(rows[0], name="x", sigma_h=0.1)]
        scenarios.run_lm_grid(mixed, 2)
    with pytest.raises(ValueError, match="grid-mode"):
        scenarios.run_lm_grid(rows, 2, mode="scan", shard="shard_map")
    with pytest.raises(ValueError, match="unknown grid mode"):
        scenarios.run_lm_grid(rows, 2, mode="bogus")


def test_engine_step_shard_validation():
    """The negative paths of the sharded train step: unknown shard strings
    (``"pmap"`` among them; "none" and "shard_map" are the only substrates)
    and any protocol realization but the engine must raise clear
    ValueErrors."""
    cfg = _arch()
    mesh = make_host_mesh(1, 1)
    for shard in ("gspmd", "pmap"):
        with pytest.raises(ValueError, match="unknown engine shard mode"):
            train_lib.build_train_step(
                cfg, _tcfg(8, shard), mesh, specs=None
            )
    with pytest.raises(ValueError, match="unknown protocol_impl 'gspmd'"):
        train_lib.build_train_step(
            cfg, TrainConfig(protocol="lad", protocol_impl="gspmd"),
            mesh, specs=None,
        )
    # unknown protocol_impl still wins over shard validation
    with pytest.raises(ValueError, match="protocol_impl"):
        train_lib.build_train_step(
            cfg, TrainConfig(protocol_impl="bogus", shard="shard_map"),
            mesh, specs=None,
        )
