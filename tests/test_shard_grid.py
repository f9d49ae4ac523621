"""Device-sharded grid engine: bitwise lane parity + padding/chunking edges.

Every test here is *device-count generic*: tier-1 runs them on the 1 real CPU
device (where the sharded paths must still degenerate to the unsharded math
bitwise), and the CI determinism job re-runs the same tests under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the lane padding,
per-device partitioning and cross-device program shapes are exercised for
real.  The parity scales are the clean ones of the engine guarantee
(N = 10/16/32 — see README "Engine guarantees" and repro/numerics.py).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ProtocolConfig, engine, scenarios
from repro.core.attacks import AttackSpec
from repro.data.synthetic import linreg_loss, linreg_subset_grads
from repro.launch.mesh import padded_lane_count
from repro.testing import given, settings, strategies as st

STEPS, DIM = 5, 12
SHARDS = ("shard_map",)


def _match(got, ref):
    for name, r in ref.items():
        g = got[name]
        np.testing.assert_array_equal(
            np.asarray(g.x), np.asarray(r.x), err_msg=f"{name}: x"
        )
        assert sorted(g.metrics) == sorted(r.metrics)
        for k in r.metrics:
            np.testing.assert_array_equal(
                np.asarray(g.metrics[k]), np.asarray(r.metrics[k]),
                err_msg=f"{name}: {k}",
            )


@pytest.mark.parametrize("n", (10, 16, 32))
def test_sharded_bitwise_vs_unsharded_and_standalone(n):
    """The sharded grid must reproduce every lane of the unsharded grid
    BITWISE, and the grid itself its standalone per-scenario trajectories —
    with a lane count (5) not divisible by any multi-device count, so the
    device-padding path is always exercised."""
    rows = scenarios.synthetic_sweep(5, n_devices=n, n_byz=2)
    ref = scenarios.run_grid(rows, STEPS, dim=DIM)
    if n == 10:  # grid == standalone-scan parity is scale-independent:
        # checking it once keeps 16/32 to the sharded contract (speed budget)
        _match(ref, scenarios.run_grid(rows, STEPS, dim=DIM, mode="scan"))
    for shard in SHARDS:
        _match(scenarios.run_grid(rows, STEPS, dim=DIM, shard=shard), ref)


def test_sharded_kernel_backend_bitwise():
    """The fully lane-resident kernel round body (gather_combine + attack
    kernels + cwtm) under shard_map, bitwise vs the unsharded kernel grid."""
    rows = scenarios.synthetic_sweep(3, n_devices=10, n_byz=2, backend="interpret")
    ref = scenarios.run_grid(rows, STEPS, dim=DIM)
    # (kernel grid == standalone scan is test_engine's kernel-backend test)
    _match(scenarios.run_grid(rows, STEPS, dim=DIM, shard="shard_map"), ref)


def test_chunked_streaming_bitwise():
    """max_lanes_per_device streams the sweep through equal-sized chunks of
    one program; every chunk size (down to 1 lane per device) must
    concatenate back to the unchunked result bitwise — sharded or not."""
    rows = scenarios.synthetic_sweep(5, n_devices=10, n_byz=2)
    ref = scenarios.run_grid(rows, STEPS, dim=DIM)
    for mlpd in (1, 2):
        _match(
            scenarios.run_grid(
                rows, STEPS, dim=DIM, shard="shard_map", max_lanes_per_device=mlpd
            ),
            ref,
        )
    _match(
        scenarios.run_grid(rows, STEPS, dim=DIM, max_lanes_per_device=2), ref
    )  # chunked single-device streaming (shard="none")


def test_single_lane_bucket_under_shard_map():
    """A 1-lane bucket pads up to the full device count and still matches
    its standalone trajectory bitwise."""
    rows = scenarios.synthetic_sweep(1, n_devices=16, n_byz=3)
    ref = scenarios.run_grid(rows, STEPS, dim=DIM, mode="scan")
    for shard in SHARDS:
        _match(scenarios.run_grid(rows, STEPS, dim=DIM, shard=shard), ref)


def test_sharded_warm_zero_compiles_zero_dispatch(monkeypatch):
    """A warm sharded+chunked section7_grid() sweep must make zero
    per-scenario dispatches and zero grid-program cache misses — the
    lru-cached one-program-per-bucket contract extends to the sharded path
    (multiple compile buckets included: method x compressor stay separate
    programs, each sharded)."""
    rows = [
        dataclasses.replace(s, n_devices=16, n_byz=3, lr=1e-5)
        for s in scenarios.section7_grid(
            methods=(("plain", 1), ("lad", 4)), attacks=("sign_flip", "alie"),
            compressors=("none",),
        )
    ]
    assert len({scenarios._bucket_signature(s) for s in rows}) == 2
    kw = dict(dim=DIM, shard="shard_map", max_lanes_per_device=2)
    scenarios.run_grid(rows, STEPS, **kw)  # cold: compiles + caches
    misses0 = engine._grid_program.cache_info().misses

    def _boom(*a, **k):  # any per-scenario dispatch would be a regression
        raise AssertionError("run_grid(mode='grid') dispatched per-scenario")

    monkeypatch.setattr(scenarios, "run_scenario", _boom)
    scenarios.run_grid(rows, STEPS, **kw)  # warm
    assert engine._grid_program.cache_info().misses == misses0


@pytest.mark.parametrize("n", (10, 32))
def test_all_ones_participation_grid_bitwise_vs_legacy(n):
    """iid at rate 0.0 — all-ones masks through the FULL masked machinery
    (widened scan carry, erasure multiply, mask-aware server) — must
    reproduce the legacy full-participation grid BITWISE, unsharded and
    under shard_map.  Grid-level runs the edge scales; the full N=10/16/32
    x backend matrix lives in test_participation.py at trajectory level."""
    legacy_rows = scenarios.synthetic_sweep(3, n_devices=n, n_byz=2)
    rows = [
        dataclasses.replace(s, participation="iid", p_rate=0.0)
        for s in legacy_rows
    ]
    ref = scenarios.run_grid(legacy_rows, STEPS, dim=DIM)
    got = scenarios.run_grid(rows, STEPS, dim=DIM)
    for name, r in ref.items():
        g = got[name]
        np.testing.assert_array_equal(
            np.asarray(g.x), np.asarray(r.x), err_msg=f"{name}: x"
        )
        for k in r.metrics:  # the masked run adds n_report on top
            np.testing.assert_array_equal(
                np.asarray(g.metrics[k]), np.asarray(r.metrics[k]),
                err_msg=f"{name}: {k}",
            )
        np.testing.assert_array_equal(
            np.asarray(g.metrics["n_report"]), np.full((STEPS,), float(n)),
            err_msg=name,
        )
    _match(scenarios.run_grid(rows, STEPS, dim=DIM, shard="shard_map"), got)


def test_participation_sharded_warm_zero_compiles(monkeypatch):
    """The zero-warm-compile contract extends to active-participation lanes:
    the stateful carry and the mask-aware server ride the same lru-cached
    one-program-per-bucket grid path, sharded."""
    rows = scenarios.participation_sweep(
        d=4, n_devices=16, schedules=("iid", "adversarial"),
        aggregators=("decode",), attacks=("sign_flip",),
    )
    kw = dict(dim=DIM, shard="shard_map")
    scenarios.run_grid(rows, STEPS, **kw)  # cold: compiles + caches
    misses0 = engine._grid_program.cache_info().misses

    def _boom(*a, **k):
        raise AssertionError("run_grid(mode='grid') dispatched per-scenario")

    monkeypatch.setattr(scenarios, "run_scenario", _boom)
    scenarios.run_grid(rows, STEPS, **kw)  # warm
    assert engine._grid_program.cache_info().misses == misses0


def test_engine_level_sharded_axes(key):
    """Direct engine.run_grid under shard: batched x0 + batched lr + shared
    data (the axis combinations scenarios.run_grid never produces) must
    match the unsharded call bitwise, including with a non-divisible lane
    count (3)."""
    from repro.data.synthetic import linear_regression_problem

    n = 10
    z, y = linear_regression_problem(key, n=n, dim=DIM, sigma_h=0.3)
    cfg = ProtocolConfig(n_devices=n, d=4, aggregator="cwtm", trim_frac=0.2,
                         n_byz=2, attack=AttackSpec("sign_flip", n_byz=2))
    keys = jnp.stack([jax.random.fold_in(key, i) for i in range(3)])
    x0 = jnp.stack([jnp.zeros((DIM,)), jnp.ones((DIM,)), 0.5 * jnp.ones((DIM,))])
    kw = dict(
        steps=STEPS,
        lr=jnp.array([1e-6, 2e-6, 0.0]),
        data=(z, y),
        data_batched=False,
        x0_batched=True,
        grad_scale=float(n),
        loss_fn=_shared_loss,
    )
    ref = engine.run_grid(cfg, keys, x0, _shared_grads, **kw)
    for shard in SHARDS:
        got = engine.run_grid(cfg, keys, x0, _shared_grads, shard=shard, **kw)
        np.testing.assert_array_equal(np.asarray(got.x), np.asarray(ref.x))
        for k in ref.metrics:
            np.testing.assert_array_equal(
                np.asarray(got.metrics[k]), np.asarray(ref.metrics[k]), err_msg=k
            )
    chunked = engine.run_grid(
        cfg, keys, x0, _shared_grads, shard="shard_map", max_lanes_per_device=1, **kw
    )
    np.testing.assert_array_equal(np.asarray(chunked.x), np.asarray(ref.x))


def _shared_grads(data, x):
    return linreg_subset_grads(data[0], data[1], x)


def _shared_loss(data, x):
    return linreg_loss(data[0], data[1], x)


@given(st.integers(1, 23), st.integers(1, 9), st.integers(1, 4))
@settings(max_examples=30, deadline=None)
def test_lane_padding_replication_property(lanes, devs, mlpd):
    """The padding/replication contract, for arbitrary lane and device
    counts: ``pad_lanes`` up to ``padded_lane_count`` replicates exactly the
    last lane, slices back to the unpadded tree bitwise, and the chunked
    streaming loop of ``run_grid`` covers the lane axis exactly once, in
    order — on every leaf rank."""
    target = padded_lane_count(lanes, devs)
    assert target % devs == 0 and target - lanes < devs and target >= lanes
    rng = np.random.default_rng(lanes * 1000 + devs * 10 + mlpd)
    tree = {
        "mat": jnp.asarray(rng.normal(size=(lanes, 3))),
        "vec": jnp.asarray(rng.normal(size=(lanes,))),
    }
    padded = engine.pad_lanes(tree, target - lanes)
    for k in tree:
        p, o = np.asarray(padded[k]), np.asarray(tree[k])
        assert p.shape[0] == target
        np.testing.assert_array_equal(p[:lanes], o, err_msg=k)
        for row in p[lanes:]:  # every padding lane replicates the last lane
            np.testing.assert_array_equal(row, o[-1], err_msg=k)
    # the chunk loop (run_grid's streaming contract): equal-shaped chunks
    # whose un-padded slices concatenate back to exactly [0, lanes)
    chunk = mlpd * devs
    covered = []
    for start in range(0, lanes, chunk):
        take = min(chunk, lanes - start)
        assert 1 <= take <= chunk
        covered.extend(range(start, start + take))
    assert covered == list(range(lanes))


def test_padded_lane_count_rejects_empty_axis():
    """Zero lanes cannot be made device-divisible by padding: replication
    needs a last lane to copy.  The contract helper and the engine both
    refuse."""
    with pytest.raises(ValueError, match="at least one lane"):
        padded_lane_count(0, 4)
    with pytest.raises(ValueError, match="device count"):
        padded_lane_count(3, 0)


def test_shard_validation():
    rows = scenarios.synthetic_sweep(2, n_devices=10, n_byz=2)
    # "none" and "shard_map" are the only substrates
    for shard in ("gspmd", "pmap"):
        with pytest.raises(ValueError, match="unknown shard mode"):
            scenarios.run_grid(rows, 2, dim=DIM, shard=shard)
    with pytest.raises(ValueError, match="max_lanes_per_device"):
        scenarios.run_grid(rows, 2, dim=DIM, max_lanes_per_device=0)
    # the per-scenario reference modes must refuse (not silently drop) the
    # grid-only sharding options
    with pytest.raises(ValueError, match="grid-mode"):
        scenarios.run_grid(rows, 2, dim=DIM, mode="scan", shard="shard_map")
    with pytest.raises(ValueError, match="grid-mode"):
        scenarios.run_grid(rows, 2, dim=DIM, mode="loop", max_lanes_per_device=1)
    # an empty lane axis is un-paddable (nothing to replicate): the engine
    # refuses instead of emitting a zero-lane program
    cfg = rows[0].protocol()
    empty_keys = jnp.zeros((0, 2), jnp.uint32)
    with pytest.raises(ValueError, match="at least one lane"):
        engine.run_grid(
            cfg, empty_keys, jnp.zeros((DIM,)), _shared_grads,
            steps=2, lr=1e-6, shard="shard_map",
        )


def test_synthetic_sweep_is_single_bucket():
    """The scaling-sweep builder must emit one compile bucket (that is its
    whole point) with unique names and lane-distinct traced axes."""
    rows = scenarios.synthetic_sweep(30, n_devices=16, n_byz=3)
    sigs = {scenarios._bucket_signature(s) for s in rows}
    assert len(sigs) == 1
    names = [s.name for s in rows]
    assert len(set(names)) == len(names)
    assert len({s.attack for s in rows}) == 3
    assert len({(s.lr, s.sigma_h) for s in rows}) == len(rows)
    with pytest.raises(ValueError):
        scenarios.synthetic_sweep(0)
