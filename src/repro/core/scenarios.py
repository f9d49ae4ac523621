"""Declarative scenario registry: method x attack x aggregator x compressor
x heterogeneity, one row per experimental condition.

The paper's Section VII (and the grids of DRACO [13] and the compressed-
momentum line of work) evaluate over a *matrix* of conditions.  Before this
module every benchmark hand-wired its own handful of ``ProtocolConfig``s;
now a single ``Scenario`` row names a full condition and every consumer —
``benchmarks/paper_figures.py``, ``benchmarks/run.py``, the sweep example,
the engine tests — drives the scan-compiled engine from the same table.

Entry points:
  * ``Scenario``            — one declarative row; ``.protocol()`` lowers it
                              to the engine's ``ProtocolConfig``.
  * ``section7_grid()``     — the paper's comparison grid as a cartesian
                              product (>= 3 methods x >= 3 attacks x >= 2
                              compressors by default).
  * ``PAPER_FIG4/5/6``      — the exact named curves of Figs. 4-6.
  * ``run_scenario()``      — scenario -> scan-compiled trajectory on the
                              Section-VII linear-regression problem.
  * ``run_grid()``          — whole-grid on-device: scenarios are grouped
                              into compile buckets by their *static* protocol
                              structure and each bucket runs as ONE vmapped
                              scan (``engine.run_grid``); per-lane results are
                              bit-identical to ``run_scenario``.
  * ``lm_sweep()``          — the same matrix at LM scale: every lane trains
                              a small transformer (its flattened parameter
                              vector is the engine iterate) through the
                              identical protocol pipeline.
  * ``run_lm_grid()`` /     — the LM-scale twins of ``run_grid`` /
    ``run_lm_scenario()``     ``run_scenario`` (shared heterogeneous-LM data
                              per bucket, transformer gradients per subset).
  * ``grid_finals()``       — flatten a grid result to per-scenario final
                              metrics (the benchmark CSV row format).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp

from repro.core import engine as engine_lib
from repro.core.attacks import AttackSpec
from repro.core.byzantine import ProtocolConfig, make_attack_fn, make_server_fn
from repro.core.coding import erasure_margin
from repro.core.compression import CompressionSpec, spec_from
from repro.core.participation import ParticipationSpec
from repro.core.engine import TrajectoryResult, run_trajectory
from repro.data.synthetic import (
    linear_regression_problem,
    linreg_loss,
    linreg_subset_grads,
    lm_batch_for_devices,
)

__all__ = [
    "Scenario",
    "section7_grid",
    "synthetic_sweep",
    "participation_sweep",
    "fleet_chaos_cases",
    "fleet_comlad_cases",
    "scenario_name",
    "PAPER_FIG4",
    "PAPER_FIG5",
    "PAPER_FIG6",
    "run_scenario",
    "run_grid",
    "grid_compiled_hlo",
    "lm_arch",
    "lm_sweep",
    "run_lm_scenario",
    "run_lm_grid",
    "ZOO_FAMILIES",
    "zoo_arch",
    "zoo_sweep",
    "run_zoo_sweep",
    "grid_finals",
]


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One experimental condition of the evaluation matrix."""

    name: str
    method: str = "lad"  # lad | plain | draco
    d: int = 1  # computational load (ignored for plain)
    aggregator: str = "cwtm"
    attack: str = "sign_flip"
    n_byz: int = 20
    compressor: str = "none"  # none | rand_sparse | rand_sparse_shared | quant | top_k
    q_hat_frac: float = 0.3
    quant_levels: int = 16
    sigma_h: float = 0.3  # data-heterogeneity level of the linreg problem
    trim_frac: float = 0.1
    n_devices: int = 100
    lr: float = 1e-6
    backend: str = "xla"  # kernels/ops backend for the protocol hot path
    # participation / straggler fault model (core/participation.py):
    # "full" (default) | "iid" | "onoff" | "adversarial" | "markov"
    participation: str = "full"
    p_rate: float = 0.0  # iid per-round drop probability
    p_drop_n: int = 0  # erased/straggler device count (onoff / adversarial)
    p_period: int = 4  # onoff duty-cycle window (rounds)
    p_duty: float = 0.5  # onoff fraction of the window a straggler reports

    def protocol(self) -> ProtocolConfig:
        return ProtocolConfig(
            n_devices=self.n_devices,
            d=self.d,
            method=self.method,
            aggregator=self.aggregator,
            trim_frac=self.trim_frac,
            n_byz=self.n_byz,
            attack=AttackSpec(self.attack, n_byz=self.n_byz),
            # spec_from accepts both the bare legacy name and the registry
            # spelling ("quant:8"), so scenario rows share the fleet's grammar
            compression=spec_from(
                self.compressor, q_hat_frac=self.q_hat_frac, levels=self.quant_levels
            ),
            participation=ParticipationSpec(
                self.participation,
                rate=self.p_rate,
                n_drop=self.p_drop_n,
                period=self.p_period,
                duty=self.p_duty,
                # worst-case erasure hits honest rows: the Byzantine block
                # (rows [0, n_byz) under fixed identities) keeps reporting
                offset=self.n_byz if self.participation == "adversarial" else 0,
            ),
            backend=self.backend,
        )


def scenario_name(
    method: str, d: int, aggregator: str, attack: str, compressor: str, sigma_h: float
) -> str:
    comp = "" if compressor == "none" else f"/{compressor}"
    return f"{method}-d{d}/{aggregator}/{attack}{comp}/s{sigma_h:g}"


def section7_grid(
    methods: Sequence[tuple[str, int]] = (("plain", 1), ("lad", 10), ("draco", 4)),
    attacks: Sequence[str] = ("sign_flip", "alie", "ipm"),
    aggregators: Sequence[str] = ("cwtm",),
    compressors: Sequence[str] = ("none", "rand_sparse"),
    sigma_levels: Sequence[float] = (0.3,),
    n_devices: int = 100,
    n_byz: int = 20,
    lr: float = 1e-6,
) -> list[Scenario]:
    """The paper's Section-VII comparison grid as a flat scenario list.

    Defaults give 3 methods x 3 attacks x 2 compressors (x 1 aggregator x 1
    heterogeneity level) = 18 conditions.  Combinations the paper rules out
    are dropped rather than generated: DRACO is incompatible with compression
    (Section VII.B), so draco rows only appear with ``compressor="none"``,
    and its ``N`` is rounded down to a multiple of ``d`` (fractional
    repetition needs d | N).

    Under ``run_grid`` the resulting 15 rows collapse into 5 compile buckets
    (method x compressor; the attack axis is traced), each a single vmapped
    on-device program.
    """
    rows = []
    seen = set()
    for method, d in methods:
        for attack in attacks:
            for agg in aggregators:
                for comp in compressors:
                    if method == "draco" and comp != "none":
                        continue
                    for sigma in sigma_levels:
                        n = n_devices - (n_devices % d) if method == "draco" else n_devices
                        # DRACO decodes by majority vote — the aggregator axis
                        # collapses to its vote ("mean" post-decode), so emit
                        # one honestly-named row instead of per-agg duplicates
                        agg_eff = "vote" if method == "draco" else agg
                        name = scenario_name(method, d, agg_eff, attack, comp, sigma)
                        if name in seen:
                            continue
                        seen.add(name)
                        rows.append(
                            Scenario(
                                name=name,
                                method=method,
                                d=d,
                                aggregator="mean" if method == "draco" else agg,
                                attack=attack,
                                n_byz=n_byz,
                                compressor=comp,
                                sigma_h=sigma,
                                n_devices=n,
                                lr=lr,
                            )
                        )
    return rows


def _fig4(label: str, method: str, d: int, agg: str, **kw) -> Scenario:
    return Scenario(name=label, method=method, d=d, aggregator=agg,
                    attack="sign_flip", n_byz=20, sigma_h=0.3, lr=1e-6, **kw)


# Fig. 4: training loss under sign-flip(-2), H=80, sigma_H=0.3.
PAPER_FIG4 = {
    "VA": _fig4("VA", "plain", 1, "mean"),
    "CWTM": _fig4("CWTM", "plain", 1, "cwtm"),
    "CWTM-NNM": _fig4("CWTM-NNM", "plain", 1, "cwtm-nnm"),
    "LAD-CWTM-d5": _fig4("LAD-CWTM-d5", "lad", 5, "cwtm"),
    "LAD-CWTM-d10": _fig4("LAD-CWTM-d10", "lad", 10, "cwtm"),
    "LAD-CWTM-d20": _fig4("LAD-CWTM-d20", "lad", 20, "cwtm"),
    "LAD-CWTM-NNM-d10": _fig4("LAD-CWTM-NNM-d10", "lad", 10, "cwtm-nnm"),
    "DRACO-d41": _fig4("DRACO-d41", "draco", 41, "mean", n_devices=82),
}

# Fig. 5: heterogeneity sweep — the LAD advantage grows with sigma_H.
PAPER_FIG5 = {
    f"{label}-s{sigma:g}": Scenario(
        name=f"{label}-s{sigma:g}", method=method, d=d, aggregator="cwtm",
        attack="sign_flip", n_byz=20, sigma_h=sigma, lr=1e-6,
    )
    for sigma in (0.0, 0.1)
    for label, method, d in (("CWTM", "plain", 1), ("LAD-CWTM-d10", "lad", 10))
}


def _fig6(label: str, method: str, d: int, agg: str) -> Scenario:
    return Scenario(name=label, method=method, d=d, aggregator=agg,
                    attack="sign_flip", n_byz=30, compressor="rand_sparse",
                    q_hat_frac=0.3, sigma_h=0.3, lr=3e-7)


# Fig. 6: compressed communication — random sparsification Q_hat=30, H=70, d=3.
PAPER_FIG6 = {
    "Com-VA": _fig6("Com-VA", "plain", 1, "mean"),
    "Com-CWTM": _fig6("Com-CWTM", "plain", 1, "cwtm"),
    "Com-CWTM-NNM": _fig6("Com-CWTM-NNM", "plain", 1, "cwtm-nnm"),
    "Com-TGN": _fig6("Com-TGN", "plain", 1, "tgn"),
    "Com-LAD-CWTM": _fig6("Com-LAD-CWTM", "lad", 3, "cwtm"),
    "Com-LAD-CWTM-NNM": _fig6("Com-LAD-CWTM-NNM", "lad", 3, "cwtm-nnm"),
}


def run_scenario(
    scn: Scenario,
    steps: int,
    *,
    seed: int = 0,
    problem: tuple[jax.Array, jax.Array] | None = None,
    dim: int = 100,
    mode: str = "scan",
    with_sol_err: bool = False,
) -> TrajectoryResult:
    """Run one scenario on the Section-VII linear-regression problem through
    the scan-compiled engine.

    ``problem``: optionally share one ``(Z, y)`` across scenarios (figure
    curves compare on identical data); it is truncated to ``scn.n_devices``
    subsets (the DRACO rows use N=82 of the common N=100 problem).
    """
    z, y = _lane_problem(scn, seed=seed, problem=problem, dim=dim)
    x_star = None
    if with_sol_err:
        x_star, *_ = jnp.linalg.lstsq(z, y)
    return run_trajectory(
        scn.protocol(),
        jax.random.PRNGKey(seed),
        jnp.zeros((z.shape[1],)),
        _grid_subset_grads,  # module-level + data operand: stable program-cache key
        steps=steps,
        lr=scn.lr,
        # the engine's aggregate estimates (1/N) grad F; eq. (7) steps on F
        grad_scale=float(scn.n_devices),
        loss_fn=_grid_loss,
        x_star=x_star,
        mode=mode,
        data=(z, y),
    )


def _lane_problem(scn: Scenario, *, seed: int, problem, dim: int):
    """The (Z, y) data a scenario trains on — shared-and-truncated or
    freshly generated at the scenario's own heterogeneity level.  One code
    path for ``run_scenario`` and the grid lanes keeps them bit-identical."""
    if problem is None:
        return linear_regression_problem(
            jax.random.PRNGKey(seed), n=scn.n_devices, dim=dim, sigma_h=scn.sigma_h
        )
    z, y = problem
    if z.shape[0] < scn.n_devices:
        raise ValueError(
            f"shared problem has {z.shape[0]} subsets < n_devices="
            f"{scn.n_devices} of scenario {scn.name!r} (truncation only "
            f"shrinks, and out-of-bounds gathers would clamp silently)"
        )
    return z[: scn.n_devices], y[: scn.n_devices]


def _bucket_signature(scn: Scenario, exact: bool = True) -> tuple:
    """Everything that changes *compiled structure*: scenarios agreeing on
    this tuple share shapes and static protocol wiring, so they can ride the
    same vmapped program; attack / lr / sigma_h always stay per-lane.

    ``exact=True`` (the default) additionally pins the aggregator per bucket.
    A per-lane *server* switch is supported by the engine, but on the CPU
    backend the fused multiply-add clustering around the switch differs from
    the single-scenario program by ~1 ulp — keeping the aggregator static is
    what upgrades "allclose" to the bit-exactness guarantee.  (The *attack*
    switch shows no such drift and is always per-lane.)
    """
    return (
        scn.method,
        scn.d,
        scn.n_devices,
        scn.n_byz,
        scn.trim_frac,
        scn.compressor,
        scn.q_hat_frac,
        scn.quant_levels,
        scn.backend,
        # the participation schedule is static protocol structure: an active
        # schedule widens the scan carry and switches the server signature,
        # so rows differing here cannot share a compiled program
        scn.participation,
        scn.p_rate,
        scn.p_drop_n,
        scn.p_period,
        scn.p_duty,
    ) + ((scn.aggregator,) if exact else ())


@dataclasses.dataclass(frozen=True)
class _BucketProblem:
    """What one compile bucket trains on — the problem adapter that lets the
    linear-regression grid and the LM-scale grid share the whole bucketing /
    branch-table / sharding machinery of ``_run_bucket``.

    ``subset_grad_fn`` / ``loss_fn`` must be module-level (or lru-cached)
    callables: their identities key the engine's compiled-program cache.
    """

    subset_grad_fn: Callable[[Any, jax.Array], jax.Array]
    loss_fn: Callable[[Any, jax.Array], jax.Array]
    x0: jax.Array
    data: Any
    data_batched: bool
    grad_scale: float
    optimizer: str = "sgd"


def _bucket_engine_args(
    group: list[Scenario], prob: _BucketProblem, *, seed: int
) -> tuple[ProtocolConfig, jax.Array, dict]:
    """The ``engine.run_grid`` call of one compile bucket: template config,
    stacked lane keys and the full kwargs dict (branch tables, traced ids,
    per-lane lr, the problem adapter's operands).  Shared by ``_run_bucket``
    and ``grid_compiled_hlo`` so roofline introspection lowers the exact
    program the sweep runs."""
    tmpl = group[0].protocol()
    attack_names = list(dict.fromkeys(s.attack for s in group))
    agg_names = list(dict.fromkeys(s.aggregator for s in group))
    attack_branches = tuple(
        make_attack_fn(
            dataclasses.replace(tmpl, attack=AttackSpec(a, n_byz=tmpl.n_byz))
        )
        for a in attack_names
    )
    server_branches = tuple(
        make_server_fn(dataclasses.replace(tmpl, aggregator=g)) for g in agg_names
    )
    attack_ids = (
        None
        if len(attack_names) == 1
        else jnp.array([attack_names.index(s.attack) for s in group], jnp.int32)
    )
    server_ids = (
        None
        if len(agg_names) == 1
        else jnp.array([agg_names.index(s.aggregator) for s in group], jnp.int32)
    )
    lrs = [s.lr for s in group]
    lr = lrs[0] if len(set(lrs)) == 1 else jnp.array(lrs, jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(seed)] * len(group))
    kwargs = dict(
        lr=lr,
        data=prob.data,
        data_batched=prob.data_batched,
        attack_branches=attack_branches,
        attack_ids=attack_ids,
        server_branches=server_branches,
        server_ids=server_ids,
        optimizer=prob.optimizer,
        grad_scale=prob.grad_scale,
        loss_fn=prob.loss_fn,
    )
    return tmpl, keys, kwargs


def _run_bucket(
    group: list[Scenario],
    steps: int,
    prob: _BucketProblem,
    *,
    seed: int,
    shard: str = "none",
    max_lanes_per_device: int | str | None = None,
) -> dict[str, TrajectoryResult]:
    """One compile bucket -> one vmapped ``engine.run_grid`` call."""
    tmpl, keys, kwargs = _bucket_engine_args(group, prob, seed=seed)
    res = engine_lib.run_grid(
        tmpl,
        keys,
        prob.x0,
        prob.subset_grad_fn,
        steps=steps,
        shard=shard,
        max_lanes_per_device=max_lanes_per_device,
        **kwargs,
    )
    return {s.name: res.lane(i) for i, s in enumerate(group)}


def grid_compiled_hlo(
    scenarios: Iterable[Scenario],
    steps: int,
    *,
    seed: int = 0,
    problem: tuple[jax.Array, jax.Array] | None = None,
    dim: int = 100,
    exact: bool = True,
    shard: str = "none",
    max_lanes_per_device: int | str | None = None,
) -> str:
    """Optimized HLO of the single compiled chunk program a same-arguments
    ``run_grid`` call executes — the scenario-level face of
    ``engine.grid_compiled_hlo`` (the roofline %-of-peak hook).

    The scenario list must collapse into ONE compile bucket (e.g. a
    ``synthetic_sweep``): a multi-bucket sweep has one program per bucket and
    no single module to analyze.
    """
    scns = list(scenarios)
    buckets: dict[tuple, list[Scenario]] = {}
    for s in scns:
        buckets.setdefault(_bucket_signature(s, exact=exact), []).append(s)
    if len(buckets) != 1:
        raise ValueError(
            f"grid_compiled_hlo needs a single compile bucket, got "
            f"{len(buckets)} — analyze each bucket's scenario subset separately"
        )
    (group,) = buckets.values()
    prob = _linreg_bucket_problem(group, seed=seed, problem=problem, dim=dim)
    tmpl, keys, kwargs = _bucket_engine_args(group, prob, seed=seed)
    return engine_lib.grid_compiled_hlo(
        tmpl,
        keys,
        prob.x0,
        prob.subset_grad_fn,
        steps=steps,
        shard=shard,
        max_lanes_per_device=max_lanes_per_device,
        **kwargs,
    )


def _linreg_bucket_problem(
    group: list[Scenario], *, seed: int, problem, dim: int
) -> _BucketProblem:
    """The Section-VII linear-regression problem of one compile bucket."""
    if problem is not None:
        data = _lane_problem(group[0], seed=seed, problem=problem, dim=dim)
        data_batched = False
    else:
        lanes = [_lane_problem(s, seed=seed, problem=None, dim=dim) for s in group]
        data = tuple(jnp.stack(parts) for parts in zip(*lanes))
        data_batched = True
    q = data[0].shape[-1]
    return _BucketProblem(
        subset_grad_fn=_grid_subset_grads,  # module-level: stable identity
        loss_fn=_grid_loss,
        x0=jnp.zeros((q,)),
        data=data,
        data_batched=data_batched,
        # the engine's aggregate estimates (1/N) grad F; eq. (7) steps on F
        grad_scale=float(group[0].n_devices),
    )


def _grid_subset_grads(data, x):
    z, y = data
    return linreg_subset_grads(z, y, x)


def _grid_loss(data, x):
    z, y = data
    return linreg_loss(z, y, x)


def run_grid(
    scenarios: Iterable[Scenario],
    steps: int,
    *,
    seed: int = 0,
    problem: tuple[jax.Array, jax.Array] | None = None,
    dim: int = 100,
    mode: str = "grid",
    exact: bool = True,
    shard: str = "none",
    max_lanes_per_device: int | str | None = None,
) -> dict[str, TrajectoryResult]:
    """Sweep scenarios through the engine; returns ``{name: TrajectoryResult}``
    in input order (use ``grid_finals`` for the final-metric summary).

    ``mode="grid"`` (default) is the whole-grid on-device path: scenarios are
    grouped into compile buckets by their static structure (method, d, N,
    compressor sizes, backend, aggregator) and each bucket executes as a
    single vmapped+scanned program, with the attack axis dispatched per lane
    via ``lax.switch``.  The default ``section7_grid()`` (15 cells) compiles
    5 programs instead of 15 and makes zero per-scenario Python dispatches.
    Every lane is **bit-identical** to running its scenario alone (tests
    assert equality against ``mode="scan"``/``"loop"``).

    ``exact=False`` additionally dispatches the *aggregator* per lane (fewest
    possible compiles — e.g. all of ``PAPER_FIG6`` in 2 programs), at the
    cost of weakening bit-exactness to ~1-ulp agreement: the CPU backend
    clusters fused multiply-adds around the server switch differently than
    in the single-scenario program.

    Kernel backends (``backend="interpret"``/``"pallas"``) ride the exact
    same path: the ops wrappers batch every Pallas kernel over scenario
    lanes (``jax.custom_vmap`` maps the engine's lane vmap onto the kernels'
    2-D ``(lane, q_tile)`` grid — see ``kernels/ops.py``), so a kernel
    bucket compiles to the same lru-cached one-program-per-bucket form as an
    XLA bucket: zero per-scenario dispatches on a warm sweep, every lane
    bitwise equal to its standalone trajectory.

    ``shard="shard_map"`` partitions every compile bucket's lane
    axis over the visible devices (lane counts padded to a device multiple;
    see ``engine.run_grid``), and ``max_lanes_per_device`` streams a large
    bucket through equal-sized chunks of one cached program — together they
    are what makes 1000+-row sweeps practical.  Both keep every lane bitwise
    equal to the unsharded grid at the clean simulation scales.
    ``max_lanes_per_device="auto"`` delegates the capacity choice to
    ``repro.launch.tuner`` (probed once per bucket signature, cached on
    disk; bitwise-equal to any hand-picked value).

    ``mode="scan"`` / ``mode="loop"`` fall back to one ``run_scenario`` call
    per row (the bit-exactness references).
    """
    scns = list(scenarios)
    if mode in ("scan", "loop"):
        if shard != "none" or max_lanes_per_device is not None:
            # the per-scenario reference paths have no lane axis to shard;
            # silently dropping the flags would hand back an unsharded
            # "reference" timing that was never sharded in the first place
            raise ValueError(
                f"shard={shard!r} / max_lanes_per_device="
                f"{max_lanes_per_device!r} are grid-mode options; "
                f"mode={mode!r} dispatches per scenario"
            )
        return {
            s.name: run_scenario(s, steps, seed=seed, problem=problem, dim=dim, mode=mode)
            for s in scns
        }
    if mode != "grid":
        raise ValueError(f"unknown grid mode {mode!r}")
    buckets: dict[tuple, list[Scenario]] = {}
    for s in scns:
        buckets.setdefault(_bucket_signature(s, exact=exact), []).append(s)
    out: dict[str, TrajectoryResult] = {}
    for group in buckets.values():
        prob = _linreg_bucket_problem(group, seed=seed, problem=problem, dim=dim)
        out.update(
            _run_bucket(
                group, steps, prob, seed=seed,
                shard=shard, max_lanes_per_device=max_lanes_per_device,
            )
        )
    return {s.name: out[s.name] for s in scns}


def synthetic_sweep(
    n_rows: int,
    *,
    method: str = "lad",
    d: int = 4,
    aggregator: str = "cwtm",
    n_devices: int = 16,
    n_byz: int = 3,
    attacks: Sequence[str] = ("sign_flip", "alie", "ipm"),
    compressor: str = "none",
    base_lr: float = 1e-5,
    backend: str = "xla",
) -> list[Scenario]:
    """A single-compile-bucket scenario list of arbitrary size — the workload
    of the sharded-grid scaling studies (1000+-row sweeps).

    Every row shares the full static protocol structure (method, d, N,
    compressor, backend, aggregator), so the whole sweep rides ONE vmapped
    program however long it is; rows vary only along the traced axes — the
    attack (cycled), the learning rate and the data's heterogeneity level
    (both swept densely), so every lane is a distinct trajectory.
    """
    if n_rows < 1:
        raise ValueError(f"n_rows must be >= 1, got {n_rows}")
    rows = []
    for i in range(n_rows):
        frac = i / max(1, n_rows - 1)
        attack = attacks[i % len(attacks)]
        rows.append(
            Scenario(
                name=f"syn{i:05d}/{attack}",
                method=method,
                d=d,
                aggregator=aggregator,
                attack=attack,
                n_byz=n_byz,
                compressor=compressor,
                sigma_h=round(0.05 + 0.45 * frac, 6),
                n_devices=n_devices,
                lr=base_lr * (0.5 + frac),
                backend=backend,
            )
        )
    return rows


def participation_sweep(
    *,
    method: str = "lad",
    d: int = 4,
    n_devices: int = 16,
    n_byz: int = 0,
    schedules: Sequence[str] = ("iid", "onoff", "adversarial"),
    aggregators: Sequence[str] = ("decode", "mean"),
    attacks: Sequence[str] = ("sign_flip",),
    rate: float = 0.25,
    n_drop: int | None = None,
    period: int = 4,
    duty: float = 0.5,
    base_lr: float = 1e-5,
    backend: str = "xla",
) -> list[Scenario]:
    """The partial-participation / straggler row-family: schedule x
    aggregator x attack over the cyclic code at redundancy margin
    ``s = erasure_margin(d) = d - 1``.

    ``n_drop`` (erased/straggler devices for the deterministic schedules)
    defaults to the full margin ``s`` — the worst erasure pattern the code
    still decodes exactly.  The default aggregator pair is the benchmark
    contrast: ``"decode"`` (the K-of-N erasure decode — *recovered*) vs
    ``"mean"`` (erased rows imputed, no code exploited — *undefended*
    against erasure bias).  Each (schedule, aggregator) pair is its own
    compile bucket (an active schedule is static protocol structure); the
    attack axis stays traced per lane as everywhere else.
    """
    if method == "draco":
        raise ValueError(
            "participation_sweep targets the cyclic code; DRACO has its own "
            "masked group decoder (set aggregator rows on a draco grid instead)"
        )
    if n_devices % d != 0:
        raise ValueError(
            f"participation rows need d | N (the erasure decode's offset "
            f"classes must tile the subset circle): N={n_devices} d={d}"
        )
    drop = erasure_margin(d) if n_drop is None else n_drop
    rows = []
    for i_s, sched in enumerate(schedules):
        if sched not in ("iid", "onoff", "adversarial", "markov"):
            raise ValueError(
                f"unknown participation schedule {sched!r} for a sweep row "
                "('full' rows are just the plain grid; 'external' is fleet-only)"
            )
        for agg in aggregators:
            for i_a, attack in enumerate(attacks):
                rows.append(
                    Scenario(
                        name=f"part/{sched}/{agg}/{attack}",
                        method=method,
                        d=d,
                        aggregator=agg,
                        attack=attack,
                        n_byz=n_byz,
                        n_devices=n_devices,
                        lr=base_lr * (1.0 + 0.1 * i_a),
                        backend=backend,
                        participation=sched,
                        p_rate=rate,
                        p_drop_n=drop,
                        p_period=period,
                        p_duty=duty,
                    )
                )
    return rows


def fleet_chaos_cases(procs: int = 3, steps: int = 8) -> list[dict]:
    """The fleet's chaos-conformance row-family: one seeded fault schedule
    per failure mode of the self-healing transport (``launch/chaos.py``).

    Declarative plain-data rows (no launch import — the registry stays
    engine-side): each case is ``{"name", "chaos", "within_margin"}`` where
    ``chaos`` is a ``launch.chaos.parse_chaos`` schedule dict.  Every
    default case keeps per-round erasures within ``erasure_margin(d)`` for
    the bench's N=6 / d=3 / 2-rows-per-block geometry — one faulted worker
    block is exactly the margin — so the K-of-N decode keeps recovering the
    full gradient and the final loss must sit inside the erasure-decode
    envelope (``benchmarks/fleet_bench.py`` asserts it).

    ``partition_rejoin`` pads every round with a small honest ``delay`` on
    worker 1 so the round cadence is slow enough for worker ``procs-1``'s
    0.5 s partition to heal while training is still running — the rejoin
    path is the subject under test, not a race.
    """
    if procs < 3:
        raise ValueError(f"chaos cases need >= 2 workers (procs >= 3), got {procs}")
    w1, w2 = 1, procs - 1
    return [
        {"name": "healthy", "within_margin": True,
         "chaos": {"seed": 0, "faults": []}},
        {"name": "dup", "within_margin": True,
         "chaos": {"seed": 1, "faults": [
             {"op": "dup", "proc": w1, "rounds": [1, 2, 3]}]}},
        {"name": "corrupt", "within_margin": True,
         "chaos": {"seed": 2, "faults": [
             {"op": "corrupt", "proc": w2, "rounds": [2, 3]}]}},
        {"name": "drop", "within_margin": True,
         "chaos": {"seed": 3, "faults": [
             {"op": "drop", "proc": w2, "rounds": [2]}]}},
        {"name": "delay", "within_margin": True,
         "chaos": {"seed": 4, "faults": [
             {"op": "delay", "proc": w1, "rounds": [1, 2], "arg": 0.2}]}},
        {"name": "partition_rejoin", "within_margin": True,
         "chaos": {"seed": 5, "faults": [
             {"op": "delay", "proc": w1, "rounds": list(range(steps)), "arg": 0.25},
             {"op": "partition", "proc": w2, "rounds": [2], "arg": 0.5}]}},
    ]


def fleet_comlad_cases(procs: int = 3, steps: int = 8) -> list[dict]:
    """The fleet's Com-LAD-over-the-wire row family: one case per uplink
    compression spec, measured on the real TCP data plane.

    Declarative plain-data rows (no launch import): each case is
    ``{"name", "compress", "min_ratio", "within_envelope"}``.  ``compress``
    is the registry spelling (``CompressionSpec.parse``); ``min_ratio`` is
    the minimum measured uplink bytes/round reduction vs the identity case
    that ``benchmarks/fleet_bench.py`` enforces; ``within_envelope`` asserts
    the final loss lands within the erasure-decode envelope of the identity
    fleet — claimed only for identity and quant (the sparse family at 25%
    keep has 4x-scaled unbiased variance, and top_k is biased, so their
    trajectories legitimately drift beyond float noise).  The headline
    row is ``quant4`` — the paper's 4-level QSGD at >= 4x fewer uplink
    bytes/round.  ``quant4_chaos_byz`` additionally runs the compressed
    uplink under ``byz_payload`` + ``corrupt`` chaos faults: both must land
    as tallied per-round erasures of the compressed frames, never a crash.
    """
    if procs < 3:
        raise ValueError(f"comlad cases need >= 2 workers (procs >= 3), got {procs}")
    w1, w2 = 1, procs - 1
    return [
        {"name": "identity", "compress": "identity",
         "min_ratio": 1.0, "within_envelope": True, "chaos": None},
        {"name": "quant4", "compress": "quant:4",
         "min_ratio": 4.0, "within_envelope": True, "chaos": None},
        {"name": "quant8", "compress": "quant:8",
         "min_ratio": 3.0, "within_envelope": True, "chaos": None},
        {"name": "randk16", "compress": "randk:16",
         "min_ratio": 1.5, "within_envelope": False, "chaos": None},
        {"name": "randk_shared16", "compress": "randk_shared:16",
         "min_ratio": 1.5, "within_envelope": False, "chaos": None},
        {"name": "topk16", "compress": "topk:16",
         "min_ratio": 1.5, "within_envelope": False, "chaos": None},
        {"name": "quant4_chaos_byz", "compress": "quant:4",
         "min_ratio": 0.0, "within_envelope": False,
         "chaos": {"seed": 6, "faults": [
             {"op": "byz_payload", "proc": w1, "rounds": [2, 3]},
             {"op": "corrupt", "proc": w2, "rounds": [3]}]}},
    ]


@functools.lru_cache(maxsize=1)
def lm_arch():
    """The default small transformer of the LM-scale engine sweeps: 1 layer,
    d_model=32, vocab=64 — big enough to exercise every model subsystem
    (attention, SwiGLU, RMSNorm, tied unembed CE), small enough that a whole
    method x attack x aggregator x compressor matrix of *trajectories* runs
    on one CPU core in seconds.  lru-cached so every caller shares one
    config object (and with it the lru-cached problem fns below)."""
    from repro.configs.archs import ARCHS, reduced

    return reduced(ARCHS["smollm-360m"]).scaled(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, head_dim=16,
        d_ff=64, vocab=64,
    )


@functools.lru_cache(maxsize=16)
def _lm_fns(arch):
    """(x0, subset_grad_fn, loss_fn) of the LM problem for one architecture.

    The engine iterate is the transformer's FLATTENED fp32 parameter vector:
    ``subset_grad_fn`` unflattens it, computes every subset's full-model
    gradient (``jax.vmap`` over the N data subsets) and flattens each back to
    a row of the ``(N, P)`` stack ``protocol_round`` consumes — exactly the
    ``launch.train.build_train_step`` pipeline, realized as a grid lane.
    lru-cached so the returned callables have stable identities: they key the
    engine's compiled-program cache (zero warm compiles across sweeps).
    (maxsize covers the whole ``ZOO_FAMILIES`` zoo at once.)

    Frontend-bearing families (vlm / audio) train on a 3-tuple ``data`` —
    ``(tokens, labels, frontend)`` — which the engine threads through
    unchanged as a runtime pytree operand; everything else keeps the 2-tuple.
    """
    from repro import models
    from repro.core.coding import flatten_pytree, unflatten_pytree

    params0, specs = models.init(jax.random.PRNGKey(0), arch)
    params0 = jax.tree.map(lambda a: a.astype(jnp.float32), params0)
    x0, flat_spec = flatten_pytree(params0)
    has_frontend = arch.family in ("vlm", "audio")

    def _unpack(data):
        if has_frontend:
            return data  # (tokens, labels, frontend)
        tokens, labels = data
        return tokens, labels, None

    def lm_subset_grads(data, x):
        tokens, labels, frontend = _unpack(data)  # leaves (N, rows, ...)
        params = unflatten_pytree(x, flat_spec)

        def one(sub_batch):
            def lf(pp):
                loss, _ = models.loss_fn(pp, specs, arch, sub_batch, remat=False)
                return loss

            flat, _ = flatten_pytree(jax.grad(lf)(params))
            return flat

        batch = {"tokens": tokens, "labels": labels}
        if has_frontend:
            batch["frontend"] = frontend
        return jax.vmap(one)(batch)

    def lm_loss(data, x):
        tokens, labels, frontend = _unpack(data)
        params = unflatten_pytree(x, flat_spec)
        batch = {
            "tokens": tokens.reshape((-1,) + tokens.shape[2:]),
            "labels": labels.reshape((-1,) + labels.shape[2:]),
        }
        if has_frontend:
            batch["frontend"] = frontend.reshape((-1,) + frontend.shape[2:])
        loss, _ = models.loss_fn(params, specs, arch, batch, remat=False)
        return loss

    return x0, lm_subset_grads, lm_loss


# _lm_fns pins x0 + each closure's captured parameter template on device for
# the process lifetime — exactly the footprint engine.clear_program_caches
# exists to release, so it rides the same registry.  (Clearing changes the
# callables' identities, which correctly also invalidates any grid program
# cached on them.)
engine_lib.register_program_cache(
    "scenarios.lm_fns", _lm_fns.cache_clear,
    lambda: _lm_fns.cache_info().currsize,
)


def _lm_problem(arch, *, seed: int, n_subsets: int, sigma_h: float,
                per_subset: int, seq_len: int):
    """The shared heterogeneous-LM data of one bucket: ``(tokens, labels)``
    with ``(N, per_subset, seq_len)`` leaves (see ``data.synthetic``).  For
    frontend-bearing archs (vlm / audio) a third leaf carries the stub
    modality embeddings, ``(N, per_subset, n_frontend_tokens, d_frontend)``,
    drawn deterministically from a fold of the same seed."""
    batch = lm_batch_for_devices(
        jax.random.PRNGKey(seed), arch.vocab, n_subsets=n_subsets,
        per_subset=per_subset, seq_len=seq_len, sigma_h=sigma_h,
    )
    data = (batch["tokens"], batch["labels"])
    if arch.family in ("vlm", "audio"):
        enc = arch.encoder
        frontend = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), 7),
            (n_subsets, per_subset, enc.n_frontend_tokens, enc.d_frontend),
            dtype=jnp.float32,
        )
        data = data + (frontend,)
    return data


def lm_sweep(
    methods: Sequence[tuple[str, int]] = (("lad", 2), ("plain", 1)),
    attacks: Sequence[str] = ("sign_flip", "alie", "ipm"),
    aggregators: Sequence[str] = ("cwtm",),
    compressors: Sequence[str] = ("none", "rand_sparse"),
    *,
    n_devices: int = 10,
    n_byz: int = 2,
    sigma_h: float = 0.5,
    q_hat_frac: float = 0.5,
    trim_frac: float = 0.2,
    lr: float = 3e-3,
    backend: str = "xla",
) -> list[Scenario]:
    """The LM-scale evaluation matrix: method x attack x aggregator x
    compressor over a small transformer (``lm_arch`` by default).

    Mirrors ``section7_grid``'s pruning (DRACO rows drop compression and
    round ``N`` down to a ``d``-multiple).  All rows share ``sigma_h`` —
    ``run_lm_grid`` trains every bucket on ONE shared heterogeneous-LM
    problem per ``(N, sigma_h)``, so lanes differ along the traced protocol
    axes, not the data.  The default 12 rows collapse into 4 compile buckets
    (method x compressor; attacks traced per lane).
    """
    rows = []
    seen = set()
    for method, d in methods:
        for attack in attacks:
            for agg in aggregators:
                for comp in compressors:
                    if method == "draco" and comp != "none":
                        continue
                    n = n_devices - (n_devices % d) if method == "draco" else n_devices
                    agg_eff = "vote" if method == "draco" else agg
                    name = "lm/" + scenario_name(method, d, agg_eff, attack, comp, sigma_h)
                    if name in seen:
                        continue
                    seen.add(name)
                    rows.append(
                        Scenario(
                            name=name,
                            method=method,
                            d=d,
                            aggregator="mean" if method == "draco" else agg,
                            attack=attack,
                            n_byz=n_byz,
                            compressor=comp,
                            q_hat_frac=q_hat_frac,
                            sigma_h=sigma_h,
                            trim_frac=trim_frac,
                            n_devices=n,
                            lr=lr,
                            backend=backend,
                        )
                    )
    return rows


def run_lm_scenario(
    scn: Scenario,
    steps: int,
    *,
    arch=None,
    seed: int = 0,
    per_subset: int = 2,
    seq_len: int = 16,
    mode: str = "scan",
) -> TrajectoryResult:
    """One LM-scale scenario through the scan-compiled engine — the
    per-scenario bit-exactness reference of ``run_lm_grid`` (the same role
    ``run_scenario`` plays for the linear-regression grid)."""
    arch = arch if arch is not None else lm_arch()
    x0, lm_subset_grads, lm_loss = _lm_fns(arch)
    data = _lm_problem(
        arch, seed=seed, n_subsets=scn.n_devices, sigma_h=scn.sigma_h,
        per_subset=per_subset, seq_len=seq_len,
    )
    return run_trajectory(
        scn.protocol(),
        jax.random.PRNGKey(seed),
        x0,
        lm_subset_grads,
        steps=steps,
        lr=scn.lr,
        grad_scale=1.0,  # the LM loss is a mean: step on the mean gradient
        loss_fn=lm_loss,
        mode=mode,
        data=data,
    )


def run_lm_grid(
    scenarios: Iterable[Scenario],
    steps: int,
    *,
    arch=None,
    seed: int = 0,
    per_subset: int = 2,
    seq_len: int = 16,
    mode: str = "grid",
    exact: bool = True,
    shard: str = "none",
    max_lanes_per_device: int | str | None = None,
) -> dict[str, TrajectoryResult]:
    """Sweep LM-scale scenarios through the engine: every lane trains the
    small transformer's flattened parameter vector through the full protocol
    pipeline, with the same bucketing / traced-attack-axis / sharding /
    chunked-streaming machinery as the linear-regression ``run_grid`` (the
    two share ``_run_bucket`` via the ``_BucketProblem`` adapter).

    Every lane is bitwise equal to its standalone ``run_lm_scenario``
    trajectory, and ``shard="shard_map"`` to the unsharded grid, at
    the clean simulation scales (N = 10/16/32) — asserted by
    tests/test_train_engine_shard.py on 1 device in tier-1 and on 8 forced
    host devices in CI.  All rows must share one heterogeneity level
    (buckets share one data tensor; ``sigma_h`` is not a traced LM axis).
    """
    scns = list(scenarios)
    if not scns:
        raise ValueError("run_lm_grid needs at least one scenario")
    sigmas = {s.sigma_h for s in scns}
    if len(sigmas) != 1:
        raise ValueError(
            f"run_lm_grid rows must share sigma_h (got {sorted(sigmas)}): the "
            "LM sweep trains on one shared problem per bucket, so data "
            "heterogeneity cannot vary per lane"
        )
    arch = arch if arch is not None else lm_arch()
    kw = dict(arch=arch, seed=seed, per_subset=per_subset, seq_len=seq_len)
    if mode in ("scan", "loop"):
        if shard != "none" or max_lanes_per_device is not None:
            raise ValueError(
                f"shard={shard!r} / max_lanes_per_device="
                f"{max_lanes_per_device!r} are grid-mode options; "
                f"mode={mode!r} dispatches per scenario"
            )
        return {s.name: run_lm_scenario(s, steps, mode=mode, **kw) for s in scns}
    if mode != "grid":
        raise ValueError(f"unknown grid mode {mode!r}")
    buckets: dict[tuple, list[Scenario]] = {}
    for s in scns:
        buckets.setdefault(_bucket_signature(s, exact=exact), []).append(s)
    out: dict[str, TrajectoryResult] = {}
    for group in buckets.values():
        x0, lm_subset_grads, lm_loss = _lm_fns(arch)
        prob = _BucketProblem(
            subset_grad_fn=lm_subset_grads,
            loss_fn=lm_loss,
            x0=x0,
            data=_lm_problem(
                arch, seed=seed, n_subsets=group[0].n_devices,
                sigma_h=group[0].sigma_h, per_subset=per_subset, seq_len=seq_len,
            ),
            data_batched=False,
            grad_scale=1.0,
        )
        out.update(
            _run_bucket(
                group, steps, prob, seed=seed,
                shard=shard, max_lanes_per_device=max_lanes_per_device,
            )
        )
    return {s.name: out[s.name] for s in scns}


# --------------------------------------------------------------------------
# The architecture zoo: the LM sweep generalized over an architecture axis.
# Each family is a tiny (d_model=32, vocab=64) but structurally faithful
# member of the assigned model zoo; every family's rows ride the identical
# grid / bucketing / sharding machinery via ``run_lm_grid(rows, arch=...)``.
# --------------------------------------------------------------------------

ZOO_FAMILIES = ("transformer", "jamba", "rwkv", "moe", "swa", "cross", "audio")


@functools.lru_cache(maxsize=None)
def zoo_arch(family: str):
    """The tiny-but-faithful ``ArchConfig`` of one zoo family.

    Structure is preserved — jamba keeps its 8-block 1:7 attn:mamba period
    with MoE on even positions, rwkv its token-shift FFN, cross/audio their
    frontend encoders, swa a non-power-of-two sliding window (ring-buffer
    alignment coverage) — while dims shrink to the ``lm_arch`` scale so a
    whole family sweep trains in seconds on CPU.  lru-cached for the same
    reason as ``lm_arch``: one config identity per family keys the
    ``_lm_fns`` / engine program caches.
    """
    from repro.configs.archs import ARCHS, reduced
    from repro.configs.base import (
        BlockSpec, EncoderConfig, MambaConfig, RWKVConfig,
    )

    if family == "transformer":
        return lm_arch()  # shared identity with the plain LM sweeps
    tiny = dict(d_model=32, n_heads=2, n_kv_heads=1, head_dim=16,
                d_ff=64, vocab=64)
    if family == "swa":
        # non-power-of-two window that does NOT divide typical seq lens —
        # exercises the prefill ring-buffer modular alignment
        return lm_arch().scaled(
            name="zoo-swa", period=(BlockSpec(sliding_window=6),),
        )
    if family == "jamba":
        base = reduced(ARCHS["jamba-1.5-large-398b"])
        return base.scaled(
            name="zoo-jamba", n_layers=8, **tiny,
            moe=dataclasses.replace(base.moe, d_ff_expert=32),
            mamba=MambaConfig(d_state=4, d_conv=4, expand=2),
        )
    if family == "rwkv":
        return reduced(ARCHS["rwkv6-1.6b"]).scaled(
            name="zoo-rwkv", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
            head_dim=16, d_ff=64, vocab=64,
            rwkv=RWKVConfig(head_dim=16, decay_lora=8),
        )
    if family == "moe":
        base = reduced(ARCHS["granite-moe-3b-a800m"])
        return base.scaled(
            name="zoo-moe", n_layers=1, **tiny,
            moe=dataclasses.replace(base.moe, d_ff_expert=32),
        )
    if family == "cross":
        return reduced(ARCHS["llama-3.2-vision-90b"]).scaled(
            name="zoo-cross", n_layers=5, **tiny,
            encoder=EncoderConfig(n_frontend_tokens=8, d_frontend=16,
                                  n_encoder_layers=0),
        )
    if family == "audio":
        return reduced(ARCHS["whisper-small"]).scaled(
            name="zoo-audio", n_layers=2, **tiny,
            encoder=EncoderConfig(n_frontend_tokens=8, d_frontend=16,
                                  n_encoder_layers=1),
        )
    raise ValueError(f"unknown zoo family {family!r} (have {ZOO_FAMILIES})")


def zoo_sweep(
    families: Sequence[str] = ZOO_FAMILIES,
    methods: Sequence[tuple[str, int]] = (("lad", 2), ("plain", 1)),
    attacks: Sequence[str] = ("sign_flip",),
    aggregators: Sequence[str] = ("cwtm",),
    compressors: Sequence[str] = ("none",),
    *,
    n_devices: int = 8,
    n_byz: int = 2,
    sigma_h: float = 0.5,
    **kw,
) -> dict[str, list[Scenario]]:
    """The zoo evaluation matrix: ``lm_sweep``'s method x attack x aggregator
    x compressor rows, replicated per architecture family and renamed
    ``zoo/<family>/...``.  Families stay separate lists (one grid call per
    family — buckets cannot mix architectures: the iterate dimension P
    differs), but within a family every row rides ``run_lm_grid`` unchanged.
    """
    out: dict[str, list[Scenario]] = {}
    for fam in families:
        zoo_arch(fam)  # validate the family name up front
        rows = lm_sweep(
            methods, attacks, aggregators, compressors,
            n_devices=n_devices, n_byz=n_byz, sigma_h=sigma_h, **kw,
        )
        out[fam] = [
            dataclasses.replace(s, name=f"zoo/{fam}/" + s.name[len("lm/"):])
            for s in rows
        ]
    return out


def run_zoo_sweep(
    steps: int,
    *,
    families: Sequence[str] = ZOO_FAMILIES,
    sweep: dict[str, list[Scenario]] | None = None,
    seed: int = 0,
    per_subset: int = 2,
    seq_len: int = 16,
    mode: str = "grid",
    **grid_kw,
) -> dict[str, dict[str, TrajectoryResult]]:
    """Train the whole zoo under attack: one ``run_lm_grid`` per family with
    that family's ``zoo_arch``.  Returns ``{family: {row_name: trajectory}}``;
    per-lane results are bitwise equal to standalone ``run_lm_scenario(...,
    arch=zoo_arch(family))`` (same ``_run_bucket`` contract as the LM grid).
    """
    sweep = sweep if sweep is not None else zoo_sweep(families)
    return {
        fam: run_lm_grid(
            rows, steps, arch=zoo_arch(fam), seed=seed,
            per_subset=per_subset, seq_len=seq_len, mode=mode, **grid_kw,
        )
        for fam, rows in sweep.items()
    }


def grid_finals(results: dict[str, TrajectoryResult]) -> dict[str, dict[str, float]]:
    """Flatten a ``run_grid`` result to ``{name: {final_loss,
    final_agg_dist}}`` — the summary-row format of the benchmark drivers."""
    return {
        name: {
            "final_loss": float(res.metrics["loss"][-1]),
            "final_agg_dist": float(res.metrics["agg_dist"][-1]),
        }
        for name, res in results.items()
    }
