"""Scan-compiled multi-round protocol engine.

Before this module, a "training run" was a Python loop that re-dispatched a
jitted single-round function per iteration: N steps = N dispatches + N host
round-trips for metric readback.  The engine compiles an *entire trajectory*
— task assignment, eq.-(5) encoding, compression, attack injection, robust
aggregation, optimizer step — as ONE ``jax.lax.scan`` over rounds.  PRNG
keys, optimizer state and the iterate thread through the scan carry; per-
round metrics (loss, solution error, aggregation distance) come back as
stacked ``(steps,)`` arrays in a single device->host transfer at the end.

Three execution modes share the identical round body:

  * ``mode="scan"`` — the compiled ``lax.scan`` hot path (default);
  * ``mode="loop"`` — the legacy per-round jitted Python loop, kept as the
    bit-exactness reference (tests assert scan == loop on the same keys);
  * ``run_grid``   — whole-grid on-device: ``jax.vmap`` over a scenario-lane
    axis with the attack/aggregator axes dispatched per lane by
    ``lax.switch``; compiled programs are cached across calls and every lane
    is bitwise equal to its standalone trajectory.

The per-round randomness is ``jax.random.fold_in(key, t)`` — exactly the
convention of the previous hand-written loops in benchmarks/ and examples/,
so trajectories are reproducible across engine modes and across the old code.

``protocol_rounds`` is the metric-free sibling used by statistical tests:
``rounds`` aggregates of the *same* subset-gradient stack under fresh round
keys, again as one compiled scan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core.byzantine import (
    ProtocolConfig,
    make_attack_fn,
    make_server_fn,
    protocol_round,
)
from repro.core.participation import (
    PARTICIPATION_KEY_SALT,
    init_participation_state,
    sample_participation,
)
from repro.numerics import stable_mean0, stable_norm, tree_sum
from repro.optim import make_optimizer

__all__ = [
    "TrajectoryResult",
    "run_trajectory",
    "run_grid",
    "grid_compiled_hlo",
    "last_grid_chunk_info",
    "engine_device_grid",
    "make_engine_mesh",
    "engine_device_count",
    "padded_lane_count",
    "pad_lanes",
    "protocol_rounds",
    "register_program_cache",
    "program_cache_sizes",
    "clear_program_caches",
]


def engine_device_grid() -> np.ndarray:
    """Every global device as a ``(process_count, local_device_count)`` grid,
    process-major.

    This is the multi-process plumbing of the engine mesh: row ``p`` holds
    process ``p``'s local devices in id order.  Flattened row-major it is the
    device order of ``make_engine_mesh`` — contiguous lane/subset shards land
    on one process before spilling to the next, which is what keeps the
    future multi-host step a device-list change rather than a resharding.
    Today every caller is single-process, so the grid is ``(1, D)``.
    """
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    n_proc = jax.process_count()
    if len(devs) % n_proc != 0:  # pragma: no cover - heterogeneous hosts
        raise ValueError(
            f"{len(devs)} global devices do not split evenly over "
            f"{n_proc} process(es)"
        )
    return np.array(devs).reshape(n_proc, len(devs) // n_proc)


def make_engine_mesh(axis: str = "lanes") -> Mesh:
    """The 1-D named device mesh of the engine's sharded paths.

    One axis (default ``"lanes"``; the LM train path names it ``"subsets"``)
    over *every* global device in process-major order — see
    ``engine_device_grid``.  ``_grid_program`` runs its vmapped lane program
    under ``shard_map`` over this mesh, and
    ``launch.train.build_engine_step`` its subset-gradient fan-out.
    """
    return Mesh(engine_device_grid().reshape(-1), (axis,))


def engine_device_count() -> int:
    """Size of the engine mesh = process_count x local devices (global)."""
    return len(jax.devices())


def padded_lane_count(n: int, n_devices: int | None = None) -> int:
    """The padding contract: ``n`` lanes/subsets rounded up to a multiple of
    the engine device count (or an explicit ``n_devices``).

    Padding is realized by replicating the LAST lane (``pad_lanes``), so an
    empty axis is un-paddable — there is no lane to replicate — and is
    rejected here with a ``ValueError``.
    """
    if n < 1:
        raise ValueError(
            f"cannot pad a lane axis of length {n} to a device multiple: "
            "padding replicates the last lane, so at least one lane must exist"
        )
    d = n_devices if n_devices is not None else engine_device_count()
    if d < 1:
        raise ValueError(f"device count must be >= 1, got {d}")
    return -(-n // d) * d


@dataclasses.dataclass(frozen=True)
class TrajectoryResult:
    """Output of ``run_trajectory`` (one trajectory) or ``run_grid`` (a
    batched stack of trajectories).

    Attributes:
      x: final iterate ``(Q,)`` (or pytree matching ``x0``).  From
        ``run_grid``: ``(S, Q)`` with a leading scenario-lane axis.
      metrics: dict of per-round ``(steps,)`` arrays — always ``loss`` (if a
        ``loss_fn`` was given), ``agg_dist`` (||aggregate - honest subset
        mean||, the round's aggregation error) and ``grad_norm``; plus
        ``sol_err`` (||x_t - x*||) when ``x_star`` is supplied.  From
        ``run_grid``: ``(S, steps)`` arrays.
    """

    x: Any
    metrics: dict[str, jax.Array]

    def curve(self, name: str = "loss", every: int = 1) -> list[tuple[int, float]]:
        """(iteration, value) pairs thinned to ``every`` (always keeps the
        last round) — the row format of benchmarks/paper_figures.py.

        Only defined for a single trajectory (1-D metric arrays); on a
        batched ``run_grid`` result select a lane first: ``res.lane(i)``.
        """
        vals = jax.device_get(self.metrics[name])
        if getattr(vals, "ndim", 1) != 1:
            raise ValueError(
                "curve() needs a single trajectory; this result is batched "
                f"(metric {name!r} has shape {vals.shape}) — use .lane(i) first"
            )
        n = len(vals)
        return [
            (i, float(v))
            for i, v in enumerate(vals)
            if i % every == 0 or i == n - 1
        ]

    def lane(self, i: int) -> "TrajectoryResult":
        """Extract scenario lane ``i`` of a batched ``run_grid`` result as a
        plain single-trajectory result (indexes the leading axis of ``x`` and
        every metric)."""
        return TrajectoryResult(
            x=jax.tree.map(lambda a: a[i], self.x),
            metrics={k: v[i] for k, v in self.metrics.items()},
        )


def _round_body(
    cfg: ProtocolConfig,
    key: jax.Array,
    opt,
    subset_grad_fn: Callable[[Any], jax.Array],
    lr: float | Callable[[jax.Array], jax.Array],
    grad_scale: float,
    attack_fn=None,
    server_fn=None,
    with_metrics: bool = True,
):
    """The single round used by every engine mode (shared => bit-identical).

    The body emits RAW per-round vectors (aggregate, honest mean, iterate)
    rather than scalar metrics: scalar reductions computed inside the round
    would share fusions with the protocol subgraph, and XLA freely
    duplicates producers into consumer fusions where a copy may compile with
    different reduce/fma choices per program shape — a 1-ulp drift that
    breaks the scan == loop == grid-lane bitwise guarantee once a
    Pallas-interpret subgraph is in the module.  Scan outputs, by contrast,
    are materialized buffers XLA never recomputes, so the metric math runs
    AFTER the scan on bit-stable inputs (``_finalize_metrics``).

    The raw stacks cost ``3 x steps x Q`` floats of scan output;
    ``with_metrics=False`` emits nothing (final-iterate-only runs at large
    ``Q`` — see ``run_trajectory``).

    Participation: ``cfg.participation`` branches STATICALLY.  The default
    ``"full"`` schedule compiles this body exactly as before — same carry
    ``(x, opt_state)``, same program, byte-identical (so the whole existing
    bitwise surface is untouched by construction).  An active schedule
    widens the carry to ``(x, opt_state, p_state)`` (the schedule state —
    the previous mask, which ``"markov"`` evolves), samples the round mask
    from ``fold_in(round_key, PARTICIPATION_KEY_SALT)`` (out-of-band of the
    4-way round-key split — existing streams unshifted), hands it to
    ``protocol_round`` (erasure at the transmission boundary + mask-aware
    server), and emits the per-round reporting count as raw ``"n_report"``.
    """
    p_spec = cfg.participation
    p_active = p_spec.active

    def body(carry, t):
        if p_active:
            x, opt_state, p_state = carry
        else:
            x, opt_state = carry
        k = jax.random.fold_in(key, t)
        grads = subset_grad_fn(x)  # (N, Q)
        if p_active:
            pk = jax.random.fold_in(k, PARTICIPATION_KEY_SALT)
            pm, p_state = sample_participation(
                p_spec, pk, t, cfg.n_devices, p_state
            )
            g = protocol_round(
                cfg, k, grads, attack_fn=attack_fn, server_fn=server_fn,
                participation_mask=pm,
            )
        else:
            g = protocol_round(
                cfg, k, grads, attack_fn=attack_fn, server_fn=server_fn
            )
        lr_t = lr(t) if callable(lr) else lr
        new_x, new_state = opt.update(x, grad_scale * g, opt_state, lr_t)
        raw = (
            {"g": g, "gmean": stable_mean0(grads), "x": new_x}
            if with_metrics
            else {}
        )
        if p_active:
            if with_metrics:
                raw["n_report"] = tree_sum(pm, axis=0)
            return (new_x, new_state, p_state), raw
        return (new_x, new_state), raw

    return body


def _init_carry(cfg: ProtocolConfig, x0, opt):
    """The scan/loop carry of ``_round_body``: ``(x, opt_state)``, plus the
    participation schedule state when ``cfg.participation`` is active (one
    helper so every engine mode builds the identical structure)."""
    base = (x0, opt.init(x0))
    if cfg.participation.active:
        return base + (init_participation_state(cfg.participation, cfg.n_devices),)
    return base


def _finalize_metrics(
    raw: dict[str, jax.Array],
    loss_fn: Callable[[Any], jax.Array] | None,
    x_star: jax.Array | None,
) -> dict[str, jax.Array]:
    """Per-round metrics from the stacked ``(steps, ...)`` raw trajectory.

    Runs on materialized scan outputs in Pallas-free fusions, with the
    reductions in the fixed-tree forms of ``repro/numerics.py`` — both
    conditions the cross-program bitwise guarantee needs (see
    ``_round_body``).
    """
    metrics = {
        "agg_dist": stable_norm(raw["g"] - raw["gmean"]),
        "grad_norm": stable_norm(raw["g"]),
    }
    if loss_fn is not None:
        metrics["loss"] = jax.vmap(loss_fn)(raw["x"])
    if x_star is not None:
        metrics["sol_err"] = stable_norm(raw["x"] - x_star)
    if "n_report" in raw:  # active participation: per-round reporting count
        metrics["n_report"] = raw["n_report"]
    return metrics


def run_trajectory(
    cfg: ProtocolConfig,
    key: jax.Array,
    x0: jax.Array,
    subset_grad_fn: Callable[..., jax.Array],
    *,
    steps: int,
    lr: float | Callable[[jax.Array], jax.Array],
    optimizer: str = "sgd",
    grad_scale: float = 1.0,
    loss_fn: Callable[..., jax.Array] | None = None,
    x_star: jax.Array | None = None,
    mode: str = "scan",
    data: Any = None,
    with_metrics: bool = True,
) -> TrajectoryResult:
    """Run ``steps`` full protocol rounds from ``x0``.

    Bit-exactness guarantee: both modes (and the vmapped ``run_grid``) share
    the identical round body, and the step size / gradient scale enter every
    compiled program as runtime operands, so ``mode="scan"`` equals
    ``mode="loop"`` BITWISE on the same key (asserted per method by the
    tests), and a ``run_grid`` lane equals the corresponding single
    trajectory bitwise.  Per-round randomness is ``fold_in(key, t)`` — the
    convention of the original hand-written benchmark loops, so trajectories
    reproduce across engine modes and across the pre-engine code.

    The iterate length ``Q`` is unconstrained: on kernel backends the ops
    wrappers zero-pad non-divisible ``Q`` up to the tile boundary and slice
    back (exact on the real coordinates — see ``kernels/ops.py``).

    Compiled programs are cached across calls (both modes), keyed on the
    static structure: ``cfg``, ``steps``, the *identities* of
    ``subset_grad_fn`` / ``loss_fn`` / a callable ``lr``, ``optimizer`` and
    the data/x_star presence flags.  ``key``, ``x0``, numeric ``lr``,
    ``grad_scale``, ``data`` and ``x_star`` are runtime operands, so a warm
    repeated call — the figure-driver / sweep regime — makes ZERO retraces
    and zero compiles.  To benefit, pass module-level functions and thread
    problem arrays through ``data`` instead of closing over them: a fresh
    closure per call misses the cache every time and pins its captured
    arrays in it.

    Args:
      cfg: protocol configuration (method/attack/aggregator/compression).
      key: trajectory PRNG key; round ``t`` uses ``fold_in(key, t)``.
      x0: initial iterate.
      subset_grad_fn: ``x -> (N, Q)`` per-subset gradients at ``x`` — or,
        when ``data`` is given, ``(data, x) -> (N, Q)``.
      steps: number of rounds (static; the scan length).
      lr: step size, a float or a ``t -> lr`` schedule.
      optimizer: any ``repro.optim.make_optimizer`` name.
      grad_scale: multiplies the aggregate before the optimizer step (the
        paper's eq.-(7) sum-loss F needs ``N x`` the mean-gradient estimate).
      loss_fn / x_star: optional per-round metric hooks (``loss_fn`` takes
        ``(data, x)`` when ``data`` is given, else ``x``).
      mode: ``"scan"`` (one compiled trajectory) or ``"loop"`` (per-round
        jitted dispatch; the bit-exactness reference).
      data: optional pytree of problem arrays, passed to ``subset_grad_fn``
        and ``loss_fn`` as a runtime operand (program-cache friendly).
      with_metrics: ``False`` skips the per-round raw stacks entirely (the
        metric pipeline materializes ``3 x steps x Q`` floats of scan
        output — prohibitive for final-iterate-only runs at LM-scale ``Q``);
        the result's ``metrics`` is empty and ``loss_fn``/``x_star`` must be
        ``None``.
    """
    if mode not in ("scan", "loop"):
        raise ValueError(f"unknown engine mode {mode!r}")
    if not with_metrics and (loss_fn is not None or x_star is not None):
        raise ValueError("with_metrics=False is incompatible with loss_fn/x_star")

    # lr and grad_scale enter the compiled programs as *runtime operands*,
    # never baked constants: as constants XLA may fold them through the
    # aggregator's own constants (e.g. the mean's 1/N) in one compilation
    # but not another (single vs vmapped grid) — a 1-ulp drift that would
    # break the engine's bit-exactness guarantee between modes.  Non-constant
    # float multiplies are never reassociated, so traced scalars pin the
    # evaluation order everywhere.  The PRNG key, problem data and x_star are
    # operands for the same reason — plus they must not bake into the cached
    # program (the cache would otherwise never hit across seeds/problems).
    gs = jnp.float32(grad_scale)
    lr_arg = 0.0 if callable(lr) else jnp.float32(lr)
    static = (
        cfg,
        subset_grad_fn,
        loss_fn,
        lr if callable(lr) else None,
        optimizer,
        data is not None,
        x_star is not None,
        with_metrics,
    )

    if mode == "scan":
        program = _trajectory_program(steps, *static)
        x, metrics = program(key, x0, lr_arg, gs, data, x_star)
        return TrajectoryResult(x=x, metrics=metrics)

    step_fn = _step_program(
        cfg, subset_grad_fn, lr if callable(lr) else None, optimizer,
        data is not None, with_metrics,
    )
    carry = _init_carry(cfg, x0, make_optimizer(optimizer))
    per_round = []
    for t in range(steps):
        carry, r = step_fn(key, carry, jnp.asarray(t, jnp.int32), lr_arg, gs, data)
        per_round.append(r)
    if not with_metrics:
        return TrajectoryResult(x=carry[0], metrics={})
    raw = jax.tree.map(lambda *rs: jnp.stack(rs), *per_round)
    finalize = _finalize_program(loss_fn, data is not None, x_star is not None)
    return TrajectoryResult(x=carry[0], metrics=finalize(raw, data, x_star))


def _trajectory_body(cfg, opt, subset_grad_fn, lr_schedule, takes_data, with_metrics):
    """Round-body factory shared by the cached scan and loop programs: binds
    the per-call operands (key, lr, grad_scale, data) into the static
    structure the program was cached on."""

    def bind(key, lr_op, gs_op, data_op):
        sgf = (
            (lambda x: subset_grad_fn(data_op, x)) if takes_data else subset_grad_fn
        )
        return _round_body(
            cfg,
            key,
            opt,
            sgf,
            lr_schedule if lr_schedule is not None else lr_op,
            gs_op,
            with_metrics=with_metrics,
        )

    return bind


def _bind_loss(loss_fn, takes_data, data_op):
    if loss_fn is None:
        return None
    return (lambda x: loss_fn(data_op, x)) if takes_data else loss_fn


@functools.lru_cache(maxsize=192)
def _trajectory_program(
    steps, cfg, subset_grad_fn, loss_fn, lr_schedule, optimizer, takes_data,
    has_x_star, with_metrics,
):
    """Build (and cache) the jitted whole-trajectory scan program.

    Cache key = static structure only (see ``run_trajectory``); everything
    numeric is an operand, so repeated warm calls reuse both this Python-level
    program object and jit's compiled executable: zero retraces.  The cache
    is deliberately small (64): a caller passing fresh closures per call gets
    no hits, and each retained entry pins its captured arrays + executable —
    pass module-level functions with ``data`` operands instead.
    """
    opt = make_optimizer(optimizer)
    bind = _trajectory_body(cfg, opt, subset_grad_fn, lr_schedule, takes_data,
                            with_metrics)

    @jax.jit
    def trajectory(key, x0, lr_op, gs_op, data_op, x_star_op):
        (x, *_), raw = jax.lax.scan(
            bind(key, lr_op, gs_op, data_op),
            _init_carry(cfg, x0, opt),
            jnp.arange(steps, dtype=jnp.int32),
        )
        if not with_metrics:
            return x, {}
        metrics = _finalize_metrics(
            raw,
            _bind_loss(loss_fn, takes_data, data_op),
            x_star_op if has_x_star else None,
        )
        return x, metrics

    return trajectory


@functools.lru_cache(maxsize=64)
def _step_program(cfg, subset_grad_fn, lr_schedule, optimizer, takes_data,
                  with_metrics):
    """The cached jitted single-round step of ``mode="loop"`` — same cache
    contract as ``_trajectory_program`` (minus ``steps``/metric hooks: the
    loop length lives in Python and metrics finalize post-loop, so one
    cached step serves every horizon)."""
    opt = make_optimizer(optimizer)
    bind = _trajectory_body(cfg, opt, subset_grad_fn, lr_schedule, takes_data,
                            with_metrics)

    @jax.jit
    def step(key, carry, t, lr_op, gs_op, data_op):
        return bind(key, lr_op, gs_op, data_op)(carry, t)

    return step


@functools.lru_cache(maxsize=64)
def _finalize_program(loss_fn, takes_data, has_x_star):
    """Cached jitted post-loop metric finalizer of ``mode="loop"``.  The scan
    mode fuses the identical ``_finalize_metrics`` into its trajectory
    program; both consume the same materialized raw stacks, which keeps the
    modes bitwise-equal."""

    @jax.jit
    def finalize(raw, data_op, x_star_op):
        return _finalize_metrics(
            raw,
            _bind_loss(loss_fn, takes_data, data_op),
            x_star_op if has_x_star else None,
        )

    return finalize


# ---------------------------------------------------------------------------
# Program-cache lifecycle
# ---------------------------------------------------------------------------
# The lru-cached program builders above pin compiled executables AND their
# captured device buffers for the process lifetime.  That is the right trade
# for a sweep (zero warm compiles) but wrong for long-lived processes running
# many phases — a bench driver that times the grid engine, then the kernel
# backend, then the LM engine accumulates every phase's programs.  The
# registry below gives one explicit release point; other modules holding
# program caches (launch.train's engine-step programs, scenarios' LM problem
# fns) register theirs here so ONE call clears the whole engine stack without
# core importing launch.

_EXTRA_PROGRAM_CACHES: dict[str, tuple[Callable[[], None], Callable[[], int]]] = {}


def register_program_cache(
    name: str, clear_fn: Callable[[], None], size_fn: Callable[[], int]
) -> None:
    """Register an external program cache (clear + current-size callables)
    under ``name`` so ``clear_program_caches`` / ``program_cache_sizes``
    cover it.  Re-registering a name replaces the entry (module reloads)."""
    _EXTRA_PROGRAM_CACHES[name] = (clear_fn, size_fn)


def program_cache_sizes() -> dict[str, int]:
    """Entry counts of every live program cache — the engine's own four lru
    caches plus everything registered via ``register_program_cache``."""
    sizes = {
        "engine.trajectory": _trajectory_program.cache_info().currsize,
        "engine.step": _step_program.cache_info().currsize,
        "engine.finalize": _finalize_program.cache_info().currsize,
        "engine.grid": _grid_program.cache_info().currsize,
    }
    for name, (_, size_fn) in _EXTRA_PROGRAM_CACHES.items():
        sizes[name] = size_fn()
    return sizes


def clear_program_caches() -> dict[str, int]:
    """Release every cached compiled program (and the device buffers each
    pins); returns the per-cache entry counts that were dropped.

    The zero-warm-compile guarantee is *per cache generation*: after a clear
    the next sweep of a bucket compiles once and every sweep after that is
    again compile-free (tests/test_tuner.py asserts the eviction/refill
    cycle).  Benchmark drivers call this between phases so one phase's
    programs do not inflate the next phase's footprint.
    """
    dropped = program_cache_sizes()
    _trajectory_program.cache_clear()
    _step_program.cache_clear()
    _finalize_program.cache_clear()
    _grid_program.cache_clear()
    for clear_fn, _ in _EXTRA_PROGRAM_CACHES.values():
        clear_fn()
    return dropped


def pad_lanes(tree: Any, pad: int) -> Any:
    """Append ``pad`` copies of the last lane to every leaf's leading axis.

    Replicated real lanes (not zeros): padding exists only to reach a
    device-divisible lane count (``launch.mesh.padded_lane_count`` — the
    contract the sharded LM train path shares), and a replica is guaranteed
    to run the exact math of a real lane — no risk of degenerate inputs
    (zero data, zero keys) tripping NaN paths in a lane that is sliced off
    anyway.  An empty leading axis cannot be padded: there is no last lane
    to replicate (callers reject zero lanes before sharding).
    """
    if pad == 0:
        return tree
    return jax.tree.map(
        lambda v: jnp.concatenate(
            [v, jnp.broadcast_to(v[-1:], (pad,) + v.shape[1:])], axis=0
        ),
        tree,
    )


def _branch_select(branches, ids):
    """One callable from a static branch table: direct call when the table is
    a singleton, else a per-lane ``lax.switch`` on the traced branch id."""
    branches = list(branches)
    if len(branches) == 1:
        return branches[0], None
    if ids is None:
        raise ValueError(f"{len(branches)} branches need per-lane ids")

    def make(lane_id):
        def dispatch(*operands):
            return jax.lax.switch(lane_id, branches, *operands)

        return dispatch

    return None, make


def run_grid(
    cfg: ProtocolConfig,
    keys: jax.Array,
    x0: Any,
    subset_grad_fn: Callable[[Any, Any], jax.Array],
    *,
    steps: int,
    lr: float | jax.Array | Callable[[jax.Array], jax.Array],
    data: Any = None,
    data_batched: bool = True,
    attack_branches: tuple | None = None,
    attack_ids: jax.Array | None = None,
    server_branches: tuple | None = None,
    server_ids: jax.Array | None = None,
    optimizer: str = "sgd",
    grad_scale: float = 1.0,
    loss_fn: Callable[[Any, Any], jax.Array] | None = None,
    x_star: jax.Array | None = None,
    x0_batched: bool = False,
    shard: str = "none",
    max_lanes_per_device: int | str | None = None,
) -> TrajectoryResult:
    """Run a whole *batch of trajectories* as ONE compiled on-device program.

    ``jax.vmap`` lifts the scan-compiled round body of ``run_trajectory`` over
    a leading scenario axis of size ``S``: the entire sweep — every lane's
    assignment, eq.-(5) encode, compression, attack, robust aggregation and
    optimizer step, for all ``steps`` rounds — compiles once and runs without
    any per-scenario Python dispatch.  Lane ``i`` is bit-identical to
    ``run_trajectory`` called with lane ``i``'s key/data/lr (tests assert
    this), because both modes share the exact same round body.

    Static protocol structure (``method``, ``d``, ``n_devices``, compressor
    family and sizes, backend) is fixed by ``cfg`` for all lanes — callers
    with heterogeneous static fields must group lanes into compile buckets
    (``repro.core.scenarios.run_grid`` does this).  The *attack* and
    *aggregator* axes, by contrast, may vary per lane: pass a static branch
    table plus per-lane int32 ids and the engine dispatches with
    ``lax.switch`` (under vmap every branch is computed and selected per
    lane, trading a few cheap aggregator evaluations for not re-compiling).

    Args:
      cfg: shared static protocol template.  Its ``attack``/``aggregator``
        fields are ignored when the corresponding branch table is given.
      keys: ``(S, ...)`` stacked per-lane trajectory PRNG keys.
      x0: initial iterate, shared ``(Q,)`` (default) or per-lane ``(S, Q)``
        with ``x0_batched=True``.
      subset_grad_fn: ``(data_lane, x) -> (N, Q)`` per-subset gradients; the
        first argument receives this lane's slice of ``data`` (or ``data``
        itself when ``data_batched=False``, or ``None``).
      steps: number of rounds (static scan length, shared).
      lr: step size — a shared float, a per-lane ``(S,)`` array, or a shared
        ``t -> lr`` schedule.
      data: optional pytree of per-lane problem data with leading ``(S, ...)``
        leaves (``data_batched=True``) or a single shared pytree.
      attack_branches / attack_ids: static tuple of corruption maps
        ``(key, msgs, mask) -> msgs`` (build with
        ``byzantine.make_attack_fn``) + per-lane ``(S,)`` indices.  ``None``
        derives a single branch from ``cfg``.
      server_branches / server_ids: static tuple of server aggregations
        ``(N, Q) -> (Q,)`` (build with ``byzantine.make_server_fn``) +
        per-lane indices.  ``None`` derives a single branch from ``cfg``.
      optimizer / grad_scale: as in ``run_trajectory`` (shared).
      loss_fn: optional ``(data_lane, x) -> scalar`` per-round metric hook.
      x_star: optional shared ``(Q,)`` solution for the ``sol_err`` metric.
      shard: device sharding of the scenario-lane axis —

        * ``"none"``      — single-device vmap (the default; exactly the
          pre-sharding path);
        * ``"shard_map"``  — the lane axis is partitioned over every visible
          device with ``jax.shard_map`` (each device runs the
          identical vmapped scan on its lane shard; one jitted program);
        * ``"pmap"``      — the same partition via ``jax.pmap`` (per-device
          replica dispatch; kept as the second substrate / cross-check).

        Lane counts are padded up to a multiple of ``jax.device_count()``
        by replicating the last lane; padded lanes are sliced off before
        returning, so results are shape-identical to ``shard="none"`` and
        every real lane is bitwise equal to its unsharded value at the
        clean simulation scales (see README "Engine guarantees").  On a
        1-device host every mode degenerates to the unsharded math, so CPU
        CI exercises the multi-device path with
        ``--xla_force_host_platform_device_count=8``.
      max_lanes_per_device: optional streaming chunk size: the sweep runs in
        chunks of ``max_lanes_per_device * device_count`` lanes, bounding
        device memory for 1000+-lane sweeps.  Every chunk (including the
        padded tail chunk) has the same lane count, so all chunks share ONE
        compiled program — a warm chunked sweep still makes zero compiles.
        Results are concatenated in lane order; also valid with
        ``shard="none"`` (chunked single-device streaming).  Pass ``"auto"``
        to let ``repro.launch.tuner`` pick the capacity: a power-then-
        binary-search over probed chunk timings, cached per (bucket
        signature, device kind) on disk so a warm auto sweep re-probes
        nothing.  Because the per-lane math never depends on the chunk size,
        ``"auto"`` is bitwise-equal to any hand-picked capacity.

    Returns:
      A batched ``TrajectoryResult``: ``x`` has a leading ``(S,)`` lane axis
      and every metric is ``(S, steps)``.  Use ``.lane(i)`` to recover the
      per-scenario result.

    Compiled programs are cached across calls, keyed on the *object identity*
    of ``subset_grad_fn`` / ``loss_fn`` / the branch functions / a callable
    ``lr`` (plus ``cfg``, ``steps``, ``optimizer`` and the batching shape).
    To benefit from the cache in repeated sweeps, pass module-level functions
    (and build branches with the lru-cached ``make_attack_fn`` /
    ``make_server_fn``) rather than fresh lambdas — a fresh closure per call
    recompiles every time and pins its captured arrays in the cache.
    """
    plan = _plan_grid(
        cfg, keys, x0, subset_grad_fn, steps=steps, lr=lr, data=data,
        data_batched=data_batched, attack_branches=attack_branches,
        attack_ids=attack_ids, server_branches=server_branches,
        server_ids=server_ids, optimizer=optimizer, grad_scale=grad_scale,
        loss_fn=loss_fn, x_star=x_star, x0_batched=x0_batched, shard=shard,
    )
    chunk = _resolve_chunk(plan, max_lanes_per_device)
    outs = []
    for start in range(0, plan.n_lanes, chunk):
        take = min(chunk, plan.n_lanes - start)
        x, metrics = plan.program(*plan.chunk_operands(start, take, chunk))
        if take < chunk:  # drop the replicated padding lanes
            x = jax.tree.map(lambda v: v[:take], x)
            metrics = {k: v[:take] for k, v in metrics.items()}
        outs.append((x, metrics))
    if len(outs) == 1:
        x, metrics = outs[0]
    else:
        x = jax.tree.map(lambda *vs: jnp.concatenate(vs, axis=0), *[o[0] for o in outs])
        metrics = {
            k: jnp.concatenate([o[1][k] for o in outs], axis=0) for k in outs[0][1]
        }
    return TrajectoryResult(x=x, metrics=metrics)


@dataclasses.dataclass(frozen=True)
class _GridPlan:
    """Everything ``run_grid`` needs after the prologue: the cached compiled
    program, the operand tuple, which operands carry a lane axis, and a
    chunk-slicer.  Shared with ``grid_compiled_hlo`` (the roofline hook) so
    introspection lowers the exact program the sweep runs."""

    program: Callable
    operands: tuple
    lane_axes: tuple
    n_lanes: int
    devs: int
    signature: tuple  # the tuner's bucket signature (lane count excluded)

    def chunk_operands(self, start: int, take: int, chunk: int) -> tuple:
        if start == 0 and take == self.n_lanes == chunk:
            return self.operands  # whole sweep, no padding: the as-is path
        return tuple(
            pad_lanes(
                jax.tree.map(lambda v: v[start : start + take], op),
                chunk - take,
            )
            if lanes
            else op
            for op, lanes in zip(self.operands, self.lane_axes)
        )


def _plan_grid(
    cfg, keys, x0, subset_grad_fn, *, steps, lr, data, data_batched,
    attack_branches, attack_ids, server_branches, server_ids, optimizer,
    grad_scale, loss_fn, x_star, x0_batched, shard,
) -> _GridPlan:
    """Validate + assemble one grid call: branch tables, the cached program,
    the operand tuple and the lane-axis mask (the shared prologue of
    ``run_grid`` and ``grid_compiled_hlo``)."""
    if attack_ids is not None and (attack_branches is None or len(attack_branches) < 2):
        raise ValueError(
            "attack_ids given but attack_branches has fewer than 2 entries — "
            "the ids would be silently ignored"
        )
    if server_ids is not None and (server_branches is None or len(server_branches) < 2):
        raise ValueError(
            "server_ids given but server_branches has fewer than 2 entries — "
            "the ids would be silently ignored"
        )
    attack_branches = (
        attack_branches if attack_branches is not None else (make_attack_fn(cfg),)
    )
    server_branches = (
        server_branches if server_branches is not None else (make_server_fn(cfg),)
    )
    if shard not in ("none", "pmap", "shard_map"):
        raise ValueError(f"unknown shard mode {shard!r}")
    lr_batched = not callable(lr) and getattr(jnp.asarray(lr), "ndim", 0) == 1
    axes_sig = (
        lr_batched,
        attack_ids is not None,
        server_ids is not None,
        data is not None and data_batched,
        x0_batched,
        x_star is not None,
    )
    program = _grid_program(
        cfg,
        steps,
        tuple(attack_branches),
        tuple(server_branches),
        subset_grad_fn,
        loss_fn,
        lr if callable(lr) else None,
        optimizer,
        axes_sig,
        shard,
    )
    # a shared schedule rides the closure; numeric lr is a traced f32 operand
    # exactly as in run_trajectory (bit-exactness across modes)
    lr_arg = 0.0 if callable(lr) else jnp.asarray(lr, jnp.float32)
    operands = (
        keys, lr_arg, attack_ids, server_ids, data, x0, x_star,
        jnp.float32(grad_scale),
    )
    lane_axes = (True,) + axes_sig[:5] + (False, False)  # which operands carry lanes
    n_lanes = int(keys.shape[0])
    if n_lanes == 0:
        raise ValueError(
            "run_grid needs at least one lane: an empty lane axis cannot be "
            "made device-divisible by padding (padding replicates the last "
            "lane, and there is no lane to replicate)"
        )
    devs = engine_device_count() if shard != "none" else 1
    # The tuner's bucket signature: everything the capacity decision depends
    # on — protocol structure, scan length, shard mode and the PER-LANE
    # operand shapes/dtypes (the lane count itself is excluded so sweeps of
    # different sizes share one tuned capacity).
    shapes_sig = tuple(
        tuple(
            (tuple(v.shape[1:]) if lanes else tuple(v.shape), str(v.dtype))
            for v in map(jnp.asarray, jax.tree.leaves(op))
        )
        for op, lanes in zip(operands, lane_axes)
    )
    signature = ("grid", repr(cfg), steps, optimizer, shard, axes_sig, shapes_sig)
    return _GridPlan(
        program=program, operands=operands, lane_axes=lane_axes,
        n_lanes=n_lanes, devs=devs, signature=signature,
    )


_LAST_GRID_CHUNK: dict[str, Any] = {}


def last_grid_chunk_info() -> dict[str, Any]:
    """How the most recent ``run_grid``/``grid_compiled_hlo`` call chunked its
    sweep: ``{"max_lanes_per_device", "chunk", "n_lanes", "devices",
    "auto"}``.  Benchmark drivers read the auto-tuned capacity back from
    here (the sweep itself only returns trajectories)."""
    return dict(_LAST_GRID_CHUNK)


def _resolve_chunk(plan: _GridPlan, max_lanes_per_device: int | str | None) -> int:
    """Chunk size in lanes for one grid call; resolves ``"auto"`` through the
    lane-capacity tuner (probing this plan's actual compiled program)."""
    auto = isinstance(max_lanes_per_device, str)
    if auto:
        if max_lanes_per_device != "auto":
            raise ValueError(
                f"max_lanes_per_device must be an int, None or 'auto'; "
                f"got {max_lanes_per_device!r}"
            )
        # Deferred import: core must not depend on launch at module scope —
        # the tuner is pure Python (no engine import), so this cannot cycle.
        from repro.launch.tuner import auto_max_lanes
        from repro.timing import block_time

        dev0 = jax.devices()[0]
        device_kind = f"{dev0.platform}/{getattr(dev0, 'device_kind', '')}"

        def probe(capacity: int) -> float:
            lanes = capacity * plan.devs
            take = min(lanes, plan.n_lanes)
            ops = plan.chunk_operands(0, take, lanes)
            # warmup=1 compiles this chunk shape; the timed call is warm.
            # The chosen shape stays compiled in jit's per-shape cache, so
            # the sweep that follows starts warm at the winning capacity.
            return block_time(plan.program, *ops, iters=1, warmup=1)

        max_lanes_per_device = auto_max_lanes(
            probe,
            n_lanes=plan.n_lanes,
            n_devices=plan.devs,
            signature=plan.signature,
            device_kind=device_kind,
        )
    if max_lanes_per_device is not None and max_lanes_per_device < 1:
        raise ValueError(
            f"max_lanes_per_device must be >= 1, got {max_lanes_per_device}"
        )
    if max_lanes_per_device is None:
        chunk = padded_lane_count(plan.n_lanes, plan.devs)  # one padded chunk
    else:
        chunk = max_lanes_per_device * plan.devs
    _LAST_GRID_CHUNK.clear()
    _LAST_GRID_CHUNK.update(
        max_lanes_per_device=max_lanes_per_device, chunk=chunk,
        n_lanes=plan.n_lanes, devices=plan.devs, auto=auto,
    )
    return chunk


def grid_compiled_hlo(
    cfg: ProtocolConfig,
    keys: jax.Array,
    x0: Any,
    subset_grad_fn: Callable[[Any, Any], jax.Array],
    *,
    steps: int,
    lr: float | jax.Array | Callable[[jax.Array], jax.Array],
    data: Any = None,
    data_batched: bool = True,
    attack_branches: tuple | None = None,
    attack_ids: jax.Array | None = None,
    server_branches: tuple | None = None,
    server_ids: jax.Array | None = None,
    optimizer: str = "sgd",
    grad_scale: float = 1.0,
    loss_fn: Callable[[Any, Any], jax.Array] | None = None,
    x_star: jax.Array | None = None,
    x0_batched: bool = False,
    shard: str = "none",
    max_lanes_per_device: int | str | None = None,
) -> str:
    """Optimized HLO text of the EXACT chunk program a ``run_grid`` call with
    the same arguments executes — the hook ``launch.roofline`` analyzes to
    put a %-of-peak figure next to every scaling-benchmark wall clock.

    Same signature as ``run_grid`` (including ``max_lanes_per_device=
    "auto"``, which resolves through the tuner cache).  ``shard="pmap"`` has
    no single jitted module to lower (per-device replica dispatch) and is
    rejected.
    """
    plan = _plan_grid(
        cfg, keys, x0, subset_grad_fn, steps=steps, lr=lr, data=data,
        data_batched=data_batched, attack_branches=attack_branches,
        attack_ids=attack_ids, server_branches=server_branches,
        server_ids=server_ids, optimizer=optimizer, grad_scale=grad_scale,
        loss_fn=loss_fn, x_star=x_star, x0_batched=x0_batched, shard=shard,
    )
    if shard == "pmap":
        raise ValueError(
            "grid_compiled_hlo needs a single jitted module; shard='pmap' "
            "dispatches per-device replicas — lower shard='shard_map' instead"
        )
    chunk = _resolve_chunk(plan, max_lanes_per_device)
    take = min(chunk, plan.n_lanes)
    ops = plan.chunk_operands(0, take, chunk)
    return plan.program.lower(*ops).compile().as_text()


@functools.lru_cache(maxsize=192)
def _grid_program(
    cfg: ProtocolConfig,
    steps: int,
    attack_branches: tuple,
    server_branches: tuple,
    subset_grad_fn,
    loss_fn,
    lr_schedule,
    optimizer: str,
    axes_sig: tuple,
    shard: str = "none",
):
    """Build (and cache) the jitted vmapped-scan program for one bucket.

    The cache key is entirely static structure: config, scan length, branch
    *function identities* (stable across calls via the lru-cached
    ``make_attack_fn``/``make_server_fn``), the gradient/loss callables, the
    batching signature and the shard mode.  All numeric inputs — keys, lr,
    branch ids, problem data, x0, x_star, grad_scale — are runtime operands,
    so repeated sweeps (figure drivers, notebooks, parameter studies) reuse
    the compiled executable: a warm whole-grid sweep makes zero compilations
    and zero per-scenario dispatches — sharded or not.

    ``shard="shard_map"`` wraps the SAME vmapped lane program in a
    ``shard_map`` over a 1-D ``("lanes",)`` device mesh (lane-carrying
    operands partitioned, shared operands replicated); ``shard="pmap"``
    reshapes the lane axis to ``(devices, lanes_per_device)`` and dispatches
    per-device replicas.  Both reuse ``one_lane`` verbatim, which is what
    keeps sharded lanes bitwise equal to the unsharded grid.
    """
    (lr_batched, has_attack_ids, has_server_ids, data_batched,
     x0_batched, has_x_star) = axes_sig
    attack_fn0, make_attack = _branch_select(
        attack_branches, True if has_attack_ids else None
    )
    server_fn0, make_server = _branch_select(
        server_branches, True if has_server_ids else None
    )
    opt = make_optimizer(optimizer)

    def one_lane(key, lr_lane, attack_id, server_id, data_lane, x0_lane,
                 x_star_op, gs_op):
        attack_fn = attack_fn0 if make_attack is None else make_attack(attack_id)
        server_fn = server_fn0 if make_server is None else make_server(server_id)
        body = _round_body(
            cfg,
            key,
            opt,
            lambda x: subset_grad_fn(data_lane, x),
            lr_schedule if lr_schedule is not None else lr_lane,
            gs_op,
            attack_fn=attack_fn,
            server_fn=server_fn,
        )
        (x, *_), raw = jax.lax.scan(
            body, _init_carry(cfg, x0_lane, opt), jnp.arange(steps, dtype=jnp.int32)
        )
        metrics = _finalize_metrics(
            raw,
            None if loss_fn is None else (lambda x_t: loss_fn(data_lane, x_t)),
            x_star_op if has_x_star else None,
        )
        return x, metrics

    in_axes = (
        0,
        0 if lr_batched else None,
        0 if has_attack_ids else None,
        0 if has_server_ids else None,
        0 if data_batched else None,
        0 if x0_batched else None,
        None,  # x_star: shared solution (sol_err metric)
        None,  # grad_scale: shared runtime operand (see run_trajectory)
    )
    vmapped = jax.vmap(one_lane, in_axes=in_axes)

    if shard == "none":
        return jax.jit(vmapped)

    if shard == "shard_map":
        mesh = make_engine_mesh("lanes")
        in_specs = tuple(
            PartitionSpec("lanes") if ax == 0 else PartitionSpec()
            for ax in in_axes
        )
        # check_vma off: every output is lane-partitioned, there is nothing
        # replicated for the static checker to prove — and the checker has no
        # rules for some of the primitives the round body uses
        return jax.jit(
            jax.shard_map(
                vmapped,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=PartitionSpec("lanes"),
                check_vma=False,
            )
        )

    # shard == "pmap": per-device replica dispatch of the same lane program.
    devs = engine_device_count()
    pm = jax.pmap(vmapped, in_axes=in_axes)

    def grid(*args):
        split = tuple(
            jax.tree.map(
                lambda v: v.reshape((devs, v.shape[0] // devs) + v.shape[1:]), a
            )
            if ax == 0
            else a
            for a, ax in zip(args, in_axes)
        )
        out = pm(*split)
        return jax.tree.map(lambda v: v.reshape((-1,) + v.shape[2:]), out)

    return grid


def protocol_rounds(
    cfg: ProtocolConfig,
    key: jax.Array,
    subset_grads: jax.Array,
    rounds: int,
    *,
    key_offset: int = 0,
) -> jax.Array:
    """``rounds`` independent protocol rounds on a fixed ``(N, Q)`` gradient
    stack, compiled as one scan: returns the ``(rounds, Q)`` aggregates.

    Round ``t`` uses ``fold_in(key, key_offset + t)`` — statistical tests use
    this to estimate encoder bias / variance without per-round dispatch.
    """

    @jax.jit
    def sweep(subset_grads):
        def body(_, t):
            return None, protocol_round(cfg, jax.random.fold_in(key, t), subset_grads)

        _, outs = jax.lax.scan(
            body, None, key_offset + jnp.arange(rounds, dtype=jnp.int32)
        )
        return outs

    return sweep(subset_grads)
