"""The LAD train step and the training driver.

``build_train_step`` is the protocol-engine step: the LM workload runs
through core.byzantine's ``protocol_round``, i.e. the assignment -> eq.-(5)
encode -> compress -> attack -> robust-aggregate pipeline of the paper's
linear-regression experiments, at whole-model granularity.  Per-subset
gradients are computed explicitly (``jax.vmap`` over the N subset blocks of
the batch), flattened to an ``(N, P)`` stack, aggregated by the protocol
(Algorithm 1/2, including the per-round randomized cyclic task matrix), and
unflattened into the optimizer.  The round selects each device's assigned
rows of the stack rather than gathering an ``(N, d, P)`` copy.

``TrainConfig.shard`` partitions the per-subset gradient fan-out over the
engine device mesh (``launch.mesh.make_engine_mesh``): ``"none"`` (one
device) or ``"shard_map"`` (one jitted program over every device).  The
subset axis is padded to a device multiple by replicating the last subset's
batch block (``core.engine.pad_lanes`` — the grid engine's lane contract),
each device computes its subsets' gradients, and the full round body runs
replicated on the all-gathered, padding-sliced ``(N, P)`` stack — so sharded
steps are BITWISE equal to ``shard="none"`` at the clean simulation scales
(N = 10/16/32; see README "Engine guarantees" and
tests/test_train_engine_shard.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models
from repro.configs.base import ArchConfig, TrainConfig
from repro.core import attacks as attack_lib
from repro.core import compression as comp_lib
from repro.core.byzantine import ProtocolConfig, protocol_round
from repro.core.coding import flatten_pytree, unflatten_pytree
from repro.core import engine as engine_lib
from repro.core.engine import pad_lanes
from repro.launch.mesh import (
    data_axes,
    engine_device_count,
    make_engine_mesh,
    n_data_devices,
    padded_lane_count,
)
from repro.models.module import logical_to_mesh
from repro.numerics import stable_mean0
from repro.optim import make_optimizer
from repro.optim.schedule import linear_warmup_cosine


def make_round_config(tcfg: TrainConfig, n_subsets: int) -> ProtocolConfig:
    """Lower a ``TrainConfig`` to the core ``ProtocolConfig`` the engine path
    feeds to ``protocol_round`` (the same lowering a ``Scenario`` performs for
    the linear-regression grid)."""
    if tcfg.protocol == "none":
        return ProtocolConfig(
            n_devices=n_subsets,
            d=1,
            method="plain",
            aggregator="mean",
            n_byz=0,
            attack=attack_lib.AttackSpec(name="none"),
        )
    method = "plain" if tcfg.protocol == "plain" else tcfg.protocol
    return ProtocolConfig(
        n_devices=n_subsets,
        d=1 if method == "plain" else tcfg.d,
        method=method,
        aggregator=tcfg.aggregator,
        trim_frac=tcfg.trim_frac,
        n_byz=tcfg.n_byz,
        attack=attack_lib.AttackSpec(name=tcfg.attack, n_byz=tcfg.n_byz),
        compression=comp_lib.spec_from(
            tcfg.compression, q_hat_frac=tcfg.q_hat_frac, levels=tcfg.quant_levels
        ),
    )


# Compiled engine-step programs, cached across build_train_step calls.
# Each program is keyed on exactly the config it reads — (arch cfg, lowered
# ProtocolConfig, remat, shard substrate, device count) for the round
# program; (optimizer, momentum dtype, lr, steps, weight decay) for the
# optimizer-apply program — so configs differing only in fields a program
# never reads (e.g. an lr or seed sweep against the round program) share the
# cached executable instead of recompiling.  ``specs`` is deliberately NOT
# part of the key: it is a pure function of the arch ``cfg`` (models.init
# derives the spec tree from the architecture alone), so two calls agreeing
# on the key always pass equal specs.  ``_ENGINE_TRACES`` counts *trace
# events* (a Python side effect inside the traced bodies runs only while
# tracing) — the test hook for the zero-compile warm-step contract
# (tests/test_train_engine_shard.py).
_ENGINE_PROGRAMS: dict = {}
_ENGINE_TRACES = {"round": 0, "apply": 0}

_SUBSET_AXIS = "subsets"


def engine_program_cache_info() -> dict:
    """{programs, round, apply}: cached program count + trace-event counters
    for the engine train path (warm steps must leave all three unchanged)."""
    return dict(programs=len(_ENGINE_PROGRAMS), **_ENGINE_TRACES)


def engine_program_cache_clear() -> None:
    _ENGINE_PROGRAMS.clear()


# One release point for the whole engine stack: engine.clear_program_caches()
# drops these round/apply programs together with the core lru caches.
engine_lib.register_program_cache(
    "train.engine_step", engine_program_cache_clear,
    lambda: len(_ENGINE_PROGRAMS),
)


def _build_round_program(cfg, pcfg, remat, n_sub, shard, devs, specs):
    """The fan-out + protocol-round program of one engine-step configuration.

    ``(params, blocks, key) -> (loss, metrics, g_flat)`` where ``blocks`` is
    the ``(N, rows, ...)`` subset-blocked (micro)batch.  Both substrates
    share ``one`` (the per-subset gradient) and ``finalize`` (the round body
    + fixed-tree metric means) verbatim — that sharing is what keeps sharded
    steps bitwise equal to ``shard="none"`` at the clean scales.
    """

    def one(params, sub_batch):
        _ENGINE_TRACES["round"] += 1  # runs at trace time only

        def loss_fn(pp):
            return models.loss_fn(pp, specs, cfg, sub_batch, remat=remat)

        with jax.named_scope("lad.fanout"):
            (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("lad.flatten"):
            flat, _ = flatten_pytree(jax.tree.map(lambda a: a.astype(jnp.float32), g))
        return loss, metrics, flat

    def finalize(losses, metricses, stack, k):
        g = protocol_round(pcfg, k, stack)
        # cross-subset means in the fixed-tree form of repro/numerics.py: a
        # plain reduce may accumulate differently between the sharded and
        # unsharded programs and break the substrate-parity guarantee
        return stable_mean0(losses), jax.tree.map(stable_mean0, metricses), g

    if shard == "none":

        @jax.jit
        def round_none(params, blocks, k):
            losses, metricses, stack = jax.vmap(functools.partial(one, params))(blocks)
            return finalize(losses, metricses, stack, k)

        return round_none

    n_pad = padded_lane_count(n_sub, devs)

    def per_device(params, blocks_shard, k):
        # local fan-out -> all-gather -> the full round body, replicated:
        # every device aggregates the identical (N, P) stack, so the round's
        # output needs no further collective (out specs are replicated)
        losses, metricses, stack = jax.vmap(functools.partial(one, params))(blocks_shard)

        def gather(v):  # (local, ...) -> (N, ...): padding subsets sliced off
            return jax.lax.all_gather(v, _SUBSET_AXIS, tiled=True)[:n_sub]

        with jax.named_scope("lad.gather"):
            losses, metricses, stack = (gather(losses), jax.tree.map(gather, metricses),
                                        gather(stack))
        return finalize(losses, metricses, stack, k)

    inner = jax.shard_map(
        per_device,
        mesh=make_engine_mesh(_SUBSET_AXIS),
        in_specs=(P(), P(_SUBSET_AXIS), P()),
        out_specs=(P(), P(), P()),
        # every output is replicated by construction (post-all-gather);
        # check_vma has no rules for some round-body primitives
        check_vma=False,
    )

    @jax.jit
    def round_shard_map(params, blocks, k):
        return inner(params, pad_lanes(blocks, n_pad - n_sub), k)

    return round_shard_map


def _engine_round_program(cfg, tcfg, n_sub, specs):
    shard = tcfg.shard
    devs = engine_device_count() if shard != "none" else 1
    # the round program reads only the lowered protocol structure + remat
    # (never lr/seed/steps/optimizer), so parameter sweeps over those fields
    # reuse one compiled fan-out+round program per substrate
    pcfg = make_round_config(tcfg, n_sub)
    key = (cfg, pcfg, tcfg.remat, shard, devs)
    prog = _ENGINE_PROGRAMS.get(key)
    if prog is None:
        prog = _build_round_program(cfg, pcfg, tcfg.remat, n_sub, shard, devs, specs)
        _ENGINE_PROGRAMS[key] = prog
    return prog


def _engine_apply_program(tcfg):
    """The cached optimizer-apply program ``(params, opt_state, g_flat, t) ->
    (new_params, new_opt_state)``.

    One jitted program shared by both substrates: the round program's
    outputs are materialized program outputs (never re-fused into the
    optimizer math), so both shard modes step through the exact same apply
    compilation — the second half of the substrate-parity guarantee.
    """
    # keyed on the fields apply actually reads, NOT the whole tcfg: every
    # shard substrate of one run config then shares the literal jitted
    # program object — parity of the optimizer step holds by construction
    key = ("apply", tcfg.optimizer, tcfg.momentum_dtype, tcfg.lr, tcfg.steps,
           tcfg.weight_decay)
    prog = _ENGINE_PROGRAMS.get(key)
    if prog is None:
        opt = make_optimizer(tcfg.optimizer, momentum_dtype=tcfg.momentum_dtype)
        schedule = linear_warmup_cosine(tcfg.lr, warmup=max(tcfg.steps // 20, 1),
                                        total_steps=tcfg.steps)

        @jax.jit
        def apply(params, opt_state, g_flat, step_idx):
            _ENGINE_TRACES["apply"] += 1  # runs at trace time only
            with jax.named_scope("lad.unflatten"):
                _, flat_spec = flatten_pytree(params)
                grads = unflatten_pytree(g_flat, flat_spec)
            with jax.named_scope("lad.optimizer"):
                lr = schedule(step_idx)
                return opt.update(params, grads, opt_state, lr,
                                  weight_decay=tcfg.weight_decay)

        prog = apply
        _ENGINE_PROGRAMS[key] = prog
    return prog


def build_train_step(cfg: ArchConfig, tcfg: TrainConfig, mesh, specs):
    """The protocol-engine train step: LM gradients through ``protocol_round``.

    Returns ``(step_fn, optimizer)``; ``step(params, opt_state, batch, idx)``
    returns ``(params, opt_state, loss, metrics)``.  Per microbatch:

      1. the global batch's leading dim is blocked into ``N = n_subsets``
         logical LAD devices (``tcfg.n_subsets`` or the mesh's data size);
      2. ``jax.vmap`` computes every subset's full-model gradient — under
         ``tcfg.shard`` the subset axis is partitioned over the engine
         device mesh (padded to a device multiple by replicating the last
         subset's block; padding gradients are computed and discarded) and
         each device fans out only its own subsets;
      3. gradients flatten to an ``(N, P)`` stack and one ``protocol_round``
         runs the paper's pipeline — randomized cyclic assignment, eq.-(5)
         encode, Com-LAD compression, Byzantine attack, robust aggregation
         (replicated per device in the sharded modes, on the all-gathered
         stack);
      4. the aggregated flat gradient un-flattens into the optimizer step.

    With ``microbatches > 1`` the robust exchange runs once per microbatch
    and the aggregated gradients average in fp32.

    The step dispatches two cached compiled programs — the fan-out + round
    program (per shard substrate) and the shared optimizer-apply program —
    rather than being one traceable function, so callers must NOT wrap it in
    ``jax.jit`` (re-tracing would inline and re-fuse across the program
    boundary that keeps the substrates bitwise-comparable).  Programs are
    cached across ``build_train_step`` calls on the static config, so a warm
    step — and a second step fn built from an equal config — makes zero
    compiles (``engine_program_cache_info``).  The cached programs do NOT
    donate params/opt_state: they are shared across callers that may reuse
    their inputs (conformance tests re-step from one params tree).
    """
    if tcfg.protocol_impl != "engine":
        raise ValueError(f"unknown protocol_impl {tcfg.protocol_impl!r}: expected 'engine'")
    if tcfg.shard not in ("none", "shard_map"):
        raise ValueError(
            f"unknown engine shard mode {tcfg.shard!r}: expected 'none' or 'shard_map'"
        )
    n_sub = tcfg.n_subsets or n_data_devices(mesh)
    opt = make_optimizer(tcfg.optimizer, momentum_dtype=tcfg.momentum_dtype)
    round_prog = _engine_round_program(cfg, tcfg, n_sub, specs)
    apply_prog = _engine_apply_program(tcfg)
    base_key = jax.random.PRNGKey(tcfg.seed)
    m = tcfg.microbatches

    if tcfg.shard == "shard_map":
        # callers (Trainer) hand in arrays committed to their own mesh; the
        # sharded programs run over the full engine mesh, and jit refuses
        # mixed device commitments — so step inputs are re-laid-out onto the
        # engine mesh (replicated; pure data movement, bitwise-neutral).
        # After the first step params/opt_state already live there and the
        # transfer is a no-op; the per-step batch genuinely moves.
        _rep = NamedSharding(make_engine_mesh(_SUBSET_AXIS), P())

        def to_engine(tree):
            return jax.device_put(tree, _rep)

    else:  # "none" shares the caller's placement
        def to_engine(tree):
            return tree

    def step(params, opt_state, batch, step_idx):
        def blocked(x):  # (B, ...) -> (N, B/N, ...)
            assert x.shape[0] % n_sub == 0, (x.shape, n_sub)
            return x.reshape((n_sub, x.shape[0] // n_sub) + x.shape[1:])

        # host spans on the profiler's clock (inactive TraceMes when no trace
        # is being taken): what the host does while the device may wait
        with jax.profiler.TraceAnnotation("lad.place"):
            params = to_engine(params)
            opt_state = to_engine(opt_state)
            blocks = to_engine(jax.tree.map(blocked, batch))
        with jax.profiler.TraceAnnotation("lad.dispatch_round"):
            round_key = jax.random.fold_in(base_key, step_idx)
            if m <= 1:
                loss, metrics, g_flat = round_prog(
                    params, blocks, jax.random.fold_in(round_key, 0)
                )
            else:
                rows = jax.tree.leaves(blocks)[0].shape[1]
                assert rows % m == 0, (rows, m)
                sl = rows // m
                per = [
                    round_prog(
                        params,
                        jax.tree.map(lambda x: x[:, j * sl : (j + 1) * sl], blocks),
                        jax.random.fold_in(round_key, j),
                    )
                    for j in range(m)
                ]
                g_flat = per[0][2]
                for _, _, g in per[1:]:  # fp32 accumulation, in microbatch order
                    g_flat = g_flat + g
                g_flat = g_flat / m
                loss = stable_mean0(jnp.stack([l for l, _, _ in per]))
                metrics = jax.tree.map(
                    lambda *vs: stable_mean0(jnp.stack(vs)), *[met for _, met, _ in per]
                )

        with jax.profiler.TraceAnnotation("lad.dispatch_apply"):
            new_params, new_opt = apply_prog(params, opt_state, g_flat, step_idx)
        return new_params, new_opt, loss, metrics

    return step, opt


def param_mesh_rules(mesh) -> dict:
    axes = data_axes(mesh)
    return {"fsdp": axes if len(axes) > 1 else axes[0], "tp": "model", "stack": None}


def param_pspecs(specs, mesh, shapes=None):
    return logical_to_mesh(specs, mesh, rules=param_mesh_rules(mesh), shapes=shapes)


def shardings_for(specs, mesh, shapes=None):
    """NamedSharding tree for a logical-spec tree on ``mesh``."""
    pspecs = param_pspecs(specs, mesh, shapes)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_pspec(mesh, extra_dims: int = 1) -> P:
    axes = data_axes(mesh)
    lead = axes if len(axes) > 1 else axes[0]
    return P(lead, *([None] * extra_dims))


@dataclasses.dataclass
class Trainer:
    """End-to-end training driver (used by examples/ on small models)."""

    cfg: ArchConfig
    tcfg: TrainConfig
    mesh: Any

    def __post_init__(self):
        key = jax.random.PRNGKey(self.tcfg.seed)
        with self.mesh:
            self.params, self.specs = models.init(key, self.cfg)
            shardings = shardings_for(self.specs, self.mesh, self.params)
            self.params = jax.tree.map(jax.device_put, self.params, shardings)
            step_fn, self.opt = build_train_step(self.cfg, self.tcfg, self.mesh, self.specs)
            self.opt_state = self.opt.init(self.params)
            bspec = batch_pspec(self.mesh)
            # the step dispatches its own compiled programs: never re-jit it
            self._jit_step = step_fn
            self._bsharding = NamedSharding(self.mesh, bspec)
            self.step = 0

    def run(self, batches, log_every: int = 10):
        history = []
        with self.mesh:
            for i, batch in enumerate(batches):
                # one ``lad.step`` span per step, carrying its number; the
                # step's other ``lad.*`` host spans nest inside it
                with jax.profiler.StepTraceAnnotation("lad.step", step_num=i):
                    with jax.profiler.TraceAnnotation("lad.place"):
                        batch = {
                            k: jax.device_put(
                                v, NamedSharding(self.mesh, P(self._bsharding.spec[0],
                                                              *([None] * (v.ndim - 1))))
                            )
                            for k, v in batch.items()
                        }
                        step_idx = jnp.asarray(i, jnp.int32)
                    self.params, self.opt_state, loss, metrics = self._jit_step(
                        self.params, self.opt_state, batch, step_idx
                    )
                    self.step = i + 1
                    if i % log_every == 0 or i == self.tcfg.steps - 1:
                        with jax.profiler.TraceAnnotation("lad.readback"):
                            history.append((i, float(loss)))
        return history

    def save(self, path: str) -> None:
        """Write the current params as a serving-consumable checkpoint.

        The producer half of the train-to-serve loop: the file restores via
        ``repro.checkpoint.restore_for_serving(path, self.cfg)`` (bitwise for
        fp32 params — asserted by tests/test_serving.py) straight into
        ``launch.serve``'s prefill/decode fns.
        """
        from repro.checkpoint import save_checkpoint

        save_checkpoint(path, self.params, step=self.step, specs=self.specs)

    def eval_loss(self, batch) -> float:
        """Next-token NLL of the current params on one (clean) batch — the
        quality probe the zoo-serve bench records per checkpoint."""
        with self.mesh:
            loss, _ = models.loss_fn(self.params, self.specs, self.cfg, batch,
                                     remat=False)
        return float(loss)
