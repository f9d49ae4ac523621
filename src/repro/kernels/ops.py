"""Jit'd public wrappers over the Pallas kernels.

``backend`` selects the implementation:
  * ``"pallas"``      — compiled Pallas (TPU target)
  * ``"interpret"``   — Pallas interpret mode (CPU-correct; used by tests)
  * ``"xla"``         — the pure-jnp oracle (the default: lowers on every
                        backend)

The wrappers own the tiling contract: callers may pass ANY ``Q`` — when the
length does not divide the tile, inputs are zero-padded up to the next tile
boundary here and the output is sliced back.  Zero columns are exact no-ops
for every kernel (they are sliced off for cwtm/combine, contribute 0 to the
gram/row-norm accumulators, and cannot raise a max-abs quantization scale),
so padded and unpadded calls agree bitwise on the real coordinates.  Both
backends see the same padded operands, keeping xla/interpret/pallas parity.

Lane batching: every wrapper also accepts operands with extra *leading* lane
axes (e.g. ``(S, N, Q)`` messages) and runs them through ONE lane-batched
kernel launch over a 2-D ``(lane, q_tile)`` grid, bitwise equal lane-for-lane
to the unbatched call.  ``jax.vmap`` of a wrapper maps onto the same kernel
lane axis instead of falling back or unrolling: each kernel invocation is a
``jax.custom_vmap`` whose batching rule promotes the call to the lane-batched
kernel (and the lane-batched call's own rule *folds* further batch axes into
the lane axis, so nested vmaps — scenario lanes over device lanes, as in the
grid engine — collapse into a single ``(S*N,)`` launch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels import ref
from repro.kernels.attacks import attack_pallas_lanes
from repro.kernels.coded_combine import (
    coded_combine_pallas_lanes,
    gather_combine_pallas_lanes,
    masked_combine_pallas_lanes,
)
from repro.kernels.cwtm import cwtm_pallas_lanes
from repro.kernels.nnm_dist import gram_pallas_lanes
from repro.kernels.quantize import stochastic_quantize_pallas_lanes

DEFAULT_BACKEND = "xla"


def _interp(backend: str) -> bool:
    if backend == "pallas":
        return False
    if backend == "interpret":
        return True
    raise ValueError(backend)


def _pad_last(x: jax.Array, block: int) -> jax.Array:
    """Zero-pad the last axis up to a multiple of ``block``."""
    pad = (-x.shape[-1]) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _tile(q: int, q_block: int) -> int:
    """Effective tile length: never longer than the (unpadded) vector."""
    return min(q_block, q)


# --------------------------------------------------------------- vmap plumbing
#
# Two custom_vmap layers per kernel, built by one generic factory and
# lru-cached per kernel on the static kernel parameters (so the function
# identities — and with them jax's tracing caches — are stable across
# calls):
#
#   single — the unbatched call; its vmap rule PROMOTES to the lanes call
#            (a new leading axis becomes the kernel lane axis);
#   lanes  — the lane-batched call; its vmap rule FOLDS any further batch
#            axis into the existing lane axis and recurses, so arbitrarily
#            nested vmaps stay one kernel launch.
#
# Rules broadcast unbatched operands to the lane axis first (`in_batched`
# may be False for e.g. shared combine weights).


def _ensure_batched(axis_size, args, in_batched):
    return tuple(
        a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
        for a, b in zip(args, in_batched)
    )


def _lane_vmap_pair(lanes_kernel):
    """(single, lanes) custom_vmap callables for a lane-batched kernel.

    ``lanes_kernel`` takes operands with one leading lane axis each and
    returns an array or tuple of arrays with a leading lane axis.
    """

    def batched_flags(out):
        return jax.tree.map(lambda _: True, out)

    @custom_vmap
    def lanes(*args):
        return lanes_kernel(*args)

    @lanes.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = _ensure_batched(axis_size, args, in_batched)
        flat = tuple(a.reshape((-1,) + a.shape[2:]) for a in args)
        out = lanes(*flat)
        out = jax.tree.map(lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, batched_flags(out)

    @custom_vmap
    def single(*args):
        return jax.tree.map(lambda o: o[0], lanes(*(a[None] for a in args)))

    @single.def_vmap
    def _promote(axis_size, in_batched, *args):
        out = lanes(*_ensure_batched(axis_size, args, in_batched))
        return out, batched_flags(out)

    return single, lanes


@functools.lru_cache(maxsize=None)
def _cwtm_fns(trim: int, q_block: int, interpret: bool):
    return _lane_vmap_pair(
        lambda m: cwtm_pallas_lanes(m, trim, q_block=q_block, interpret=interpret)
    )


@functools.lru_cache(maxsize=None)
def _combine_fns(q_block: int, interpret: bool):
    return _lane_vmap_pair(
        lambda g, w: coded_combine_pallas_lanes(
            g, w, q_block=q_block, interpret=interpret
        )
    )


@functools.lru_cache(maxsize=None)
def _quantize_fns(levels: int, q_block: int, interpret: bool):
    return _lane_vmap_pair(
        lambda g, u: stochastic_quantize_pallas_lanes(
            g, u, levels, q_block=q_block, interpret=interpret
        )
    )


@functools.lru_cache(maxsize=None)
def _gram_fns(q_block: int, interpret: bool):
    return _lane_vmap_pair(
        lambda m: tuple(gram_pallas_lanes(m, q_block=q_block, interpret=interpret))
    )


@functools.lru_cache(maxsize=None)
def _masked_combine_fns(q_block: int, interpret: bool):
    return _lane_vmap_pair(
        lambda m, w: masked_combine_pallas_lanes(
            m, w, q_block=q_block, interpret=interpret
        )
    )


@functools.lru_cache(maxsize=None)
def _gather_combine_fns(q_block: int, interpret: bool):
    return _lane_vmap_pair(
        lambda g, s, w: gather_combine_pallas_lanes(
            g, s, w, q_block=q_block, interpret=interpret
        )
    )


@functools.lru_cache(maxsize=None)
def _attack_fns(name: str, param: float, q_block: int, interpret: bool):
    return _lane_vmap_pair(
        lambda m, mk: attack_pallas_lanes(
            m, mk, name, param, q_block=q_block, interpret=interpret
        )
    )


def _flatten_lanes(x: jax.Array, event_ndim: int):
    """Collapse all leading lane axes of ``x`` down to one."""
    lead = x.shape[: x.ndim - event_ndim]
    return x.reshape((-1,) + x.shape[x.ndim - event_ndim :]), lead


# ------------------------------------------------- measured launch crossover
#
# BENCH_kernels.json shows the lane-batched interpret launch LOSING to the
# per-lane dispatch loop at small shapes (the inlined Pallas grid loop beats
# the small cached single-lane program only past a lane-count crossover), so
# the explicit leading-lane-axes path below picks per (op, lane count) from
# the measured crossover table ``benchmarks/kernel_bench.py`` records into
# the tuner store — instead of always lane-batching.  With no measurement the
# table answers "batched" (the previous unconditional behavior).
#
# Scope: ONLY the explicit-lane path.  Under ``jax.vmap`` (the grid engine's
# regime) the custom_vmap rules above always promote/fold to the batched
# launch — a traced lax.switch body cannot host a Python loop, and keeping
# the vmap path single-launch is part of the grid bit-exactness story.
# Either way the values agree bitwise: the loop stacks single-lane calls,
# and a single-lane call IS the one-lane batched launch (see ``single``).

# past this many lanes a Python loop unrolls into an oversized jit program;
# batched launches win well before that in every measurement
_LOOP_UNROLL_MAX = 64


def _use_loop(op: str, lanes: int) -> bool:
    if lanes > _LOOP_UNROLL_MAX:
        return False
    # Deferred import: the tuner is pure Python (no kernels import — no cycle).
    from repro.launch.tuner import lane_dispatch

    return lane_dispatch(op, lanes) == "loop"


def _lane_launch(op: str, fns, *flat_args):
    """Run a lane-flattened kernel call as one batched launch or as a
    per-lane loop of single launches, per the measured crossover table.
    ``fns`` is the ``(single, lanes)`` pair; ``flat_args`` all carry one
    leading lane axis."""
    single, lanes_fn = fns
    n_lanes = flat_args[0].shape[0]
    if _use_loop(op, n_lanes):
        outs = [single(*(a[i] for a in flat_args)) for i in range(n_lanes)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return lanes_fn(*flat_args)


# -------------------------------------------------------------- public wrappers


def cwtm(msgs: jax.Array, trim: int, backend: str = DEFAULT_BACKEND, q_block: int = 2048) -> jax.Array:
    """Coordinate-wise trimmed mean.  msgs: (..., N, Q) -> (..., Q)."""
    if backend == "xla":
        return ref.cwtm_ref(msgs, trim)
    q = msgs.shape[-1]
    qb = _tile(q, q_block)
    padded = _pad_last(msgs, qb)
    if msgs.ndim == 2:
        return _cwtm_fns(trim, qb, _interp(backend))[0](padded)[:q]
    flat, lead = _flatten_lanes(padded, 2)
    out = _lane_launch("cwtm", _cwtm_fns(trim, qb, _interp(backend)), flat)
    return out.reshape(lead + out.shape[-1:])[..., :q]


def coded_combine(
    grads: jax.Array, weights: jax.Array, backend: str = DEFAULT_BACKEND, q_block: int = 2048
) -> jax.Array:
    """eq.-(5) combine.  grads: (..., d, Q), weights: (d,) or (..., d)."""
    if backend == "xla":
        return ref.coded_combine_ref(grads, weights)
    q = grads.shape[-1]
    qb = _tile(q, q_block)
    padded = _pad_last(grads, qb)
    if grads.ndim == 2:
        return _combine_fns(qb, _interp(backend))[0](padded, weights)[:q]
    flat, lead = _flatten_lanes(padded, 2)
    w = jnp.broadcast_to(weights, grads.shape[:-1]).reshape(flat.shape[:-1])
    out = _lane_launch("coded_combine", _combine_fns(qb, _interp(backend)), flat, w)
    return out.reshape(lead + out.shape[-1:])[..., :q]


def masked_combine(
    msgs: jax.Array, weights: jax.Array, backend: str = DEFAULT_BACKEND, q_block: int = 2048
) -> jax.Array:
    """Weighted row-combine over the device axis — the K-of-N erasure
    decode's surviving-class reduce.  msgs: (..., N, Q), weights: (..., N)
    per-device row weights (participation mask x class selection, exact 0.0
    on erased rows) -> (..., Q)."""
    if backend == "xla":
        return ref.masked_combine_ref(msgs, weights)
    q = msgs.shape[-1]
    qb = _tile(q, q_block)
    padded = _pad_last(msgs, qb)
    if msgs.ndim == 2:
        return _masked_combine_fns(qb, _interp(backend))[0](padded, weights)[:q]
    flat, lead = _flatten_lanes(padded, 2)
    w = jnp.broadcast_to(weights, msgs.shape[:-1]).reshape(flat.shape[:-1])
    out = _lane_launch("masked_combine", _masked_combine_fns(qb, _interp(backend)), flat, w)
    return out.reshape(lead + out.shape[-1:])[..., :q]


def stochastic_quantize(
    g: jax.Array,
    u: jax.Array,
    levels: int = 16,
    block: int = 1024,
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """QSGD quantize-dequantize.  g, u: (..., Q) -> (..., Q)."""
    # Pad BEFORE dispatch so both backends quantize identical blocks: the
    # tail block's scale is the max-abs of its real entries (zeros never win).
    q = g.shape[-1]
    qb = _tile(q, block)
    gp, up = _pad_last(g, qb), _pad_last(u, qb)
    if backend == "xla":
        return ref.stochastic_quantize_ref(gp, up, levels, qb)[..., :q]
    if g.ndim == 1:
        return _quantize_fns(levels, qb, _interp(backend))[0](gp, up)[:q]
    gf, lead = _flatten_lanes(gp, 1)
    uf, _ = _flatten_lanes(up, 1)
    out = _lane_launch("quantize", _quantize_fns(levels, qb, _interp(backend)), gf, uf)
    return out.reshape(lead + out.shape[-1:])[..., :q]


def gather_combine(
    grads: jax.Array,
    subsets: jax.Array,
    weights: jax.Array,
    backend: str = DEFAULT_BACKEND,
    q_block: int = 2048,
) -> jax.Array:
    """Fused assignment gather + eq.-(5) combine: every device's ``d``
    assigned subset gradients are gathered and weight-combined in ONE
    lane-batched launch.  grads: (..., N, Q), subsets: (..., N, d) int32,
    weights: (d,) or (..., d) -> (..., N, Q) coded vectors."""
    if backend == "xla":
        return ref.gather_combine_ref(grads, subsets, weights)
    q = grads.shape[-1]
    qb = _tile(q, q_block)
    padded = _pad_last(grads, qb)
    if grads.ndim == 2:
        return _gather_combine_fns(qb, _interp(backend))[0](
            padded, subsets, weights
        )[:, :q]
    flat, lead = _flatten_lanes(padded, 2)
    flat_s, _ = _flatten_lanes(jnp.broadcast_to(subsets, lead + subsets.shape[-2:]), 2)
    w = jnp.broadcast_to(weights, lead + weights.shape[-1:]).reshape(
        (flat.shape[0],) + weights.shape[-1:]
    )
    out = _lane_launch(
        "gather_combine", _gather_combine_fns(qb, _interp(backend)), flat, flat_s, w
    )
    return out.reshape(lead + out.shape[-2:])[..., :q]


def attack(
    msgs: jax.Array,
    mask: jax.Array,
    name: str,
    param: float,
    backend: str = DEFAULT_BACKEND,
    q_block: int = 2048,
) -> jax.Array:
    """Byzantine attack construction (sign_flip / alie / ipm) as one
    lane-batched launch.  msgs: (..., N, Q), mask: (..., N) 0/1 Byzantine
    indicator -> (..., N, Q) transmitted stacks; ``param`` is the attack's
    scalar knob (coeff / z / eps).  The collusion attacks' honest mean and
    variance reduce over ``N`` *inside* the kernel with the same fixed-tree
    sums as the XLA attacks in ``core/attacks.py``."""
    if backend == "xla":
        return ref.attack_ref(msgs, mask, name, param)
    q = msgs.shape[-1]
    qb = _tile(q, q_block)
    padded = _pad_last(msgs, qb)
    if msgs.ndim == 2:
        return _attack_fns(name, param, qb, _interp(backend))[0](padded, mask)[:, :q]
    flat, lead = _flatten_lanes(padded, 2)
    flat_mask, _ = _flatten_lanes(jnp.broadcast_to(mask, lead + mask.shape[-1:]), 1)
    out = _lane_launch(
        "attack", _attack_fns(name, param, qb, _interp(backend)), flat, flat_mask
    )
    return out.reshape(lead + out.shape[-2:])[..., :q]


def pairwise_sqdist(msgs: jax.Array, backend: str = DEFAULT_BACKEND, q_block: int = 2048) -> jax.Array:
    """Pairwise squared distances.  msgs: (..., N, Q) -> (..., N, N)."""
    if backend == "xla":
        return ref.pairwise_sqdist_ref(msgs)
    qb = _tile(msgs.shape[-1], q_block)
    padded = _pad_last(msgs, qb)
    if msgs.ndim == 2:
        gram, sq = _gram_fns(qb, _interp(backend))[0](padded)
    else:
        flat, lead = _flatten_lanes(padded, 2)
        gram, sq = _lane_launch("pairwise_sqdist", _gram_fns(qb, _interp(backend)), flat)
        gram = gram.reshape(lead + gram.shape[-2:])
        sq = sq.reshape(lead + sq.shape[-1:])
    return jnp.maximum(
        sq[..., :, None] + sq[..., None, :] - 2.0 * gram, 0.0
    )
