"""Pallas TPU kernels: Byzantine attack construction, lane-batched.

Until PR 4 the attack stage was the last part of the round body still
executing as plain vmapped XLA inside the grid engine: the collusion attacks
(ALIE, IPM) reduce the honest message stack to per-coordinate statistics and
broadcast an adversarial vector back over the Byzantine rows.  These kernels
move the per-coordinate adversary *construction and application* onto the
same 2-D ``(lane, q_tile)`` grid as the rest of the round body (one lane =
one scenario of the grid engine; the device axis ``N`` stays inside the
block).

The honest-statistics reductions stay OUTSIDE the ``pallas_call`` in exactly
the ``repro/numerics`` tree forms of ``core/attacks.py`` (computed
lane-batched, one XLA expression for all lanes), and the kernels consume the
``(L, Q)`` statistics as operands — their interiors are purely elementwise.
Computing ``mu``/``var`` inside the kernel was measured flipping low bits of
the ALIE adversary between the ``L=1`` (standalone trajectory) and ``L=S``
(grid) program shapes in interpret mode (LLVM re-contracts the mul/add
chains per fusion context), so the reduction half must not move in.

Even in this form, interpret mode only gives the *engine* bitwise stability
for the elementwise sign-flip kernel: wrapping the collusion attacks' apply
step in interpret-mode pallas still perturbs the surrounding fusion enough
to flip scale-dependent low bits, so ``core/attacks.py::make_attack`` routes
ALIE/IPM through these kernels on ``backend="pallas"`` only (Mosaic codegen;
no CPU-LLVM fma discretion) and keeps the plain-XLA forms on
``"interpret"``.  The ops-layer parity tests still verify all three kernels'
semantics in interpret mode (batched == single == vmap bitwise, vs the XLA
oracle to 1 ulp).

The canonical entry points are **lane-batched**; the unbatched call is the
``L=1`` special case, bitwise equal per lane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import _honest_stats_ref
from repro.kernels.tiling import lane_row, lane_rows, row_tiles
from repro.numerics import tree_sum


def _sign_flip_kernel(msgs_ref, mask_ref, out_ref, *, coeff: float):
    m = msgs_ref[0]  # (N, q_block)
    mask = mask_ref[0, 0]  # (N,)
    out_ref[0] = jnp.where(mask[:, None] > 0, coeff * m, m).astype(out_ref.dtype)


def _alie_kernel(msgs_ref, mask_ref, mu_ref, var_ref, out_ref, *, z: float):
    m = msgs_ref[0].astype(jnp.float32)
    mask = mask_ref[0, 0]  # (N,)
    adv = mu_ref[0] - z * jnp.sqrt(var_ref[0] + 1e-12)  # (1, q_block)
    out_ref[0] = jnp.where(mask[:, None] > 0, adv, m).astype(out_ref.dtype)


def _ipm_kernel(msgs_ref, mask_ref, mu_ref, out_ref, *, eps: float):
    m = msgs_ref[0].astype(jnp.float32)
    mask = mask_ref[0, 0]  # (N,)
    adv = -eps * mu_ref[0]  # (1, q_block)
    out_ref[0] = jnp.where(mask[:, None] > 0, adv, m).astype(out_ref.dtype)


def _stat_operands(msgs: jax.Array, mask: jax.Array, name: str):
    """The per-coordinate honest statistics an attack kernel consumes,
    computed lane-batched in the bitwise-stable XLA tree forms (see module
    docstring): ``()`` for sign_flip, ``(mu,)`` for ipm, ``(mu, var)`` for
    alie — each ``(L, Q)``."""
    if name == "sign_flip":
        return ()
    m = msgs.astype(jnp.float32)
    honest_w, h, mu = _honest_stats_ref(m, mask)
    if name == "ipm":
        return (mu,)
    if name == "alie":
        var = tree_sum(((m - mu[..., None, :]) ** 2) * honest_w, axis=-2) / h
        return (mu, var)
    raise KeyError(f"no kernel attack {name!r}")


_KERNELS = {
    "sign_flip": (_sign_flip_kernel, "coeff"),
    "alie": (_alie_kernel, "z"),
    "ipm": (_ipm_kernel, "eps"),
}

# the attacks with a kernel realization -> their AttackSpec scalar knob; the
# single source of truth for the routing in core/attacks.py::make_attack
KERNEL_ATTACK_PARAMS = {name: pname for name, (_, pname) in _KERNELS.items()}


@functools.partial(
    jax.jit, static_argnames=("name", "param", "q_block", "interpret")
)
def attack_pallas_lanes(
    msgs: jax.Array,
    mask: jax.Array,
    name: str,
    param: float,
    q_block: int = 2048,
    interpret: bool = True,
) -> jax.Array:
    """msgs: (L, N, Q), mask: (L, N) -> (L, N, Q) transmitted stacks.

    ``name`` selects the attack kernel, ``param`` its scalar knob
    (sign_flip: coeff, alie: z, ipm: eps).  Q % q_block == 0.
    """
    kernel, pname = _KERNELS[name]
    lanes, n, q = msgs.shape
    assert mask.shape == (lanes, n), (mask.shape, msgs.shape)
    q_block = min(q_block, q)
    assert q % q_block == 0, (q, q_block)
    stats = _stat_operands(msgs, mask, name)
    return pl.pallas_call(
        functools.partial(kernel, **{pname: param}),
        grid=(lanes, q // q_block),
        in_specs=[
            pl.BlockSpec((1, n, q_block), lambda l, i: (l, 0, i)),
            lane_row(n),
        ]
        + [row_tiles(q_block)] * len(stats),
        out_specs=pl.BlockSpec((1, n, q_block), lambda l, i: (l, 0, i)),
        out_shape=jax.ShapeDtypeStruct((lanes, n, q), msgs.dtype),
        interpret=interpret,
    )(msgs, lane_rows(mask), *map(lane_rows, stats))

