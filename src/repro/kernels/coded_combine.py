"""Pallas TPU kernel: eq.-(5) coded-gradient combine.

The device-side encoder reduces its ``d`` stacked subset gradients with
weights ``1/d`` (kept general: arbitrary weights support fractional-repetition
codes too).  Fusing the weighted reduce avoids writing the stacked gradients
back to HBM between accumulation steps.

The canonical entry point is **lane-batched**: ``(L, d, Q)`` stacks (a lane
is one device of one scenario — the grid engine folds scenario x device into
one lane axis) over a 2-D ``(lane, q_tile)`` grid, one ``(d, q_block)`` tile
per program, fp32 accumulation on the VPU.  The unbatched ``(d, Q)`` entry is
the ``L=1`` special case, bitwise equal per lane.

Every combine is a static weighted sum of single rows, ``w_0 g_0 + ... +
w_{d-1} g_{d-1}`` in the fixed tree of ``repro/numerics.py``: each weight is
an SMEM scalar broadcast over a ``(1, q_block)`` row.  (A vector-rhs
``einsum`` lowers to a matmul Mosaic does not implement, and a gather of
rows by an index vector is refused too.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import lane_row, lane_rows, row_tiles
from repro.numerics import tree_sum_rows


def _combine_kernel(w_ref, rows_ref, out_ref):
    k = rows_ref.shape[1]
    terms = [w_ref[0, 0, j] * rows_ref[0, j : j + 1, :].astype(jnp.float32) for j in range(k)]
    out_ref[0] = tree_sum_rows(terms).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_block", "interpret"))
def _row_combine_lanes(rows: jax.Array, weights: jax.Array, q_block: int, interpret: bool):
    """rows: (L, K, Q), weights: (L, K) -> (L, Q) per-lane weighted row sum."""
    lanes, k, q = rows.shape
    assert weights.shape == (lanes, k), (weights.shape, rows.shape)
    q_block = min(q_block, q)
    assert q % q_block == 0, (q, q_block)
    out = pl.pallas_call(
        _combine_kernel,
        grid=(lanes, q // q_block),
        in_specs=[
            lane_row(k, smem=True),
            pl.BlockSpec((1, k, q_block), lambda l, i: (l, 0, i)),
        ],
        out_specs=row_tiles(q_block),
        out_shape=jax.ShapeDtypeStruct((lanes, 1, q), rows.dtype),
        interpret=interpret,
    )(lane_rows(weights.astype(jnp.float32)), rows)
    return out[:, 0]


def coded_combine_pallas_lanes(
    grads: jax.Array, weights: jax.Array, q_block: int = 2048, interpret: bool = True
) -> jax.Array:
    """grads: (L, d, Q), weights: (L, d) -> (L, Q)."""
    return _row_combine_lanes(grads, weights, q_block=q_block, interpret=interpret)


def masked_combine_pallas_lanes(
    msgs: jax.Array, weights: jax.Array, q_block: int = 2048, interpret: bool = True
) -> jax.Array:
    """Weighted row-combine over the device axis, lane-batched.

    msgs: (L, N, Q) transmitted coded vectors, weights: (L, N) per-device
    row weights (participation mask x decode selection) -> (L, Q).  This is
    the server-side dual of ``coded_combine_pallas_lanes``: the same kernel
    with the reduce over *devices* instead of assigned subsets, used by the
    cyclic erasure decode to sum a surviving offset class in one launch.
    Erased rows carry weight exactly 0.0, so they cannot perturb the sum.
    """
    return _row_combine_lanes(msgs, weights, q_block=q_block, interpret=interpret)


def _gather_combine_kernel(subsets_ref, w_ref, grads_ref, out_ref):
    n = grads_ref.shape[1]
    d = w_ref.shape[2]
    w = [w_ref[0, 0, j] for j in range(d)]
    for r in range(n):
        # device r's d assigned subset rows, each a dynamic single-row load
        # at the SMEM subset id, then the eq.-(5) weighted combine
        terms = [
            w[j] * grads_ref[0, pl.ds(subsets_ref[0, 0, r * d + j], 1), :].astype(jnp.float32)
            for j in range(d)
        ]
        out_ref[0, r : r + 1, :] = tree_sum_rows(terms).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_block", "interpret"))
def gather_combine_pallas_lanes(
    grads: jax.Array,
    subsets: jax.Array,
    weights: jax.Array,
    q_block: int = 2048,
    interpret: bool = True,
) -> jax.Array:
    """Fused assignment gather + eq.-(5) combine, lane-batched.

    grads: (L, N, Q) subset-gradient stacks, subsets: (L, N, d) int32 per-
    device subset ids (the cyclic/fractional-repetition task assignment),
    weights: (L, d) -> (L, N, Q) coded vectors.

    Before this kernel the grid engine materialized the gathered
    ``(S, N, d, Q)`` stack in XLA and only the combine ran on the kernel
    lane path; fusing the gather keeps the whole encode stage lane-resident
    (one launch over the ``(lane, q_tile)`` grid — here a lane is one
    *scenario*; the device axis stays inside the block because the gather
    indexes across all N subset rows).
    """
    lanes, n, q = grads.shape
    d = subsets.shape[-1]
    assert subsets.shape == (lanes, n, d), (subsets.shape, grads.shape)
    assert weights.shape == (lanes, d), (weights.shape, subsets.shape)
    q_block = min(q_block, q)
    assert q % q_block == 0, (q, q_block)
    return pl.pallas_call(
        _gather_combine_kernel,
        grid=(lanes, q // q_block),
        in_specs=[
            lane_row(n * d, smem=True),
            lane_row(d, smem=True),
            pl.BlockSpec((1, n, q_block), lambda l, i: (l, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, n, q_block), lambda l, i: (l, 0, i)),
        out_shape=jax.ShapeDtypeStruct((lanes, n, q), grads.dtype),
        interpret=interpret,
    )(
        subsets.astype(jnp.int32).reshape(lanes, 1, n * d),
        lane_rows(weights.astype(jnp.float32)),
        grads,
    )
