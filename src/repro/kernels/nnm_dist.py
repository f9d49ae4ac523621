"""Pallas TPU kernel: pairwise squared distances for NNM pre-aggregation.

NNM [23] needs the ``(N, N)`` distance matrix between device messages.  The
compute shape is a Gram matmul over the huge Q axis — MXU work — plus row
norms.  The kernel tiles the contraction: the canonical entry point is
**lane-batched** over a 2-D ``(lane, q_tile)`` grid; for each lane the
programs multiply-accumulate an ``(N, q_block) @ (q_block, N)`` partial Gram
and a partial row-norm into fp32 output accumulators that live across the
q-tile axis (sequential TPU grid semantics, last grid axis fastest — the
revisited output block stays contiguous per lane).  The unbatched ``(N, Q)``
entry is the ``L=1`` special case, bitwise equal per lane.  The trivial
``(N, N)`` distance assembly happens in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import lane_row
from repro.numerics import tree_sum


def _gram_kernel(msgs_ref, gram_ref, sq_ref):
    i = pl.program_id(1)  # q-tile index (axis 0 is the lane axis)
    x = msgs_ref[0].astype(jnp.float32)  # (N, q_block)

    @pl.when(i == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    gram_ref[0] += x @ x.T
    # fixed-tree row norms: a reduce op may accumulate in a different order
    # per program shape (see repro/numerics.py); the Gram matmul is a
    # dot_general with a fixed per-shape lowering
    sq_ref[0, 0] += tree_sum(x * x, axis=1)


@functools.partial(jax.jit, static_argnames=("q_block", "interpret"))
def gram_pallas_lanes(msgs: jax.Array, q_block: int = 2048, interpret: bool = True):
    """msgs: (L, N, Q) -> (gram (L, N, N) fp32, sqnorms (L, N) fp32)."""
    lanes, n, q = msgs.shape
    q_block = min(q_block, q)
    assert q % q_block == 0, (q, q_block)
    gram, sq = pl.pallas_call(
        _gram_kernel,
        grid=(lanes, q // q_block),
        in_specs=[pl.BlockSpec((1, n, q_block), lambda l, i: (l, 0, i))],
        out_specs=[
            pl.BlockSpec((1, n, n), lambda l, i: (l, 0, 0)),
            lane_row(n),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lanes, n, n), jnp.float32),
            jax.ShapeDtypeStruct((lanes, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(msgs)
    return gram, sq[:, 0]
