"""Pallas TPU kernel: coordinate-wise trimmed mean (the LAD server hot-spot).

The server aggregates ``N`` device messages of length ``Q`` (per model shard).
CWTM is a per-coordinate sort + trim + mean — a purely memory-bound reduction,
so the win on TPU is fusing sort/trim/mean in VMEM over ``(N, q_block)`` tiles
instead of materializing the ``(N, Q)`` sorted intermediate in HBM (3x HBM
traffic for a jnp.sort-based implementation: read + sorted write + read).

The per-coordinate sort over the tiny static ``N`` axis (16/32 devices) is an
odd-even transposition network (``numerics.sort_rows``, shared with the XLA
server ``aggregators.cwtm``): ``N`` compare-exchange passes on vectors of
width ``q_block`` — each exchange a vectorized compare and select on the VPU,
no data-dependent control flow, NaN ordered last as ``jnp.sort`` does.

Tiling: the canonical entry point is **lane-batched** — ``(L, N, Q)`` stacks
of independent scenario lanes over a 2-D ``(lane, q_tile)`` grid, each program
holding one lane's ``(N, q_block)`` tile in VMEM (default q_block 2048:
32 x 2048 x 4 B = 256 KB, comfortably inside the ~16 MB VMEM budget with
double buffering).  The unbatched ``(N, Q)`` entry is the ``L=1`` special
case, so batched and single calls run the identical per-tile math and agree
bitwise lane-for-lane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import row_tiles
from repro.numerics import sort_rows, tree_sum_rows


def _cwtm_kernel(msgs_ref, out_ref, *, trim: int):
    n = msgs_ref.shape[1]
    # this lane's (N, q_block) tile as N static single-row (1, q_block) loads
    rows = [msgs_ref[0, r : r + 1, :].astype(jnp.float32) for r in range(n)]
    kept = sort_rows(rows)[trim : n - trim]
    # fixed-tree mean, not jnp.mean: a reduce op may accumulate in a
    # different order per program shape, breaking the engine's cross-mode
    # bitwise guarantee (see repro/numerics.py)
    mean = tree_sum_rows(kept) * jnp.float32(1.0 / len(kept))
    out_ref[0] = mean.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("trim", "q_block", "interpret"))
def cwtm_pallas_lanes(
    msgs: jax.Array, trim: int, q_block: int = 2048, interpret: bool = True
) -> jax.Array:
    """msgs: (L, N, Q) -> (L, Q) per-lane trimmed mean.  Q % q_block == 0."""
    lanes, n, q = msgs.shape
    if 2 * trim >= n:
        raise ValueError(f"trim={trim} too large for N={n}")
    q_block = min(q_block, q)
    assert q % q_block == 0, (q, q_block)
    out = pl.pallas_call(
        functools.partial(_cwtm_kernel, trim=trim),
        grid=(lanes, q // q_block),
        in_specs=[pl.BlockSpec((1, n, q_block), lambda l, i: (l, 0, i))],
        out_specs=row_tiles(q_block),
        out_shape=jax.ShapeDtypeStruct((lanes, 1, q), msgs.dtype),
        interpret=interpret,
    )(msgs)
    return out[:, 0]
