"""Block specs of the lane-batched kernels that the TPU compiler accepts.

Mosaic takes a block only if its last two dims are divisible by ``(8, 128)``
or equal to the array's.  One lane's row of a 2-D ``(L, X)`` operand would be
a ``(1, X)`` block whose leading 1 is neither (unless ``L == 1``), so every
such operand travels as ``(L, 1, X)`` (``lane_rows``): its ``(1, 1, w)``
block ends in ``(1, w)``, the 1 now equal to the full middle dim.  The
wrappers add that unit axis on the way in and drop it on the way out; both
are free reshapes.

Per-lane scalars that a kernel body reads one at a time (combine weights,
subset ids) ride the same ``(L, 1, X)`` layout in SMEM (``smem=True``).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def lane_rows(x: jax.Array) -> jax.Array:
    """``(L, X) -> (L, 1, X)``: the layout of every per-lane row operand."""
    return x[:, None, :]


def row_tiles(q_block: int) -> pl.BlockSpec:
    """The ``q_block``-wide tiles of an ``(L, 1, Q)`` row, on the
    ``(lane, q_tile)`` grid."""
    return pl.BlockSpec((1, 1, q_block), lambda l, i: (l, 0, i))


def lane_row(width: int, smem: bool = False) -> pl.BlockSpec:
    """The whole ``(1, 1, width)`` row of one lane, the same on every
    q-tile; ``smem=True`` puts it in scalar memory for per-element reads."""
    if smem:
        return pl.BlockSpec(
            (1, 1, width), lambda l, i: (l, 0, 0), memory_space=pltpu.SMEM
        )
    return pl.BlockSpec((1, 1, width), lambda l, i: (l, 0, 0))
