"""Pallas TPU kernel: QSGD stochastic quantization (Com-LAD wire encoder).

Fuses per-block max-abs scale, level mapping, stochastic rounding and dequant
in one VMEM pass.  The rounding randomness ``u ~ U[0,1)`` is an input (the
device derives it from its round key), so kernel and oracle are bit-exact.

Tiling: the canonical entry point is **lane-batched** — ``(L, Q)`` stacks of
independent vectors (scenario x device lanes under the grid engine) over a
2-D ``(lane, q_tile)`` grid; the quantization block equals the kernel tile
(one scale per tile), keeping the scale reduction entirely in-VMEM.  The
unbatched ``(Q,)`` entry is the ``L=1`` special case, bitwise equal per lane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import lane_rows, row_tiles


def _quant_kernel(g_ref, u_ref, out_ref, *, levels: int):
    g = g_ref[0].astype(jnp.float32)  # (1, q_block): one lane's block
    u = u_ref[0]
    scale = jnp.max(jnp.abs(g))
    safe = jnp.where(scale > 0, scale, 1.0)
    y = g / safe * levels
    lo = jnp.floor(y)
    yq = lo + (u < (y - lo)).astype(jnp.float32)
    out = jnp.where(scale > 0, yq / levels * safe, 0.0)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("levels", "q_block", "interpret"))
def stochastic_quantize_pallas_lanes(
    g: jax.Array, u: jax.Array, levels: int = 16, q_block: int = 1024, interpret: bool = True
) -> jax.Array:
    """g, u: (L, Q) -> (L, Q) per-lane dequantized stochastic quantization."""
    lanes, q = g.shape
    assert u.shape == g.shape, (u.shape, g.shape)
    q_block = min(q_block, q)
    assert q % q_block == 0, (q, q_block)
    out = pl.pallas_call(
        functools.partial(_quant_kernel, levels=levels),
        grid=(lanes, q // q_block),
        in_specs=[row_tiles(q_block), row_tiles(q_block)],
        out_specs=row_tiles(q_block),
        out_shape=jax.ShapeDtypeStruct((lanes, 1, q), g.dtype),
        interpret=interpret,
    )(lane_rows(g), lane_rows(u))
    return out[:, 0]
