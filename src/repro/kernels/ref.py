"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Like the kernels, every oracle accepts extra *leading* lane axes (the
lane-batched entry points and the grid engine's vmap both produce them);
the unbatched call is the zero-leading-axes special case of the same code
path, so batched and single calls agree bitwise per lane.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.numerics import tree_sum, tree_sum_rows


def cwtm_ref(msgs: jax.Array, trim: int) -> jax.Array:
    """Coordinate-wise trimmed mean.  msgs: (..., N, Q) -> (..., Q)."""
    n = msgs.shape[-2]
    srt = jnp.sort(msgs, axis=-2)
    kept = srt[..., trim : n - trim, :] if trim > 0 else srt
    return jnp.mean(kept.astype(jnp.float32), axis=-2).astype(msgs.dtype)


@jax.jit
def _weighted_row_sum(rows: jax.Array, weights: jax.Array) -> jax.Array:
    """``sum_k weights[..., k] * rows[..., k, :]`` as the combine kernels
    compute it: one weighted row per term, summed in the fixed tree of
    ``tree_sum_rows``.  Jitted, so XLA fuses the multiply-adds as it does in
    the kernels' interpret-mode program (op-by-op execution would round
    every product, a fused program may not)."""
    terms = [
        rows[..., k, :].astype(jnp.float32) * weights[..., k, None].astype(jnp.float32)
        for k in range(rows.shape[-2])
    ]
    return tree_sum_rows(terms).astype(rows.dtype)


def coded_combine_ref(grads: jax.Array, weights: jax.Array) -> jax.Array:
    """eq.-(5) weighted combine.  grads: (..., d, Q), weights: (d,) or
    (..., d) -> (..., Q)."""
    return _weighted_row_sum(grads, jnp.broadcast_to(weights, grads.shape[:-1]))


def masked_combine_ref(msgs: jax.Array, weights: jax.Array) -> jax.Array:
    """Weighted row-combine over the device axis (the erasure decode's
    surviving-class sum).  msgs: (..., N, Q), weights: (..., N) -> (..., Q)."""
    return _weighted_row_sum(msgs, weights)


def stochastic_quantize_ref(
    g: jax.Array, u: jax.Array, levels: int, block: int
) -> jax.Array:
    """QSGD per-block stochastic quantization (dequantized output).

    g, u: (..., Q) with Q % block == 0; u ~ Uniform[0,1) supplies the
    rounding randomness (passed in so kernel and oracle share it
    bit-for-bit).
    """
    gc = g.reshape(-1, block).astype(jnp.float32)
    uc = u.reshape(-1, block)
    scale = jnp.max(jnp.abs(gc), axis=1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    y = gc / safe * levels
    lo = jnp.floor(y)
    yq = lo + (uc < (y - lo)).astype(jnp.float32)
    out = jnp.where(scale > 0, yq / levels * safe, 0.0)
    return out.reshape(g.shape).astype(g.dtype)


def gather_combine_ref(
    grads: jax.Array, subsets: jax.Array, weights: jax.Array
) -> jax.Array:
    """Fused assignment gather + eq.-(5) combine.

    grads: (..., N, Q), subsets: (..., N, d) int32, weights: (d,) or
    (..., d) -> (..., N, Q) coded vectors.
    """
    gathered = jnp.take_along_axis(
        grads[..., None, :], subsets[..., :, :, None], axis=-3
    )  # (..., N, d, Q)
    return _weighted_row_sum(gathered, jnp.broadcast_to(weights, subsets.shape))


def _honest_stats_ref(msgs: jax.Array, mask: jax.Array):
    """(..., N, Q) msgs + (..., N) mask -> honest weights / count / mean,
    in the fixed-tree forms of ``core/attacks.py`` (bitwise parity with the
    attack kernels and the XLA attacks)."""
    honest_w = (1.0 - mask)[..., :, None]
    h = jnp.maximum(tree_sum(1.0 - mask, axis=-1), 1.0)[..., None]
    mu = tree_sum(msgs * honest_w, axis=-2) / h
    return honest_w, h, mu


def attack_ref(msgs: jax.Array, mask: jax.Array, name: str, param: float) -> jax.Array:
    """Lane-generic oracle for the attack kernels.  msgs: (..., N, Q),
    mask: (..., N) -> (..., N, Q) transmitted."""
    byz = mask[..., :, None] > 0
    if name == "sign_flip":
        return jnp.where(byz, param * msgs, msgs)
    if name == "alie":
        honest_w, h, mu = _honest_stats_ref(msgs, mask)
        var = tree_sum(((msgs - mu[..., None, :]) ** 2) * honest_w, axis=-2) / h
        adv = mu - param * jnp.sqrt(var + 1e-12)
        return jnp.where(byz, adv[..., None, :], msgs)
    if name == "ipm":
        _, _, mu = _honest_stats_ref(msgs, mask)
        return jnp.where(byz, (-param * mu)[..., None, :], msgs)
    raise KeyError(f"no kernel attack {name!r}")


def pairwise_sqdist_ref(msgs: jax.Array) -> jax.Array:
    """(..., N, Q) -> (..., N, N) squared euclidean distances (fp32)."""
    m = msgs.astype(jnp.float32)
    sq = jnp.sum(m * m, axis=-1)
    gram = m @ jnp.swapaxes(m, -1, -2)
    return jnp.maximum(
        sq[..., :, None] + sq[..., None, :] - 2.0 * gram, 0.0
    )
