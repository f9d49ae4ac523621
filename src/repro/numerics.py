"""Bitwise-deterministic reductions for the engine's cross-program guarantees.

XLA's ``reduce`` op gives the backend *implementation freedom*: the CPU
emitter picks a partial-sum / vectorization strategy per fusion, so the same
logical reduction can accumulate in a different order in two different
programs (single-trajectory vs vmapped grid, with vs without an inlined
Pallas-interpret subgraph) — a 1-ulp drift that breaks the engine's
bit-exactness guarantee.  Elementwise ops have far less freedom: an add DAG
built from elementwise adds is evaluated as written in every program shape,
up to the backend's remaining fused-multiply-add discretion (see below).

These helpers therefore compute sums as an explicit fixed binary tree of
elementwise adds (zero-padding to a power of two — exact no-ops for sums).
They are plain differentiable/vmappable jax ops.

The second half of the guarantee lives in ``core/engine.py``: XLA freely
*duplicates* producer subgraphs into consumer fusions, where a copy may
compile differently per module — so even a value that is bitwise-stable as a
program output can be recomputed differently at a use site.  Scan outputs
are materialized buffers XLA never recomputes, so the engine computes all
metric reductions AFTER the scan on the stacked raw trajectory
(``_finalize_metrics``).

Known limits of what can be pinned from JAX on the CPU backend (verified
against jaxlib 0.4.x; revisit on upgrade):
  * ``optimization_barrier`` is expanded away BEFORE fusion — it neither
    splits fusions nor blocks producer duplication (and it has no batching
    or differentiation rule);
  * a single-trip ``while_loop`` is unrolled and its loop-invariant body
    hoisted, so it cannot force materialization either;
  * LLVM may still contract a multiply feeding an add into an fma
    differently per module — there is no CPU flag to pin this.
Tree-form reductions + post-scan metrics remove every *reduce*-level
freedom; the residual fma discretion is why the bitwise guarantee is
asserted at the simulation scales the tests and benchmarks actually run
(see README "Engine guarantees") rather than claimed universally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "tree_sum", "tree_sum_rows", "sort_rows", "stable_norm", "stable_mean0",
    "stable_masked_mean0",
]


def _pad_pow2(v: jax.Array, axis: int) -> jax.Array:
    n = v.shape[axis]
    p = 1 << max(0, n - 1).bit_length()  # next power of two >= n
    if p == n:
        return v
    widths = [(0, 0)] * v.ndim
    widths[axis] = (0, p - n)
    return jnp.pad(v, widths)


def tree_sum(v: jax.Array, axis: int = -1) -> jax.Array:
    """Sum along ``axis`` as a fixed binary tree of elementwise adds.

    No ``reduce`` op is emitted, so the accumulation order cannot vary with
    the backend's per-fusion reduce strategy; equals ``jnp.sum`` up to the
    usual 1-ulp reassociation difference.
    """
    axis = axis % v.ndim
    v = _pad_pow2(v, axis)
    while v.shape[axis] > 1:
        h = v.shape[axis] // 2
        lo = jax.lax.slice_in_dim(v, 0, h, axis=axis)
        hi = jax.lax.slice_in_dim(v, h, 2 * h, axis=axis)
        v = lo + hi
    return jax.lax.squeeze(v, (axis,))


def tree_sum_rows(rows) -> jax.Array:
    """``tree_sum(jnp.stack(rows), axis=0)`` over a list of equal-shape
    arrays, with the same adds in the same tree (zero-padded to a power of
    two).  For kernel bodies, which keep rows as separate values because
    Mosaic cannot slice a stacked tile at every row offset."""
    rows = list(rows)
    p = 1 << max(0, len(rows) - 1).bit_length()
    rows += [jnp.zeros_like(rows[0])] * (p - len(rows))
    while len(rows) > 1:
        h = len(rows) // 2
        rows = [rows[i] + rows[i + h] for i in range(h)]
    return rows[0]


def sort_rows(rows) -> list:
    """Sort a list of equal-shape arrays elementwise, in ``jnp.sort``'s order:
    stable, ``-0 == +0``, NaN last.

    An odd-even transposition network: ``n`` passes of compare-exchanges
    between neighbouring rows, static and branch-free.  An exchange swaps only
    where ``b < a`` or ``a`` is NaN and ``b`` is not, so equal values keep
    their order and a NaN stays in its own slot (``minimum``/``maximum``
    would spread it to both).  NaN is found by comparison alone (``b == b``
    is false only for NaN), so the same network lowers in a Pallas kernel.
    """
    rows = list(rows)
    n = len(rows)
    for phase in range(n):
        for i in range(phase % 2, n - 1, 2):
            a, b = rows[i], rows[i + 1]
            swap = (b == b) & ~(a <= b)  # b < a, or a is NaN and b is not
            rows[i], rows[i + 1] = jnp.where(swap, b, a), jnp.where(swap, a, b)
    return rows


def stable_norm(v: jax.Array) -> jax.Array:
    """L2 norm over the last axis with a fixed-tree accumulation."""
    v = v.astype(jnp.float32)
    return jnp.sqrt(tree_sum(v * v, axis=-1))


def stable_mean0(m: jax.Array) -> jax.Array:
    """Mean over axis 0 (the device axis) with a fixed-tree accumulation."""
    return tree_sum(m.astype(jnp.float32), axis=0) * jnp.float32(1.0 / m.shape[0])


def stable_masked_mean0(m: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean over the reporting rows of axis 0 (``mask`` is ``(N,)`` 0/1
    float32) with a fixed-tree accumulation.

    Masked rows contribute exact ``0.0`` terms to the add tree — the
    participation-erasure contract — and the count divisor is the exact
    integer-valued ``tree_sum(mask)``.  NOTE: at an all-ones mask this is
    ``tree_sum(m) / N``, a true division, whereas :func:`stable_mean0` is a
    multiply by ``1/N`` — bitwise different when ``1/N`` is not dyadic.
    Callers needing all-ones == legacy bitwise must use the impute-then-
    aggregate pattern (see ``byzantine.make_server_fn``) instead.
    """
    m = m.astype(jnp.float32)
    w = mask.astype(jnp.float32)
    num = tree_sum(m * w[:, None] if m.ndim == 2 else m * w, axis=0)
    return num / jnp.maximum(tree_sum(w, axis=0), 1.0)
