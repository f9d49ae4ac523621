"""kappa-robust aggregation rules (Definition 1).

Every aggregator maps a stack of messages ``(N, Q) -> (Q,)``.  The paper's
LAD/Com-LAD is a *meta-algorithm*: any kappa-robust rule plugs in.  We provide
the full menu used by the paper and its baselines:

  * ``mean``                — vanilla averaging (VA baseline; kappa = inf)
  * ``coordinate_median``   — [4], [7]
  * ``cwtm``                — coordinate-wise trimmed mean [7] (paper's main rule)
  * ``geometric_median``    — [6], [8] via Weiszfeld iterations
  * ``krum`` / ``multi_krum`` — [3]
  * ``mcc``                 — maximum-correntropy criterion aggregation [9]
  * ``tgn``                 — thresholding on gradient norms [19] (Com-TGN baseline)
  * ``nnm``                 — nearest-neighbor mixing *pre-aggregation* [23],
                              composed as ``nnm_then(rule)``

All rules are pure jnp (jit/shard_map friendly, static N).  ``kappa_bound``
returns the theoretical robustness coefficient where one is known.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.numerics import sort_rows

Aggregator = Callable[[jax.Array], jax.Array]

__all__ = [
    "mean",
    "coordinate_median",
    "cwtm",
    "geometric_median",
    "krum",
    "multi_krum",
    "mcc",
    "tgn",
    "nnm_mix",
    "nnm_then",
    "make_aggregator",
    "kappa_bound",
    "AGGREGATORS",
]


def mean(msgs: jax.Array) -> jax.Array:
    return jnp.mean(msgs, axis=0)


def coordinate_median(msgs: jax.Array) -> jax.Array:
    return jnp.median(msgs, axis=0)


# CWTM orders at most this many message rows with ``sort_rows``' compare-
# exchange network and more with ``jnp.sort``.  The network costs N(N-1)/2
# exchanges; the sort writes a sorted copy of the stack and an index array.
# On one v5e chip, an (N, 2^26) fp32 stack at f = N/4 (scripts/cwtm_cut.py,
# recorded in PERF.md): network / sort 6.85 / 17.52 ms at N = 4,
# 23.80 / 28.11 at N = 8, 82.94 / 71.48 at N = 16.
CWTM_NETWORK_MAX_N = 8


def cwtm(msgs: jax.Array, trim_frac: float = 0.1) -> jax.Array:
    """Coordinate-wise trimmed mean: drop the ``f`` largest and smallest
    values per coordinate, average the rest.  ``f = floor(trim_frac * N)``.

    The order is ``jnp.sort``'s (stable, NaN last), so a NaN or inf row is
    trimmed like any other extreme; the kept rows are the sort's to the bit.
    """
    n = msgs.shape[0]
    f = int(trim_frac * n)
    if 2 * f >= n:
        raise ValueError(f"trim_frac={trim_frac} removes all {n} messages")
    if n <= CWTM_NETWORK_MAX_N:
        kept = jnp.stack(sort_rows([msgs[i] for i in range(n)])[f : n - f])
    else:
        kept = jnp.sort(msgs, axis=0)[f : n - f]
    return jnp.mean(kept, axis=0)


def geometric_median(msgs: jax.Array, iters: int = 8, eps: float = 1e-8) -> jax.Array:
    """Weiszfeld fixed-point iteration for the geometric median."""

    def body(z, _):
        dist = jnp.sqrt(jnp.sum((msgs - z[None]) ** 2, axis=1) + eps)  # (N,)
        w = 1.0 / dist
        z_new = jnp.sum(w[:, None] * msgs, axis=0) / jnp.sum(w)
        return z_new, None

    z0 = jnp.mean(msgs, axis=0)
    z, _ = jax.lax.scan(body, z0, None, length=iters)
    return z


def _pairwise_sqdist(msgs: jax.Array) -> jax.Array:
    """(N, N) squared euclidean distances via the Gram matrix (MXU friendly)."""
    sq = jnp.sum(msgs * msgs, axis=1)
    gram = msgs @ msgs.T
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    return jnp.maximum(d2, 0.0)


def _krum_scores(msgs: jax.Array, n_byz: int) -> jax.Array:
    """Krum score: sum of distances to the N - b - 2 nearest neighbors."""
    n = msgs.shape[0]
    k = max(n - n_byz - 2, 1)
    d2 = _pairwise_sqdist(msgs)
    d2 = d2 + jnp.eye(n) * jnp.inf  # exclude self
    nearest = jnp.sort(d2, axis=1)[:, :k]
    return jnp.sum(nearest, axis=1)


def krum(msgs: jax.Array, n_byz: int | None = None) -> jax.Array:
    n = msgs.shape[0]
    b = n // 4 if n_byz is None else n_byz
    scores = _krum_scores(msgs, b)
    return msgs[jnp.argmin(scores)]


def multi_krum(msgs: jax.Array, n_byz: int | None = None, m: int | None = None) -> jax.Array:
    n = msgs.shape[0]
    b = n // 4 if n_byz is None else n_byz
    m = (n - b) if m is None else m
    scores = _krum_scores(msgs, b)
    _, idx = jax.lax.top_k(-scores, m)
    return jnp.mean(msgs[idx], axis=0)


def mcc(msgs: jax.Array, sigma: float = 1.0, iters: int = 4) -> jax.Array:
    """Maximum-correntropy aggregation [9]: iteratively reweighted mean with
    Gaussian-kernel weights ``exp(-||g_i - z||^2 / (2 sigma^2 s))`` where the
    bandwidth is scaled by the mean squared deviation ``s`` (self-tuning)."""

    def body(z, _):
        d2 = jnp.sum((msgs - z[None]) ** 2, axis=1)
        # robust bandwidth: median (a mean would be hijacked by large
        # byzantine distances, flattening the weights back to averaging)
        s = jnp.median(d2) + 1e-12
        w = jnp.exp(-d2 / (2.0 * sigma**2 * s))
        z_new = jnp.sum(w[:, None] * msgs, axis=0) / (jnp.sum(w) + 1e-12)
        return z_new, None

    z0 = jnp.median(msgs, axis=0)
    z, _ = jax.lax.scan(body, z0, None, length=iters)
    return z


def tgn(msgs: jax.Array, thresh_frac: float = 0.2, n_byz: int = 0) -> jax.Array:
    """Thresholding on gradient norms [19] (Com-TGN): drop the ``f`` messages
    with the largest norms, average the rest (f covers n_byz when known)."""
    n = msgs.shape[0]
    f = min(max(int(thresh_frac * n), n_byz), n - 1)
    norms = jnp.sum(msgs * msgs, axis=1)
    # keep the n - f smallest-norm messages
    _, idx = jax.lax.top_k(-norms, n - f)
    return jnp.mean(msgs[idx], axis=0)


def nnm_mix(msgs: jax.Array, n_byz: int, d2: jax.Array | None = None) -> jax.Array:
    """Nearest-neighbor mixing [23] pre-aggregation: replace each message by
    the average of its ``N - b`` nearest neighbors (including itself).

    ``d2`` optionally supplies the precomputed ``(N, N)`` squared-distance
    matrix (e.g. from the Pallas gram kernel); the selection rule stays in
    one place either way."""
    n = msgs.shape[0]
    k = n - n_byz
    if d2 is None:
        d2 = _pairwise_sqdist(msgs)
    _, idx = jax.lax.top_k(-d2, k)  # (N, k) nearest-neighbor indices per row
    return jnp.mean(msgs[idx], axis=1)  # (N, Q)


def nnm_then(rule: Aggregator, n_byz: int) -> Aggregator:
    """Compose NNM pre-aggregation with a base rule (e.g. CWTM-NNM)."""

    def agg(msgs: jax.Array) -> jax.Array:
        return rule(nnm_mix(msgs, n_byz))

    return agg


AGGREGATORS = {
    "mean": lambda **kw: mean,
    "median": lambda **kw: coordinate_median,
    "cwtm": lambda trim_frac=0.1, **kw: partial(cwtm, trim_frac=trim_frac),
    "geomed": lambda iters=8, **kw: partial(geometric_median, iters=iters),
    "krum": lambda n_byz=None, **kw: partial(krum, n_byz=n_byz),
    "multi_krum": lambda n_byz=None, **kw: partial(multi_krum, n_byz=n_byz),
    "mcc": lambda sigma=1.0, **kw: partial(mcc, sigma=sigma),
    "tgn": lambda thresh_frac=0.2, n_byz=0, **kw: partial(
        tgn, thresh_frac=thresh_frac, n_byz=n_byz or 0),
}


def make_aggregator(name: str, *, nnm: bool = False, n_byz: int = 0, **kwargs) -> Aggregator:
    """Build an aggregator by name, optionally wrapped with NNM pre-aggregation.

    ``name`` may also carry the suffix ``-nnm`` (e.g. ``"cwtm-nnm"``).
    """
    if name.endswith("-nnm"):
        name, nnm = name[: -len("-nnm")], True
    if name not in AGGREGATORS:
        raise KeyError(f"unknown aggregator {name!r}; have {sorted(AGGREGATORS)}")
    base = AGGREGATORS[name](n_byz=n_byz, **kwargs)
    if nnm:
        return nnm_then(base, n_byz=n_byz)
    return base


def kappa_bound(name: str, n: int, h: int, trim_frac: float = 0.1) -> float:
    """Known robustness coefficients kappa (Definition 1) from [23] Table 1.

    b = N - H Byzantine.  These are order-correct standard bounds used for the
    theory plots; ``inf`` when the rule is not kappa-robust (mean).
    """
    b = n - h
    if b == 0:
        return 0.0
    frac = b / (n - 2 * b) if n > 2 * b else float("inf")
    if name == "mean":
        return float("inf")
    if name in ("median", "geomed"):
        return 4.0 * frac**2 * (1 + frac) ** 2 if frac != float("inf") else float("inf")
    if name == "cwtm":
        return frac * (1.0 + frac)
    if name in ("krum", "multi_krum"):
        return 6.0 * (1 + frac) ** 2 if frac != float("inf") else float("inf")
    if name.endswith("-nnm"):
        base = kappa_bound(name[: -len("-nnm")], n, h, trim_frac)
        # NNM gives kappa = O(b/n) composition ([23] Thm 2): 8 b/h (1 + base-ish)
        return 8.0 * b / h * (1.0 + base) if base != float("inf") else float("inf")
    if name == "mcc":
        return frac * (1.0 + frac)  # no published tight bound; CWTM-like proxy
    if name == "tgn":
        return frac * (1.0 + frac)
    raise KeyError(name)
