"""CWTM's compare-exchange network equals the sort-based oracle exactly.

``aggregators.cwtm`` orders up to ``CWTM_NETWORK_MAX_N`` rows with
``numerics.sort_rows`` and sorts above it; ``kernels/ref.cwtm_ref`` always
sorts.  Every case compares the two with ``assert_array_equal`` (NaN equals
NaN, -0 equals +0) on rows mixed with signed zeros, infinities, NaNs of both
signs and ties, and checks that a whole NaN or +inf row from one Byzantine
worker is trimmed at ``f >= 1``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators as agg
from repro.core.byzantine import ProtocolConfig, make_server_fn
from repro.core.participation import ParticipationSpec
from repro.kernels import ops, ref

Q = 384
LANES = 3
NS = range(2, agg.CWTM_NETWORK_MAX_N + 3)  # past the cut: the sort path too
CASES = [(n, f) for n in NS for f in range((n - 1) // 2 + 1)]
SPECIALS = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan], np.float32)


def _trim_frac(n: int, f: int) -> float:
    """A ``trim_frac`` with ``int(trim_frac * n) == f``."""
    return (f + 0.5) / n


def _mixed(n: int, seed: int, lead=()) -> np.ndarray:
    """Random rows with ~30 % special values and ~15 % ties across rows."""
    rng = np.random.default_rng(seed)
    shape = lead + (n, Q)
    x = rng.standard_normal(shape).astype(np.float32)
    x = np.where(rng.random(shape) < 0.15, x[..., :1, :], x)
    specials = SPECIALS[rng.integers(0, len(SPECIALS), shape)]
    return np.where(rng.random(shape) < 0.3, specials, x)


def _byzantine(n: int, seed: int, value: float) -> np.ndarray:
    """Finite honest rows and one worker's row set wholly to ``value``."""
    x = np.random.default_rng(seed).standard_normal((n, Q)).astype(np.float32)
    x[seed % n] = value
    return x


@pytest.mark.parametrize("n,f", CASES)
def test_cwtm_equals_sort_oracle_under_jit(n, f):
    cwtm = jax.jit(functools.partial(agg.cwtm, trim_frac=_trim_frac(n, f)))
    x = _mixed(n, seed=100 * n + f)
    np.testing.assert_array_equal(cwtm(x), ref.cwtm_ref(jnp.asarray(x), f))
    if f == 0:
        return
    for value in (np.nan, np.inf):
        x = _byzantine(n, seed=100 * n + f, value=value)
        out = np.asarray(cwtm(x))
        np.testing.assert_array_equal(out, ref.cwtm_ref(jnp.asarray(x), f))
        assert np.isfinite(out).all(), f"a {value} row was not trimmed"


@pytest.mark.parametrize("n,f", sorted({(n, f) for n in NS for f in (0, (n - 1) // 2)}))
def test_cwtm_equals_sort_oracle_under_vmap(n, f):
    cwtm = functools.partial(agg.cwtm, trim_frac=_trim_frac(n, f))
    x = _mixed(n, seed=7 * n + f, lead=(LANES,))
    np.testing.assert_array_equal(
        jax.jit(jax.vmap(cwtm))(x), ref.cwtm_ref(jnp.asarray(x), f)
    )


@pytest.mark.parametrize("n", [n for n in NS if n >= 3])
def test_nnm_then_cwtm_equals_sort_oracle(n):
    """NNM premix, then CWTM, against the oracle on the same premixed stack
    (one program, so both read the same mix)."""
    f = (n - 1) // 2
    b = max(1, f)
    rule = agg.nnm_then(functools.partial(agg.cwtm, trim_frac=_trim_frac(n, f)), n_byz=b)
    x = _byzantine(n, seed=n, value=1e6)
    out, want = jax.jit(lambda m: (rule(m), ref.cwtm_ref(agg.nnm_mix(m, b), f)))(x)
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("n", NS)
def test_masked_server_all_ones_equals_sort_oracle(n):
    """Impute-then-aggregate at an all-ones mask hands CWTM the stack as is."""
    f = (n - 1) // 2
    cfg = ProtocolConfig(
        n_devices=n, d=1, method="plain", aggregator="cwtm", trim_frac=_trim_frac(n, f),
        n_byz=f, participation=ParticipationSpec("iid", rate=0.0),
    )
    server = jax.jit(make_server_fn(cfg))
    x = _mixed(n, seed=3 * n)
    out = server(x, jnp.ones((n,), jnp.float32), jnp.zeros((n, 1), jnp.int32))
    np.testing.assert_array_equal(out, ref.cwtm_ref(jnp.asarray(x), f))


@pytest.mark.parametrize("value", [np.nan, np.inf])
def test_pallas_cwtm_trims_a_nan_or_inf_row(value):
    """The kernel shares the network: a NaN or +inf row at trim 1 is trimmed
    as the oracle trims it (a min/max network spread the NaN everywhere)."""
    x = jnp.asarray(_byzantine(4, seed=2, value=value)[:, :256].repeat(16, axis=1))
    out = np.asarray(ops.cwtm(x, 1, backend="interpret"))
    np.testing.assert_array_equal(out, ref.cwtm_ref(x, 1))
    assert np.isfinite(out).all()
