"""The long-row eq.-(5) encode selects the assigned rows instead of slicing
and stacking them.

The oracle is the slice form the XLA path used before: ``N * d`` unrolled
dynamic row slices stacked into an ``(N, d, Q)`` tensor, then ``_encode``.
The selected rows must equal the sliced ones bit for bit (compared as
``uint32``, so signed zeros and NaN payloads count), and so must the coded
stack, wherever IEEE 754 fixes the bits of the sum: where two operands of
the eq.-(5) sum are NaNs of different bit patterns (or +inf meets -inf next
to a NaN), the payload of the result is the compiler's choice, and the same
reduce over the same rows may keep either; there the coded value must be a
NaN.  The optimised program must hold neither the gathered tensor nor a
row-sized dynamic slice.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import byzantine
from repro.core.attacks import AttackSpec
from repro.core.byzantine import ProtocolConfig, protocol_round

Q = (1 << 16) + 37  # long rows, not a multiple of 128


def _oracle(cfg, g, subsets):
    """The slice form: ``x[subsets]`` as unrolled dynamic row slices, then
    the eq.-(5) mean over the stacked ``(N, d, Q)`` rows."""
    stacked = jnp.stack([
        jnp.stack([jax.lax.dynamic_index_in_dim(g, i, keepdims=False) for i in row])
        for row in subsets
    ])
    return byzantine._encode(cfg, stacked)


def _stack(seed, n, q=Q):
    """Random rows seeded with +-0.0, +-inf and NaNs of several payloads."""
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, q)).astype(np.float32)
    bits = g.view(np.uint32)
    special = np.array(
        [0x00000000, 0x80000000, 0x7F800000, 0xFF800000, 0x7FC00000,
         0xFFC00000, 0x7FC01234, 0xFFC0BEEF], np.uint32)
    at = rng.choice(n * q, size=(n * q) // 8, replace=False)
    bits.reshape(-1)[at] = rng.choice(special, size=at.size)
    # one whole row of -0.0 and one column of NaNs across every row
    bits[rng.integers(n)] = 0x80000000
    bits[:, 5] = 0x7FC05678
    return jnp.asarray(g)


def _cfg(n, d):
    return ProtocolConfig(n_devices=n, d=d, method="lad", aggregator="cwtm",
                          trim_frac=0.25, n_byz=1, attack=AttackSpec("sign_flip", n_byz=1))


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _assert_coded_equal(coded, want, g, subsets):
    """Bitwise, except where the sum's NaN payload is not fixed (docstring)."""
    got, ref = _bits(coded), _bits(want)
    rows = np.asarray(g)[np.asarray(subsets)]  # (N, d, Q) operands
    nan_bits = np.where(np.isnan(rows), rows.view(np.uint32), 0)
    nan_kinds = np.zeros(ref.shape, np.int64)
    for k in range(rows.shape[1]):
        seen = np.zeros(ref.shape, bool)
        for j in range(k):
            seen |= nan_bits[:, j] == nan_bits[:, k]
        nan_kinds += (nan_bits[:, k] != 0) & ~seen
    made = np.any(rows == np.inf, axis=1) & np.any(rows == -np.inf, axis=1)
    free = nan_kinds + made >= 2
    np.testing.assert_array_equal(np.where(free, 0, got), np.where(free, 0, ref))
    assert np.isnan(np.asarray(coded)[free]).all() and np.isnan(np.asarray(want)[free]).all()


@pytest.mark.parametrize("d", [1, 2, 3])
@pytest.mark.parametrize("n", [4, 10, 16])
def test_select_encode_bitwise_equals_slice_form(n, d):
    cfg = _cfg(n, d)
    g = _stack(1000 * n + d, n)
    key = jax.random.PRNGKey(7 * n + d)

    @jax.jit
    def both(key, g):
        coded, subsets, _ = byzantine._device_coded_gradients(cfg, key, g)
        return coded, _oracle(cfg, g, subsets), subsets

    coded, want, subsets = both(key, g)
    assert coded.shape == (n, Q)
    _assert_coded_equal(coded, want, g, subsets)


@pytest.mark.parametrize("n", [4, 16, 40])
def test_selected_rows_bitwise_equal_gather(n):
    """The selection alone, every NaN payload included: ``x[idx]``, at any
    number of candidate rows."""
    g = _stack(77 + n, n)
    idx = jax.random.randint(jax.random.PRNGKey(n), (n, 3), 0, n)
    got = jax.jit(byzantine._select_rows)(g, idx)
    np.testing.assert_array_equal(_bits(got), _bits(g)[np.asarray(idx)])


def test_select_encode_bitwise_under_vmap():
    """The LM-grid shape: a lane axis in front, one index table per lane."""
    cfg = _cfg(4, 2)
    lanes = 3
    g = jnp.stack([_stack(50 + i, 4) for i in range(lanes)])
    keys = jax.random.split(jax.random.PRNGKey(3), lanes)

    def one(key, g):
        coded, subsets, _ = byzantine._device_coded_gradients(cfg, key, g)
        return coded, _oracle(cfg, g, subsets), subsets

    coded, want, subsets = jax.jit(jax.vmap(one))(keys, g)
    assert coded.shape == (lanes, 4, Q)
    for lane in range(lanes):
        _assert_coded_equal(coded[lane], want[lane], g[lane], subsets[lane])


def test_round_hlo_has_no_gathered_stack():
    """The x4 cell's round at (4, 2^20): no (N, d, Q) buffer (an instruction
    outside a fusion) and no row-sized dynamic slice anywhere in the
    optimised program."""
    q = 1 << 20
    cfg = _cfg(4, 2)
    hlo = jax.jit(lambda k, g: protocol_round(cfg, k, g)).lower(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4, q), jnp.float32)
    ).compile().as_text()
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", hlo))
    buffers = [
        line for comp in hlo.split("\n\n") if comp.split(" ", 1)[0] not in fused
        for line in comp.splitlines() if f"f32[4,2,{q}]" in line.split(" = ", 1)[-1][:40]
    ]
    assert not buffers, buffers[:2]
    slices = [line for line in hlo.splitlines()
              if f"f32[1,{q}]" in line and " dynamic-slice(" in line]
    assert not slices, slices[:2]


def test_encode_path_counter():
    """The engine step's x4 round selects; a short-row grid gathers; a
    d = 1 long-row round keeps the slices; kernel backends use the kernel."""
    before = byzantine.encode_path_info()

    def trace(cfg, shape):
        jax.jit(lambda k, g: protocol_round(cfg, k, g)).lower(
            jax.random.PRNGKey(0), jax.ShapeDtypeStruct(shape, jnp.float32))

    trace(_cfg(4, 2), (4, 1 << 20))
    trace(_cfg(10, 3), (10, 100))
    trace(ProtocolConfig(n_devices=4, d=1, method="plain", aggregator="mean",
                         attack=AttackSpec(name="none")), (4, 1 << 20))
    trace(ProtocolConfig(n_devices=4, d=2, backend="interpret"), (4, 1 << 16))
    after = byzantine.encode_path_info()
    assert {k: after[k] - before[k] for k in after} == dict(
        select=1, slice=1, gather=1, kernel=1)
