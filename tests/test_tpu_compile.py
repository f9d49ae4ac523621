"""The Pallas kernels and the kernel protocol round compile for a TPU v5e.

Nothing runs: each case lowers with ``interpret=False`` and compiles against
a described (not attached) ``v5e:2x2`` topology, so the TPU compiler refuses
here what it would refuse on the chip — block shapes off the (8, 128)
tiling, kernel bodies Mosaic cannot lower, programs that do not fit.

This is the only test file that describes the chip.  The topology is made
inside a module fixture (never at import), which skips when the TPU compiler
cannot be loaded here; the persistent compilation cache is off around the
compiles, since a compile for a described chip cannot be read back.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.attacks import AttackSpec
from repro.core.byzantine import ProtocolConfig, protocol_round
from repro.core.compression import CompressionSpec
from repro.kernels.attacks import attack_pallas_lanes
from repro.kernels.coded_combine import (
    coded_combine_pallas_lanes,
    gather_combine_pallas_lanes,
    masked_combine_pallas_lanes,
)
from repro.kernels.cwtm import cwtm_pallas_lanes
from repro.kernels.nnm_dist import gram_pallas_lanes
from repro.kernels.quantize import stochastic_quantize_pallas_lanes

N, Q, Q_BLOCK, D = 16, 1 << 17, 2048, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# kernel -> (fn, operand shapes) at L lanes; every fn compiles the kernel
# itself (interpret=False), not the ops wrapper, which would see the CPU
KERNELS = {
    "cwtm": lambda L: (
        lambda m: cwtm_pallas_lanes(m, 3, q_block=Q_BLOCK, interpret=False),
        [((L, N, Q), jnp.float32)],
    ),
    "gram": lambda L: (
        lambda m: gram_pallas_lanes(m, q_block=Q_BLOCK, interpret=False),
        [((L, N, Q), jnp.float32)],
    ),
    "coded_combine": lambda L: (
        lambda g, w: coded_combine_pallas_lanes(g, w, q_block=Q_BLOCK, interpret=False),
        [((L, D, Q), jnp.float32), ((L, D), jnp.float32)],
    ),
    "masked_combine": lambda L: (
        lambda m, w: masked_combine_pallas_lanes(m, w, q_block=Q_BLOCK, interpret=False),
        [((L, N, Q), jnp.float32), ((L, N), jnp.float32)],
    ),
    "gather_combine": lambda L: (
        lambda g, s, w: gather_combine_pallas_lanes(g, s, w, q_block=Q_BLOCK, interpret=False),
        [((L, N, Q), jnp.float32), ((L, N, D), jnp.int32), ((L, D), jnp.float32)],
    ),
    "quantize": lambda L: (
        lambda g, u: stochastic_quantize_pallas_lanes(g, u, 16, q_block=1024, interpret=False),
        [((L, Q), jnp.float32), ((L, Q), jnp.float32)],
    ),
    **{
        name: (lambda name, param: lambda L: (
            lambda m, k: attack_pallas_lanes(m, k, name, param, q_block=Q_BLOCK, interpret=False),
            [((L, N, Q), jnp.float32), ((L, N), jnp.float32)],
        ))(name, param)
        for name, param in (("sign_flip", -2.0), ("alie", 1.5), ("ipm", 0.5))
    },
}


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, lanes, one_chip):
    fn, shapes = KERNELS[kernel](lanes)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{kernel}: no Mosaic kernel in the program"


@pytest.mark.parametrize("compressor", ["none", "quant"])
def test_pallas_protocol_round_compiles_for_v5e(compressor, one_chip):
    """The LAD round on the kernels: gather-combine, quantize, sign-flip and
    CWTM, on a (16, 2^20) stack."""
    cfg = ProtocolConfig(
        n_devices=N, d=D, method="lad", aggregator="cwtm", trim_frac=3 / N,
        n_byz=3, attack=AttackSpec("sign_flip", n_byz=3),
        compression=CompressionSpec(compressor), backend="pallas",
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    stack = jax.ShapeDtypeStruct((N, 1 << 20), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda k, g: protocol_round(cfg, k, g)).lower(key, stack).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (4 if compressor == "quant" else 3)


# Temporary bytes of the XLA round below as the row-slice encode compiled
# them (commit fd713fd, the same compile on this described v5e): the
# (N, d, Q) gather and its stacking copies.
SLICE_ENCODE_TEMP_BYTES = 805_596_672


def test_xla_protocol_round_compiles_for_v5e(one_chip):
    """The engine step's LAD round on the XLA path (select encode, sign-flip,
    CWTM) on a (4, 2^24) stack: it compiles for the chip and needs no more
    temporary memory than the row-slice encode did."""
    cfg = ProtocolConfig(
        n_devices=4, d=2, method="lad", aggregator="cwtm", trim_frac=0.25,
        n_byz=1, attack=AttackSpec("sign_flip", n_byz=1),
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    stack = jax.ShapeDtypeStruct((4, 1 << 24), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda k, g: protocol_round(cfg, k, g)).lower(key, stack).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= SLICE_ENCODE_TEMP_BYTES


# Temporary bytes of the same round with CWTM as a stable sort of the stack
# (commit 15d830a, the same compile on this described v5e): the sorted copy,
# its s32 index iota and the sorted indices.
SORT_CWTM_TEMP_BYTES = 537_128_960


def test_xla_round_cwtm_is_one_fusion_for_v5e(one_chip):
    """The same round's server orders the four rows with the compare-exchange
    network: no sort and no index iota over the stack, one fusion for the
    whole of ``lad.aggregate``, and less temporary memory than the sort."""
    q = 1 << 24
    cfg = ProtocolConfig(
        n_devices=4, d=2, method="lad", aggregator="cwtm", trim_frac=0.25,
        n_byz=1, attack=AttackSpec("sign_flip", n_byz=1),
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    stack = jax.ShapeDtypeStruct((4, q), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda k, g: protocol_round(cfg, k, g)).lower(key, stack).compile()
    hlo = compiled.as_text()
    large = [line for line in hlo.splitlines() if f",{q}]" in line]
    assert not [line for line in large if re.search(r"\b(sort|iota)\(", line)]
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[: entry.index("\n}")]
    aggregate = [
        line for line in entry.splitlines()
        if "lad.aggregate" in line and f",{q}]" in line and " bitcast(" not in line
    ]
    assert len(aggregate) == 1 and " fusion(" in aggregate[0], aggregate
    assert compiled.memory_analysis().temp_size_in_bytes < SORT_CWTM_TEMP_BYTES
