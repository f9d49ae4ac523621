"""Shared fixtures + the `slow` marker.

NOTE: no device-count XLA_FLAGS here — smoke tests and benches must see the
1 real CPU device.  Tests that need a small virtual mesh spawn a subprocess
(see tests/test_stage_scopes.py) or run single-device shard_map.

The suite is jit-compile bound (~140 tests, each compiling small programs),
so we trim LLVM's expensive passes for test runs: correctness is unchanged,
compile time drops substantially.  Unset XLA_FLAGS to benchmark real compile
output; the flags are only applied when the caller set none.

Flag notes (load-bearing for the engine's bit-exactness tests):
  * ``--xla_cpu_enable_fast_math=false`` — fast-math licenses LLVM to
    reassociate/contract f32 chains differently per program shape, which
    breaks the grid==single-trajectory BITWISE guarantee by 1 ulp;
  * optimization level 1, not 0: at level 0 the CPU backend's codegen also
    varies 1-ulp between vmapped and single programs even with fast-math
    off (level 1 is deterministic and nearly as fast to compile).

Tests marked ``@pytest.mark.slow`` (multi-minute subprocess meshes, the
biggest architecture smoke configs) are skipped by default so the tier-1
run stays fast; run them with ``pytest --runslow``.
"""
import os

if "XLA_FLAGS" not in os.environ:  # must happen before jax initializes XLA
    os.environ["XLA_FLAGS"] = (
        "--xla_backend_optimization_level=1 "
        "--xla_llvm_disable_expensive_passes=true "
        "--xla_cpu_enable_fast_math=false"
    )

import jax
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, skipped unless --runslow is given"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow: needs --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
