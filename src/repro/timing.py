"""The one blocking wall-clock timer shared by every perf path.

This module is the single implementation of a wall-clock timing loop:
monotonic ``time.perf_counter`` (``time.time()`` is NON-monotonic — NTP slew
or clock steps can make an interval appear negative), and for device work a
``jax.block_until_ready`` on EVERY iteration — async dispatch otherwise lets
the loop enqueue without finishing, timing only the final drain.

``block_time`` returns seconds (the unit of every BENCH_*.json value);
callers needing microseconds scale at the call site.
"""
from __future__ import annotations

import time
from typing import Any, Callable

__all__ = ["block_time", "wallclock"]


def wallclock() -> float:
    """Monotonic wall-clock seconds — the only clock perf code may read.

    (``time.time()`` is wall time subject to NTP adjustment; an interval
    measured across a clock step is garbage.  Every elapsed-time measurement
    in benchmarks/, launch/ and the tuner goes through here.)
    """
    return time.perf_counter()


def block_time(
    fn: Callable[..., Any], *args: Any, iters: int = 1, warmup: int = 1
) -> float:
    """Mean wall-clock seconds per call of ``fn(*args)``, blocking on every
    iteration.

    ``warmup`` un-timed calls run first (compile + cache warm); pass
    ``warmup=0`` to include compile time in the measurement (cold timing).
    """
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    import jax  # deferred: keep the module importable before jax init flags

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = wallclock()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (wallclock() - t0) / iters
