"""Time CWTM's two orderings on one chip: the compare-exchange network
(``numerics.sort_rows``) against ``jnp.sort``, on an (N, Q) fp32 stack.

    python scripts/cwtm_cut.py [--ns 4 8 16] [--log2q 26] [--reps 10]

Each program reads a seeded stack already on the device (the parameter's
layout is the one the engine round hands its server) and returns the trimmed
mean at ``f = N // 4``; the median of ``--reps`` timed calls, each ended by
``block_until_ready``, is printed per N and ordering as one JSON line.  A
stack the sort cannot hold (it writes a sorted copy and an index array) is
retried at half the length, and the line says so.  The largest N at which
the network wins sets ``aggregators.CWTM_NETWORK_MAX_N``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.numerics import sort_rows  # noqa: E402

HBM_BYTES_PER_S = 819e9  # TPU v5e


def network(msgs: jax.Array, f: int) -> jax.Array:
    n = msgs.shape[0]
    return jnp.mean(jnp.stack(sort_rows([msgs[i] for i in range(n)])[f : n - f]), axis=0)


def by_sort(msgs: jax.Array, f: int) -> jax.Array:
    n = msgs.shape[0]
    return jnp.mean(jnp.sort(msgs, axis=0)[f : n - f], axis=0)


def time_ms(fn, x, reps: int) -> float:
    for _ in range(2):
        fn(x).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--ns", type=int, nargs="+", default=[4, 8, 16])
    parser.add_argument("--log2q", type=int, default=26)
    parser.add_argument("--reps", type=int, default=10)
    args = parser.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    for n in args.ns:
        f = n // 4
        for name, order in (("network", network), ("sort", by_sort)):
            log2q = args.log2q
            while True:
                q = 1 << log2q
                x = jax.random.normal(jax.random.key(n), (n, q), jnp.float32)
                fn = jax.jit(lambda m, order=order: order(m, f))
                try:
                    ms = time_ms(fn, x, args.reps)
                    break
                except jax.errors.JaxRuntimeError as e:
                    if "RESOURCE_EXHAUSTED" not in str(e) or log2q <= 20:
                        raise
                    del x
                    log2q -= 1
            floor_ms = (n + 1) * q * 4 / HBM_BYTES_PER_S * 1e3
            print(json.dumps({
                "n": n, "f": f, "order": name, "log2q": log2q, "ms": round(ms, 3),
                "ms_per_2^26": round(ms * (1 << 26) / q, 3),
                "hbm_floor_ms": round(floor_ms, 3), "device": dev.device_kind,
            }), flush=True)
            del x
    return 0


if __name__ == "__main__":
    sys.exit(main())
