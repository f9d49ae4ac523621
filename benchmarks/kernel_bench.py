"""Microbenchmarks of the protocol hot-spots (CPU timings: relative only;
the TPU picture comes from the chip benchmark under ``bench/``, not from
these timings).

Besides the single-call rows, ``lane_batched_bench`` times every Pallas
kernel in its lane-batched form (ONE 2-D ``(lane, q_tile)`` grid launch over
a stack of independent lanes) against the per-lane dispatch loop it
replaced — the kernel-level view of the grid engine's whole-sweep speedup.

``write_kernel_json`` emits the rows as machine-readable
``benchmarks/out/BENCH_kernels.json`` (schema below) so the perf trajectory
is tracked across PRs; ``scripts/bench_smoke.py`` validates the schema in
tier-1.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from repro.core import aggregators as agg
from repro.core.compression import CompressionSpec
from repro.kernels import ops
from repro.timing import block_time

SCHEMA_VERSION = 1


def _time(fn, *args, iters=20):
    """Mean wall-clock per call in us — ``repro.timing.block_time`` (the
    shared blocking timer: monotonic clock, block_until_ready every
    iteration) scaled to this file's microsecond row unit."""
    return block_time(fn, *args, iters=iters) * 1e6  # us


def aggregator_bench(
    n: int = 32,
    q: int = 1 << 20,
    iters: int = 20,
    names=("mean", "median", "cwtm", "cwtm-nnm", "geomed", "krum", "tgn", "mcc"),
):
    """Server-side aggregation cost per rule over (N, Q) messages."""
    key = jax.random.PRNGKey(0)
    msgs = jax.random.normal(key, (n, q))
    rows = []
    for name in names:
        a = jax.jit(agg.make_aggregator(name, n_byz=n // 4, trim_frac=0.2))
        us = _time(a, msgs, iters=iters)
        rows.append((f"agg_{name}", us, msgs.size * 4 / (us * 1e-6) / 1e9))
    return rows


def kernel_vs_ref_bench(n: int = 16, q: int = 1 << 16, iters: int = 10):
    """Pallas-interpret vs pure-jnp oracle (correct-path check + relative cost)."""
    key = jax.random.PRNGKey(1)
    msgs = jax.random.normal(key, (n, q))
    rows = []
    t_ref = _time(jax.jit(lambda m: ops.cwtm(m, 2, backend="xla")), msgs, iters=iters)
    rows.append(("cwtm_xla_ref", t_ref, 0.0))
    grads = jax.random.normal(key, (8, q))
    w = jnp.full((8,), 0.125)
    t = _time(jax.jit(lambda g: ops.coded_combine(g, w, backend="xla")), grads, iters=iters)
    rows.append(("coded_combine_xla", t, 0.0))
    return rows


def lane_batched_bench(
    lanes: int = 8, n: int = 16, d: int = 8, q: int = 1 << 14, iters: int = 5,
    store=None,
):
    """Lane-batched kernel launch vs the per-lane dispatch loop it replaced.

    Rows come in pairs per kernel: ``<op>_lanes_batched`` (one 2-D-grid
    launch over ``lanes`` stacked inputs; ``derived`` = lane count) and
    ``<op>_per_lane_loop`` (a Python loop of single-lane launches;
    ``derived`` = t_loop / t_batched).  All on the interpret backend, where
    the Pallas grid loop is inlined into the XLA program — on CPU that
    inlining can make the batched launch *slower per call* than the small
    cached single-lane program (derived < 1), which is honest CPU-interpret
    data, not the deployment story: the lane batching wins at the engine
    level (grid_timing.csv ``kernel_*`` rows — fewer compiles, zero
    per-scenario dispatches on a warm sweep) and as one kernel launch on a
    real TPU.

    The batched side goes through ``jax.vmap`` of the single-lane wrapper —
    the custom_vmap promote rule, which ALWAYS lane-batches — so the
    measurement stays a clean batched-vs-loop pair even now that the
    wrappers' explicit-lane path dispatches from the crossover table this
    very bench feeds.  Pass a ``repro.launch.tuner.TunerStore`` as ``store``
    to record each measured pair into that table (``benchmarks/run.py``
    does; the tiny-shape tier-1 smoke passes none and records nothing).
    """
    key = jax.random.PRNGKey(2)
    rows = []

    def record(name, t_b, t_l):
        rows.append((f"{name}_lanes_batched", t_b, float(lanes)))
        rows.append((f"{name}_per_lane_loop", t_l, t_l / t_b))
        if store is not None:
            from repro.launch.tuner import record_crossover

            record_crossover(name, lanes, t_b, t_l, store=store)

    def pair(name, batched_fn, batched_arg, single_fn, lanes_of):
        t_b = _time(batched_fn, batched_arg, iters=iters)
        jax.block_until_ready(single_fn(lanes_of(0)))  # warm single program

        def loop(a):
            return [single_fn(lanes_of(i)) for i in range(lanes)]

        t_l = _time(loop, batched_arg, iters=iters)
        record(name, t_b, t_l)

    msgs = jax.random.normal(key, (lanes, n, q))
    cw_b = jax.jit(jax.vmap(lambda m: ops.cwtm(m, 2, backend="interpret")))
    cw_s = jax.jit(lambda m: ops.cwtm(m, 2, backend="interpret"))
    pair("cwtm", cw_b, msgs, cw_s, lambda i: msgs[i])

    grads = jax.random.normal(key, (lanes, d, q))
    w = jnp.full((d,), 1.0 / d, jnp.float32)
    cc_b = jax.jit(jax.vmap(lambda g: ops.coded_combine(g, w, backend="interpret")))
    cc_s = jax.jit(lambda g: ops.coded_combine(g, w, backend="interpret"))
    pair("coded_combine", cc_b, grads, cc_s, lambda i: grads[i])

    g = jax.random.normal(key, (lanes, q))
    u = jax.random.uniform(jax.random.fold_in(key, 1), (lanes, q))
    qz_b = jax.jit(jax.vmap(
        lambda a, b: ops.stochastic_quantize(a, b, 16, 1024, backend="interpret")
    ))
    qz_s = jax.jit(lambda a, b: ops.stochastic_quantize(a, b, 16, 1024, backend="interpret"))
    t_b = _time(qz_b, g, u, iters=iters)
    jax.block_until_ready(qz_s(g[0], u[0]))
    t_l = _time(lambda a, b: [qz_s(a[i], b[i]) for i in range(lanes)], g, u, iters=iters)
    record("quantize", t_b, t_l)

    gr_b = jax.jit(jax.vmap(lambda m: ops.pairwise_sqdist(m, backend="interpret")))
    gr_s = jax.jit(lambda m: ops.pairwise_sqdist(m, backend="interpret"))
    pair("pairwise_sqdist", gr_b, msgs, gr_s, lambda i: msgs[i])
    return rows


def compression_bench(q: int = 1 << 20, iters: int = 10):
    """Compression op cost + achieved wire compression ratio."""
    key = jax.random.PRNGKey(2)
    g = jax.random.normal(key, (q,))
    rows = []
    for text in ["randk:0.3", "randk_shared:0.3", "quant:16", "topk:0.3"]:
        spec = CompressionSpec.parse(text)
        c = jax.jit(spec.make(g.shape[0]))
        us = _time(lambda k: c(k, g), key, iters=iters)
        from repro.core.compression import wire_bits

        ratio = wire_bits(spec, g.shape[0]) / (g.shape[0] * 32)
        rows.append((f"comp_{spec.name}", us, ratio))
    return rows


def write_kernel_json(rows, path):
    """Write bench rows as BENCH_kernels.json.

    Schema (validated by scripts/bench_smoke.py):
      {"schema_version": 1,
       "rows": [{"name": str, "us_per_call": float, "derived": float}, ...]}
    """
    payload = {
        "schema_version": SCHEMA_VERSION,
        "rows": [
            {"name": name, "us_per_call": float(us), "derived": float(derived)}
            for name, us, derived in rows
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return payload
