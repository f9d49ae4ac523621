"""Plain float32 reference of the configured training step.

It imports nothing of the program.  From the seed, the configuration file and
the traffic file alone it makes the model's initial weights, runs the first
steps of training on the batches the benchmark made, and returns what the
comparison needs: each step's loss, each parameter leaf's first aggregated
gradient norm and each leaf's change over the steps.

The model is the configuration's own (``bench/models/<model_type>/
reference.py``, found by ``manifest.load_model``): its initial weights, its
leaves' stored dtypes, and its forward pass to a per-token loss plus any
extra loss term, every product through the function given here.  The rest
is this module's, the same for every model.  Products are float32 at
``Precision.HIGHEST``; stored state keeps the dtypes the configuration
states (the model's leaves; Adam moments in ``momentum_dtype``).  A step is
the LAD round of the paper (Algorithm 1): every subset's gradient, the
random cyclic assignment of ``d`` subsets to each of N workers, the eq.-(5)
average, the sign-flip attack of the first ``n_byz`` workers, and
coordinate-wise trimmed mean (or the plain mean of all subset gradients for
``protocol: none``); then AdamW with decoupled weight decay under a
linear-warmup cosine schedule.

``mode="fp8"`` is the control: every product takes float8 operands (e4m3
forward, e5m2 for the cotangents of the backward pass) with one scale per
tensor.  ``fault`` plants one fault in this step, for the readings that set
the limits: ``"half_batch"`` averages the model's per-token loss over the
first half of each sequence only; ``"no_exchange"`` gives every worker's
server only subset 0's gradient, as if the gradients were never exchanged;
``"frozen"`` returns the parameters and the optimizer's state unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from harness.compare import change_norms, leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Spec:
    model: Any  # the model's reference, ``from_config(config)``: frozen, hashable
    n_subsets: int
    rows: int
    seq: int
    protocol: str
    d_load: int
    aggregator: str
    trim_frac: float
    n_byz: int
    attack_coeff: float
    lr: float
    warmup: int
    total_steps: int
    final_frac: float
    b1: float
    b2: float
    adam_eps: float
    weight_decay: float
    momentum_dtype: str
    mode: str = "f32"
    fault: str | None = None


def spec_from(model, config: dict, traffic: dict, mode: str = "f32",
              fault: str | None = None) -> Spec:
    """``model`` is the model's part, as ``manifest.load_model`` gives it."""
    train, sched = config["train"], traffic["schedule"]
    return Spec(
        model=model.reference.from_config(config),
        n_subsets=traffic["n_subsets"], rows=traffic["rows_per_subset"],
        seq=traffic["seq_len"], protocol=traffic["protocol"], d_load=traffic["d"],
        aggregator=traffic["aggregator"], trim_frac=float(traffic["trim_frac"]),
        n_byz=traffic["n_byz"], attack_coeff=float(traffic["attack_coeff"]),
        lr=float(traffic["lr"]), warmup=sched["warmup"], total_steps=sched["total_steps"],
        final_frac=float(sched["final_frac"]), b1=float(train["b1"]), b2=float(train["b2"]),
        adam_eps=float(train["eps"]), weight_decay=float(train["weight_decay"]),
        momentum_dtype=train["momentum_dtype"], mode=mode, fault=fault,
    )


# ----------------------------------------------------------------- weights


@functools.partial(jax.jit, static_argnums=(1,))
def init_params(seed_key, model) -> dict:
    """The model's initial leaves, in one call: leaf name -> float32 array
    holding the stored (dtype-rounded) value."""
    return model.init_params(seed_key)


# ----------------------------------------------------------------- products


def _quantize(x, dtype):
    """``x`` rounded to ``dtype`` under one per-tensor scale, back in f32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(eq, a, b):
    return _einsum(eq, _quantize(a, jnp.float8_e4m3fn), _quantize(b, jnp.float8_e4m3fn))


def _fp8_fwd(eq, a, b):
    qa, qb = _quantize(a, jnp.float8_e4m3fn), _quantize(b, jnp.float8_e4m3fn)
    return _einsum(eq, qa, qb), (qa, qb)


def _fp8_bwd(eq, res, ct):
    _, vjp = jax.vjp(functools.partial(_einsum, eq), *res)
    return vjp(_quantize(ct, jnp.float8_e5m2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


# ----------------------------------------------------------------- loss


def loss_fn(params, tokens, labels, s: Spec):
    """Mean per-token loss of one subset's rows, plus the model's extra term."""
    mm = _fp8_einsum if s.mode == "fp8" else _einsum
    nll, extra = s.model.forward(params, tokens, labels, mm)
    if s.fault == "half_batch":
        nll = nll[:, : s.seq // 2]
    return jnp.mean(nll) + extra


# ----------------------------------------------------------------- protocol


def aggregate(stack, key, s: Spec):
    """The server's output for one leaf: ``stack`` is (N, ...) subset grads."""
    n = s.n_subsets
    if s.fault == "no_exchange":
        stack = jnp.broadcast_to(stack[:1], stack.shape)
    if s.protocol == "none":
        return jnp.mean(stack, axis=0)
    k_assign = jax.random.split(key, 4)[0]
    k_task, k_perm = jax.random.split(k_assign)
    task = jax.random.permutation(k_task, n)
    perm = jax.random.permutation(k_perm, n)
    subsets = perm[(task[:, None] + jnp.arange(s.d_load)[None, :]) % n]  # (N, d)
    # eq. (5), worker by worker: a row is read by a dynamic slice, since
    # XLA:TPU compiles a gather of such long rows slowly
    row = functools.partial(jax.lax.dynamic_index_in_dim, stack, axis=0, keepdims=False)
    coded = jnp.stack([sum(row(subsets[i, j]) for j in range(s.d_load)) / s.d_load
                       for i in range(n)])
    byz = (jnp.arange(n) < s.n_byz).reshape((n,) + (1,) * (stack.ndim - 1))
    sent = jnp.where(byz, s.attack_coeff * coded, coded)
    if s.aggregator == "mean":
        return jnp.mean(sent, axis=0)
    if s.aggregator != "cwtm":
        raise ValueError(f"the reference has no aggregator {s.aggregator!r}")
    f = int(s.trim_frac * n)
    return jnp.mean(jnp.sort(sent, axis=0)[f: n - f], axis=0)


def learning_rate(step, s: Spec):
    warm = s.lr * step / max(s.warmup, 1)
    frac = jnp.clip((step - s.warmup) / max(s.total_steps - s.warmup, 1), 0.0, 1.0)
    cos = s.lr * (s.final_frac + (1.0 - s.final_frac) * 0.5 * (1.0 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < s.warmup, warm, cos)


@functools.partial(jax.jit, static_argnums=(3,))
def subset_grads(params, tokens, labels, s: Spec):
    """Every subset's loss and gradient, one subset at a time: (N,), {leaf: (N, ...)}."""
    tokens = tokens.reshape(s.n_subsets, s.rows, s.seq)
    labels = labels.reshape(s.n_subsets, s.rows, s.seq)
    grad = jax.value_and_grad(loss_fn)
    return jax.lax.map(lambda tl: grad(params, tl[0], tl[1], s), (tokens, labels))


@functools.partial(jax.jit, static_argnums=(6, 7), donate_argnums=(0, 1, 2))
def leaf_step(p, m, v, stack, key, step, s: Spec, dtype):
    """The server and AdamW for one leaf: returns (p, m, v, aggregated grad)."""
    g = aggregate(stack, key, s)
    if s.fault == "frozen":
        return p, m, v, jnp.zeros_like(g)
    lr = learning_rate(step.astype(jnp.float32), s)
    t = step.astype(jnp.float32) + 1.0
    m32 = s.b1 * m.astype(jnp.float32) + (1 - s.b1) * g
    v32 = s.b2 * v.astype(jnp.float32) + (1 - s.b2) * g * g
    upd = (m32 / (1.0 - s.b1 ** t)) / (jnp.sqrt(v32 / (1.0 - s.b2 ** t)) + s.adam_eps)
    p = p - lr * (upd + s.weight_decay * p)
    md = jnp.dtype(s.momentum_dtype)
    return p.astype(dtype).astype(jnp.float32), m32.astype(md), v32.astype(md), g


def run(model, seed: int, config: dict, traffic: dict, batches: list[dict], steps: int,
        mode: str = "f32", fault: str | None = None, log=None) -> dict:
    """The reference's readings over the first ``steps`` steps of training
    of ``model`` (``manifest.load_model``).

    Returns ``{"losses": [...], "first_grad": {leaf: norm}, "change":
    {leaf: norm}}`` with Python floats.  ``log(text)``, if given, hears when
    each step is done."""
    s = spec_from(model, config, traffic, mode, fault)
    base = jax.random.PRNGKey(seed)
    params = init_params(base, s.model)
    # a copy in the stored dtype (exact): the step donates ``params``
    p0 = {k: jnp.array(x, dtype=s.model.leaf_dtype(k), copy=True) for k, x in params.items()}
    md = jnp.dtype(s.momentum_dtype)
    m = {k: jnp.zeros(x.shape, md) for k, x in p0.items()}
    v = {k: jnp.zeros(x.shape, md) for k, x in p0.items()}
    losses, first = [], None
    for i in range(steps):
        round_key = jax.random.fold_in(jax.random.fold_in(base, i), 0)
        loss, grads = subset_grads(params, batches[i]["tokens"], batches[i]["labels"], s)
        losses.append(jnp.mean(loss))
        agg = {}
        for k in sorted(grads):
            params[k], m[k], v[k], agg[k] = leaf_step(
                params[k], m[k], v[k], grads.pop(k), round_key, jnp.int32(i), s,
                s.model.leaf_dtype(k))
        if i == 0:
            first = leaf_norms(agg)
        del agg
        if log is not None:
            log(f"reference step {i} ({float(losses[-1])})")
    change = change_norms(params, p0)
    return {
        "losses": [float(x) for x in losses],
        "first_grad": {k: float(x) for k, x in first.items()},
        "change": {k: float(x) for k, x in change.items()},
    }
