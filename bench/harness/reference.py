"""Plain float32 reference of the configured training step.

It imports nothing of the program.  From the seed, the configuration file and
the traffic file alone it makes the model's initial weights, runs the first
steps of training on the batches the benchmark made, and returns what the
comparison needs: each step's loss, each parameter leaf's first aggregated
gradient norm and each leaf's change over the steps.

The model is a Llama-architecture decoder with tied embeddings (RMSNorm,
rotary positions, grouped-query causal attention, SwiGLU), with every
product in float32 at ``Precision.HIGHEST``.  Stored state keeps the dtypes
the configuration states: bfloat16 weight matrices and Adam moments, float32
norm scales.  A step is the LAD round of the paper (Algorithm 1): every
subset's gradient, the random cyclic assignment of ``d`` subsets to each of
N workers, the eq.-(5) average, the sign-flip attack of the first ``n_byz``
workers, and coordinate-wise trimmed mean (or the plain mean of all subset
gradients for ``protocol: none``); then AdamW with decoupled weight decay
under a linear-warmup cosine schedule.

The initial weights follow the program's initialiser, which the architecture
does not fix: truncated normal on [-2, 2] scaled by 1/sqrt(first dim), from
the key tree ``split(PRNGKey(seed), 5)`` -> per layer ``split(k, 1)[0]`` ->
``split(., 4)`` (attention, MLP) -> per weight; norm scales are one.

``mode="fp8"`` is the control: every product takes float8 operands (e4m3
forward, e5m2 for the cotangents of the backward pass) with one scale per
tensor.  ``fault`` plants one fault in this step, for the readings that set
the limits: ``"half_batch"`` averages the loss over the first half of each
sequence only; ``"no_exchange"`` gives every worker's server only subset 0's
gradient, as if the gradients were never exchanged; ``"frozen"`` returns
the parameters and the optimizer's state unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from harness.compare import change_norms, leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_PREFIX = "periods/blk0/"


@dataclasses.dataclass(frozen=True)
class Spec:
    vocab: int
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    layers: int
    norm_eps: float
    theta: float
    param_dtype: str
    norm_dtype: str
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


def spec_from(config: dict, traffic: dict, mode: str = "f32",
              fault: str | None = None) -> Spec:
    if not config["tie_word_embeddings"]:
        raise ValueError("the reference covers tied embeddings only")
    train, sched = config["train"], traffic["schedule"]
    heads = config["num_attention_heads"]
    return Spec(
        vocab=config["vocab_size"], d=config["hidden_size"], heads=heads,
        kv=config["num_key_value_heads"],
        hd=config.get("head_dim", config["hidden_size"] // heads),
        ff=config["intermediate_size"], layers=config["num_hidden_layers"],
        norm_eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        param_dtype=train["param_dtype"], norm_dtype=train["norm_dtype"],
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


def _trunc(key, shape, fan_in, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def init_params(seed_key, s: Spec) -> dict:
    """Leaf name -> float32 array holding the stored (dtype-rounded) value."""
    dt = jnp.dtype(s.param_dtype)
    k_emb, k_blocks = jax.random.split(seed_key, 5)[:2]

    def layer(k):
        k_attn, k_mlp = jax.random.split(jax.random.split(k, 1)[0], 4)[:2]
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        k1, k2, k3 = jax.random.split(k_mlp, 3)
        return {
            "mixer/wq": _trunc(kq, (s.d, s.heads, s.hd), s.d, dt),
            "mixer/wk": _trunc(kk, (s.d, s.kv, s.hd), s.d, dt),
            "mixer/wv": _trunc(kv, (s.d, s.kv, s.hd), s.d, dt),
            "mixer/wo": _trunc(ko, (s.heads, s.hd, s.d), s.heads, dt),
            "mlp/w_gate": _trunc(k1, (s.d, s.ff), s.d, dt),
            "mlp/w_up": _trunc(k2, (s.d, s.ff), s.d, dt),
            "mlp/w_down": _trunc(k3, (s.ff, s.d), s.ff, dt),
        }

    stacked = jax.vmap(layer)(jax.random.split(k_blocks, s.layers))
    params = {LAYER_PREFIX + k: v for k, v in stacked.items()}
    ones = jnp.ones((s.layers, s.d), jnp.float32)
    params[LAYER_PREFIX + "ln1"] = ones
    params[LAYER_PREFIX + "ln2"] = ones
    params["embed/table"] = _trunc(k_emb, (s.vocab, s.d), s.vocab, dt)
    params["ln_f"] = jnp.ones((s.d,), jnp.float32)
    return params


def leaf_dtype(s: Spec, name: str):
    return jnp.dtype(s.norm_dtype if name.endswith(("ln1", "ln2", "ln_f")) else s.param_dtype)


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


# ----------------------------------------------------------------- model


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary positions, rotate-half form: x (B, S, heads, hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _layer(s: Spec, mm, x, w):
    b, n = x.shape[:2]
    h = _rmsnorm(x, w["ln1"], s.norm_eps)
    q = _rope(mm("bsd,dhk->bshk", h, w["mixer/wq"]), s.theta)
    k = _rope(mm("bsd,dhk->bshk", h, w["mixer/wk"]), s.theta)
    v = mm("bsd,dhk->bshk", h, w["mixer/wv"])
    q = q.reshape(b, n, s.kv, s.heads // s.kv, s.hd)  # query head j reads kv head j // g
    logits = mm("bqhgd,bkhd->bhgqk", q, k) * (1.0 / math.sqrt(s.hd))
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = mm("bhgqk,bkhd->bqhgd", probs, v).reshape(b, n, s.heads, s.hd)
    x = x + mm("bshk,hkd->bsd", o, w["mixer/wo"])
    h = _rmsnorm(x, w["ln2"], s.norm_eps)
    act = jax.nn.silu(mm("bsd,df->bsf", h, w["mlp/w_gate"])) * mm("bsd,df->bsf", h, w["mlp/w_up"])
    return x + mm("bsf,fd->bsd", act, w["mlp/w_down"])


def loss_fn(params, tokens, labels, s: Spec):
    """Mean next-token cross entropy of one subset's rows."""
    mm = _fp8_einsum if s.mode == "fp8" else _einsum
    layers = {k[len(LAYER_PREFIX):]: v for k, v in params.items() if k.startswith(LAYER_PREFIX)}
    table = params["embed/table"]
    x = table[tokens]
    body = jax.checkpoint(lambda x, w: (_layer(s, mm, x, w), None))
    x, _ = jax.lax.scan(body, x, layers)
    x = _rmsnorm(x, params["ln_f"], s.norm_eps)
    logits = mm("bsd,vd->bsv", x, table)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    if s.fault == "half_batch":
        nll = nll[:, : s.seq // 2]
    return jnp.mean(nll)


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


def run(seed: int, config: dict, traffic: dict, batches: list[dict], steps: int,
        mode: str = "f32", fault: str | None = None, log=None) -> dict:
    """The reference's readings over the first ``steps`` steps of training.

    Returns ``{"losses": [...], "first_grad": {leaf: norm}, "change":
    {leaf: norm}}`` with Python floats.  ``log(text)``, if given, hears when
    each step is done."""
    s = spec_from(config, traffic, mode, fault)
    base = jax.random.PRNGKey(seed)
    params = init_params(base, s)
    # a copy in the stored dtype (exact): the step donates ``params``
    p0 = {k: jnp.array(x, dtype=leaf_dtype(s, k), copy=True) for k, x in params.items()}
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
                leaf_dtype(s, k))
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
