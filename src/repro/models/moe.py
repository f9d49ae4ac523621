"""Mixture-of-Experts MLP with top-k routing and capacity-bounded dispatch.

The batch's tokens route as one group (the leading ``n`` axis of size 1):
each expert takes its top-``C`` tokens by gate weight into an ``(E, C, D)``
capacity buffer via gather, the grouped SwiGLU einsums run per expert, and
results scatter-add back to their tokens.  Tokens beyond an expert's capacity
are dropped (Switch-style).  The router aux (load-balance) loss is
``n_e * sum_e f_e p_e``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.module import dense_param, split_tree


def moe_init(key, d_model: int, d_ff: int, n_experts: int, dtype):
    kr, kg, ku, kd = jax.random.split(key, 4)
    return split_tree(
        {
            "router": dense_param(kr, (d_model, n_experts), ("fsdp", None), jnp.float32),
            "w_gate": dense_param(kg, (n_experts, d_model, d_ff), ("tp", "fsdp", None), dtype),
            "w_up": dense_param(ku, (n_experts, d_model, d_ff), ("tp", "fsdp", None), dtype),
            "w_down": dense_param(kd, (n_experts, d_ff, d_model), ("tp", None, "fsdp"), dtype),
        }
    )


def expert_capacity(n_tokens: int, n_experts: int, top_k: int, factor: float = 1.25) -> int:
    c = int(n_tokens * top_k / n_experts * factor)
    c = max(8, -(-c // 8) * 8)  # round up to a multiple of 8
    return min(c, n_tokens)


def moe(params, x, *, top_k: int, aux_coef: float = 0.01, capacity_factor: float = 1.25):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar fp32)."""
    b, s, d = x.shape
    n_experts = params["router"].shape[1]
    nb = 1  # routing groups
    t = b * s  # tokens per group
    xb = x.reshape(nb, t, d)

    logits = jnp.einsum("ntd,de->nte", xb.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)  # (n, T, k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # (n, T, E) combine weights, nonzero only at each token's top-k experts
    combine = jnp.sum(
        jax.nn.one_hot(idx, n_experts, dtype=jnp.float32) * gate_vals[..., None], axis=2
    )

    cap = expert_capacity(t, n_experts, top_k, capacity_factor)
    # per-expert top-C tokens by gate weight
    weights_ec, token_idx = jax.lax.top_k(combine.swapaxes(1, 2), cap)  # (n, E, C)

    x_ec = jnp.take_along_axis(
        xb[:, None, :, :], token_idx[..., None], axis=2
    )  # (n, E, C, D) gather dispatch
    gate = jnp.einsum("necd,edf->necf", x_ec, params["w_gate"])
    up = jnp.einsum("necd,edf->necf", x_ec, params["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    y_ec = jnp.einsum("necf,efd->necd", act, params["w_down"])

    y = jnp.zeros((nb, t, d), dtype=jnp.float32)
    contrib = (y_ec * weights_ec[..., None].astype(y_ec.dtype)).astype(jnp.float32)
    n_idx = jnp.arange(nb)[:, None, None]
    y = y.at[n_idx, token_idx, :].add(contrib)

    # load-balance aux loss
    token_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32), axis=2), axis=1
    )  # (n, E)
    prob_frac = jnp.mean(probs, axis=1)  # (n, E)
    aux = aux_coef * n_experts * jnp.mean(jnp.sum(token_frac * prob_frac, axis=-1))
    return y.astype(x.dtype).reshape(b, s, d), aux
