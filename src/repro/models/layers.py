"""Shared layers: norms, embeddings, RoPE, SwiGLU MLP."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.module import dense_param, scale_param, split_tree


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d_model: int):
    return split_tree({"scale": scale_param((d_model,), (None,))})


def rmsnorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["scale"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embedding_init(key, vocab: int, d_model: int, dtype):
    return split_tree(
        {"table": dense_param(key, (vocab, d_model), ("tp", "fsdp"), dtype, scale=1.0)}
    )


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    """Logits via the (tied or untied) embedding table: (..., d) @ (V, d)^T."""
    return jnp.einsum("...d,vd->...v", x, params["table"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # (hd/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., seq, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoidal position embeddings: (seq, d_model)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((seq, d_model), dtype=jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    # odd d_model: the cos half has floor(d/2) slots but angle has ceil(d/2)
    # columns — the last sin frequency carries no cos partner
    pe = pe.at[:, 1::2].set(jnp.cos(angle[:, : d_model // 2]))
    return pe


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def mlp_init(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return split_tree(
        {
            "w_gate": dense_param(k1, (d_model, d_ff), ("fsdp", "tp"), dtype),
            "w_up": dense_param(k2, (d_model, d_ff), ("fsdp", "tp"), dtype),
            "w_down": dense_param(k3, (d_ff, d_model), ("tp", "fsdp"), dtype),
        }
    )


def mlp(params, x):
    gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("bsf,fd->bsd", act, params["w_down"])
