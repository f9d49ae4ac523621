"""Mamba (selective SSM) mixer — the Jamba hybrid's recurrent block.

Faithful Mamba-1 block: in-projection to (x, z), causal depthwise conv,
input-dependent (Δ, B, C) selection, diagonal SSM recurrence

    h_t = exp(Δ_t ⊙ A) h_{t-1} + Δ_t B_t x_t,   y_t = C_t · h_t + D ⊙ x_t,

gated by SiLU(z) and projected out.  Training/prefill runs a lax.scan over
the sequence (TPU-wise this is where a fused selective-scan kernel would go;
the recurrence is kept in fp32).  Decode is the single-step update with the
(conv window, h) state carried in the cache.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.module import dense_param, scale_param, split_tree, zeros_param


def mamba_init(key, d_model: int, d_state: int, d_conv: int, expand: int, dtype):
    d_inner = expand * d_model
    dt_rank = max(1, d_model // 16)
    ks = jax.random.split(key, 6)
    # A initialized to -[1..d_state] per channel (S4D-real), stored as log
    a_log = jnp.log(jnp.tile(jnp.arange(1, d_state + 1, dtype=jnp.float32), (d_inner, 1)))
    return split_tree(
        {
            "in_proj": dense_param(ks[0], (d_model, 2 * d_inner), ("fsdp", "tp"), dtype),
            "conv_w": dense_param(ks[1], (d_conv, d_inner), (None, "tp"), dtype, scale=1.0),
            "conv_b": zeros_param((d_inner,), ("tp",), dtype),
            "x_proj": dense_param(ks[2], (d_inner, dt_rank + 2 * d_state), ("tp", None), dtype),
            "dt_proj": dense_param(ks[3], (dt_rank, d_inner), (None, "tp"), dtype),
            "dt_bias": zeros_param((d_inner,), ("tp",), jnp.float32),
            "a_log": (a_log, ("tp", None)),
            "d_skip": scale_param((d_inner,), ("tp",), jnp.float32, 1.0),
            "out_proj": dense_param(ks[4], (d_inner, d_model), ("tp", "fsdp"), dtype),
        }
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MambaState:
    conv: jax.Array  # (B, d_conv-1, d_inner) trailing inputs
    h: jax.Array  # (B, d_inner, d_state) fp32 SSM state


def init_mamba_state(batch: int, d_inner: int, d_state: int, d_conv: int, dtype) -> MambaState:
    return MambaState(
        conv=jnp.zeros((batch, d_conv - 1, d_inner), dtype=dtype),
        h=jnp.zeros((batch, d_inner, d_state), dtype=jnp.float32),
    )


def _causal_depthwise_conv(xz: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """xz: (B, S, C); w: (K, C) depthwise taps — causal conv along S."""
    k = w.shape[0]
    pad = jnp.pad(xz, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(xz)
    for i in range(k):  # K is tiny (4): unrolled taps beat a conv op here
        out = out + pad[:, i : i + xz.shape[1], :] * w[i]
    return out + b


def _selection(params, x_in: jax.Array, d_state: int, spec_prefix: str):
    """Input-dependent Δ (fp32, softplus), B, C.  x_in: (..., d_inner)."""
    dt_rank = params["dt_proj"].shape[0]
    proj = jnp.einsum(f"{spec_prefix}i,ir->{spec_prefix}r", x_in, params["x_proj"])
    dt_raw, b_sel, c_sel = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    dt = jnp.einsum(f"{spec_prefix}r,ri->{spec_prefix}i", dt_raw,
                    params["dt_proj"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt + params["dt_bias"])
    return dt, b_sel.astype(jnp.float32), c_sel.astype(jnp.float32)


def mamba(params, x: jax.Array, d_state: int, return_state: bool = False):
    """Full-sequence selective scan.  x: (B, S, D) -> (B, S, D)[, MambaState]."""
    b, s, _ = x.shape
    d_conv = params["conv_w"].shape[0]
    xz = jnp.einsum("bsd,di->bsi", x, params["in_proj"])
    x_raw, z = jnp.split(xz, 2, axis=-1)
    x_in = _causal_depthwise_conv(x_raw, params["conv_w"], params["conv_b"])
    x_in = jax.nn.silu(x_in.astype(jnp.float32)).astype(x.dtype)

    dt, b_sel, c_sel = _selection(params, x_in, d_state, "bs")
    a_b = (-jnp.exp(params["a_log"]))[None]  # (1, di, ds)

    def step(h, inputs):
        dt_t, b_t, c_t, x_t = inputs  # (B,di),(B,ds),(B,ds),(B,di)
        dt_r = dt_t.reshape(1, b, -1)
        decay = jnp.exp(dt_r[..., None] * a_b[:, None]).reshape(h.shape[0], -1, d_state)
        h = decay * h + (dt_t * x_t.astype(jnp.float32))[..., None] * b_t[:, None, :]
        y = jnp.einsum("bis,bs->bi", h, c_t)
        return h, y

    h0 = jnp.zeros((b, a_b.shape[1], d_state), dtype=jnp.float32)
    xs = (
        dt.transpose(1, 0, 2),
        b_sel.transpose(1, 0, 2),
        c_sel.transpose(1, 0, 2),
        x_in.transpose(1, 0, 2),
    )
    h_fin, ys = jax.lax.scan(step, h0, xs)  # (S, B, di)
    y = ys.transpose(1, 0, 2) + x_in.astype(jnp.float32) * params["d_skip"]
    y = y.astype(x.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsi,id->bsd", y, params["out_proj"])
    if not return_state:
        return out
    tail = x_raw[:, -(d_conv - 1):, :] if s >= d_conv - 1 else jnp.pad(
        x_raw, ((0, 0), (d_conv - 1 - s, 0), (0, 0))
    )
    return out, MambaState(conv=tail, h=h_fin)


def mamba_decode(params, x: jax.Array, state: MambaState, d_state: int):
    """Single-token step.  x: (B, 1, D) -> (y (B, 1, D), new_state)."""
    xz = jnp.einsum("bsd,di->bsi", x, params["in_proj"])
    x_in, z = jnp.split(xz, 2, axis=-1)  # (B,1,di)
    window = jnp.concatenate([state.conv, x_in], axis=1)  # (B, d_conv, di)
    w = params["conv_w"]
    conv_out = jnp.einsum("bki,ki->bi", window, w) + params["conv_b"]
    x_t = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)  # (B, di)

    dt_rank = params["dt_proj"].shape[0]
    proj = jnp.einsum("bi,ir->br", x_t, params["x_proj"])
    dt_raw, b_sel, c_sel = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    dt = jnp.einsum("br,ri->bi", dt_raw, params["dt_proj"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt + params["dt_bias"])
    b_sel, c_sel = b_sel.astype(jnp.float32), c_sel.astype(jnp.float32)

    a = -jnp.exp(params["a_log"])
    decay = jnp.exp(dt[..., None] * a[None])
    h = decay * state.h + (dt * x_t.astype(jnp.float32))[..., None] * b_sel[:, None, :]
    y = jnp.einsum("bis,bs->bi", h, c_sel) + params["d_skip"][None] * x_t.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z[:, 0].astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bi,id->bd", y, params["out_proj"])[:, None, :]
    return out, MambaState(conv=window[:, 1:], h=h)
