"""RWKV-6 ("Finch") time-mix and channel-mix blocks [arXiv:2404.05892].

Faithful structure: token-shift interpolation with learned per-channel mixing
coefficients, receptance/key/value/gate projections, **data-dependent decay**
``w_t = exp(-exp(w0 + LoRA(x_shifted)))`` (the Finch contribution over Eagle),
bonus ``u`` for the current token, and the per-head matrix-valued WKV state

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    y_t = r_t @ S_{t-1} + (sum_i r_i u_i k_i) * v_t

(the u-bonus readout factored so that ``u`` is consumed outside the
sequence scan).  Training/prefill
is a lax.scan over the sequence carrying ``S``; decode is the single-step
update.  GroupNorm over heads on the readout, gated by SiLU(g).

The channel-mix is RWKV's squared-ReLU FFN with token shift.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.module import dense_param, scale_param, split_tree, zeros_param


def rwkv_time_mix_init(key, d_model: int, head_dim: int, decay_lora: int, dtype):
    n_heads = d_model // head_dim
    ks = jax.random.split(key, 8)
    return split_tree(
        {
            # token-shift mixing coefficients for r/k/v/g/w
            "mu": scale_param((5, d_model), (None, None), jnp.float32, 0.5),
            "wr": dense_param(ks[0], (d_model, d_model), ("fsdp", "tp"), dtype),
            "wk": dense_param(ks[1], (d_model, d_model), ("fsdp", "tp"), dtype),
            "wv": dense_param(ks[2], (d_model, d_model), ("fsdp", "tp"), dtype),
            "wg": dense_param(ks[3], (d_model, d_model), ("fsdp", "tp"), dtype),
            "wo": dense_param(ks[4], (d_model, d_model), ("tp", "fsdp"), dtype),
            # data-dependent decay LoRA: w = exp(-exp(w0 + tanh(x A) B))
            "w0": zeros_param((d_model,), (None,), jnp.float32),
            "w_lora_a": dense_param(ks[5], (d_model, decay_lora), ("fsdp", None), dtype),
            "w_lora_b": dense_param(ks[6], (decay_lora, d_model), (None, "tp"), dtype),
            "bonus_u": zeros_param((n_heads, head_dim), ("tp", None), jnp.float32),
            "ln_scale": scale_param((d_model,), (None,), jnp.float32, 1.0),
        }
    )


def rwkv_channel_mix_init(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return split_tree(
        {
            "mu": scale_param((2, d_model), (None, None), jnp.float32, 0.5),
            "wk": dense_param(k1, (d_model, d_ff), ("fsdp", "tp"), dtype),
            "wv": dense_param(k2, (d_ff, d_model), ("tp", "fsdp"), dtype),
            "wr": dense_param(k3, (d_model, d_model), ("fsdp", "tp"), dtype),
        }
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RWKVState:
    x_prev: jax.Array  # (B, d_model) last token's input (for token shift)
    wkv: jax.Array  # (B, H, head_dim, head_dim) fp32 state
    ffn_x_prev: jax.Array  # (B, d_model) channel-mix token shift


def init_rwkv_state(batch: int, d_model: int, head_dim: int, dtype) -> RWKVState:
    n_heads = d_model // head_dim
    return RWKVState(
        x_prev=jnp.zeros((batch, d_model), dtype=dtype),
        wkv=jnp.zeros((batch, n_heads, head_dim, head_dim), dtype=jnp.float32),
        ffn_x_prev=jnp.zeros((batch, d_model), dtype=dtype),
    )


def _token_shift(x: jax.Array, x_prev_first: jax.Array | None = None) -> jax.Array:
    """x: (B, S, D) -> previous token's x (zeros or carried state at t=0)."""
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if x_prev_first is not None:
        shifted = shifted.at[:, 0, :].set(x_prev_first.astype(x.dtype))
    return shifted


def _mix(x, x_shift, mu_row):
    """lerp(x, x_shift, mu)."""
    delta = (x_shift - x).astype(jnp.float32)
    return x + (delta * mu_row).astype(x.dtype)


def _projections(params, x, x_shift, head_dim: int):
    mu = params["mu"]
    r = jnp.einsum("bsd,de->bse", _mix(x, x_shift, mu[0]), params["wr"])
    k = jnp.einsum("bsd,de->bse", _mix(x, x_shift, mu[1]), params["wk"])
    v = jnp.einsum("bsd,de->bse", _mix(x, x_shift, mu[2]), params["wv"])
    g = jnp.einsum("bsd,de->bse", _mix(x, x_shift, mu[3]), params["wg"])
    xw = _mix(x, x_shift, mu[4])
    lora_h = jnp.tanh(jnp.einsum("bsd,dr->bsr", xw, params["w_lora_a"]))
    lora = jnp.einsum("bsr,re->bse", lora_h, params["w_lora_b"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(lora + params["w0"]))  # (B,S,D) in (0,1)
    b, s, d = x.shape
    h = d // head_dim
    heads = lambda t: t.reshape(b, s, h, head_dim)
    return heads(r), heads(k), heads(v), g, heads(w.astype(jnp.float32))


def _group_norm(y: jax.Array, scale: jax.Array, head_dim: int, eps: float = 64e-5):
    """Per-head LayerNorm on the readout (RWKV's group_norm).  y: (B,S,H,hd)."""
    yf = y.astype(jnp.float32)
    mean = jnp.mean(yf, axis=-1, keepdims=True)
    var = jnp.var(yf, axis=-1, keepdims=True)
    normed = (yf - mean) * jax.lax.rsqrt(var + eps)
    b, s, h, hd = y.shape
    return normed.reshape(b, s, h * hd) * scale


def rwkv_time_mix(params, x: jax.Array, head_dim: int, state: RWKVState | None = None):
    """Full-sequence RWKV6 time mix.  x: (B, S, D) -> (B, S, D)."""
    b, s, d = x.shape
    x_shift = _token_shift(x, None if state is None else state.x_prev)
    r, k, v, g, w = _projections(params, x, x_shift, head_dim)
    # u-bonus readout, factored outside the scan: s_u = sum_i r_i u_i k_i
    ru_k = (r * k).astype(jnp.float32)  # (B,S,H,hd)
    s_u = jnp.sum(ru_k * params["bonus_u"], axis=-1)  # (B,S,H)

    def step(s_wkv, inputs):
        r_t, k_t, v_t, w_t, su_t = inputs  # (B,H,hd) x4, (B,H)
        kv = k_t[..., :, None].astype(jnp.float32) * v_t[..., None, :].astype(jnp.float32)
        y = jnp.einsum("bhi,bhij->bhj", r_t.astype(jnp.float32), s_wkv)
        y = y + su_t[..., None] * v_t.astype(jnp.float32)
        s_new = w_t[..., :, None] * s_wkv + kv
        return s_new, y

    s0 = (
        jnp.zeros((b, d // head_dim, head_dim, head_dim), dtype=jnp.float32)
        if state is None
        else state.wkv
    )
    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, w)) + (s_u.transpose(1, 0, 2),)
    s_fin, ys = jax.lax.scan(step, s0, xs)  # ys: (S, B, H, hd) fp32
    y = ys.transpose(1, 0, 2, 3)  # (B, S, H, hd)
    y = _group_norm(y, params["ln_scale"], head_dim).astype(x.dtype)
    y = y * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsd,de->bse", y, params["wo"]).astype(x.dtype)
    return out, s_fin, x[:, -1, :]


def rwkv_channel_mix(params, x: jax.Array, state_prev: jax.Array | None = None):
    """RWKV squared-ReLU FFN with token shift.  x: (B, S, D)."""
    mu = params["mu"]
    x_shift = _token_shift(x, state_prev)
    k = jnp.einsum("bsd,df->bsf", _mix(x, x_shift, mu[0]), params["wk"])
    k = jnp.square(jax.nn.relu(k.astype(jnp.float32))).astype(x.dtype)
    r_in = jnp.einsum("bsd,de->bse", _mix(x, x_shift, mu[1]), params["wr"])
    r = jax.nn.sigmoid(r_in.astype(jnp.float32)).astype(x.dtype)
    return r * jnp.einsum("bsf,fd->bsd", k, params["wv"]), x[:, -1, :]