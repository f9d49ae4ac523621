"""Serving: prefill and single-token decode against per-block caches.

Decode state layout mirrors the period-scan parameter layout: one cache entry
per block position in the period, every leaf stacked over periods (leading P
axis), so the decode step is a single ``lax.scan`` over periods.

Cache kinds per mixer:
  * ``attn`` / ``attn_nope`` — ring-buffer ``KVCache`` (capacity = full
    ``seq_len`` for ordinary decode, ``long_window`` for sliding-window
    long-context decode)
  * ``cross``                — fixed encoder K/V (written at prefill)
  * ``mamba``                — conv window + fp32 SSM state (O(1) in context)
  * ``rwkv``                 — token-shift + fp32 WKV matrix state (O(1))
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockSpec
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models.transformer import _block_apply, _encode_frontend


def _cache_capacity(cfg: ArchConfig, spec: BlockSpec, seq_len: int) -> int:
    if spec.sliding_window is not None:
        return min(spec.sliding_window, seq_len)
    if cfg.long_context == "window" and seq_len > cfg.long_window:
        return cfg.long_window
    return seq_len


def block_cache_init(cfg: ArchConfig, spec: BlockSpec, batch: int, seq_len: int, filled: int):
    """Zero-initialized cache for one block (single period slice)."""
    hd = cfg.resolved_head_dim
    if spec.mixer in ("attn", "attn_nope"):
        cap = _cache_capacity(cfg, spec, seq_len)
        c = attn_lib.init_cache(batch, cap, cfg.n_kv_heads, hd, cfg.dtype)
        return attn_lib.KVCache(k=c.k, v=c.v, length=jnp.asarray(filled, jnp.int32))
    if spec.mixer == "cross":
        n_src = cfg.encoder.n_frontend_tokens
        c = attn_lib.init_cache(batch, n_src, cfg.n_kv_heads, hd, cfg.dtype)
        return attn_lib.KVCache(k=c.k, v=c.v, length=jnp.asarray(filled, jnp.int32))
    if spec.mixer == "mamba":
        mc = cfg.mamba
        return mamba_lib.init_mamba_state(
            batch, mc.expand * cfg.d_model, mc.d_state, mc.d_conv, cfg.dtype
        )
    if spec.mixer == "rwkv":
        return rwkv_lib.init_rwkv_state(batch, cfg.d_model, cfg.rwkv.head_dim, cfg.dtype)
    raise ValueError(spec.mixer)


def init_decode_state(cfg: ArchConfig, batch: int, seq_len: int, filled: int | None = None):
    """Full decode state: per-block caches stacked over periods, plus an
    explicit ``"pos"`` counter (absolute position of the next token).

    ``filled`` — number of tokens already in the cache (default ``seq_len``: one new
    token against a full cache).
    ``"pos"`` is the position source of truth for decode-time position
    embeddings: block caches are not reliable here (a cross-attention or
    recurrent first block never advances a ``length``).
    """
    filled = seq_len if filled is None else filled

    def stack(make):
        leaves = [make() for _ in range(cfg.n_periods)]
        return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *leaves)

    state = {
        f"blk{i}": stack(lambda i=i: block_cache_init(cfg, cfg.period[i], batch, seq_len, filled))
        for i in range(len(cfg.period))
    }
    state["pos"] = jnp.asarray(filled, jnp.int32)
    return state


def _sinusoidal_at(pos, d_model: int) -> jax.Array:
    """Single-position sinusoidal embedding (dynamic position).  -> (d_model,).

    Matches ``layers.sinusoidal_positions(seq, d_model)[pos]`` exactly, for
    even AND odd ``d_model`` (the cos half has floor(d/2) slots, one fewer
    than ``angle`` when d is odd)."""
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)
    angle = jnp.asarray(pos).astype(jnp.float32) / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((d_model,), dtype=jnp.float32)
    pe = pe.at[0::2].set(jnp.sin(angle))
    pe = pe.at[1::2].set(jnp.cos(angle[: d_model // 2]))
    return pe


def _block_decode(cfg: ArchConfig, spec: BlockSpec, bp, x, bcache):
    """x: (B, 1, D) -> (x, new_cache).  Pre-norm residual wiring as in train."""
    normed = L.rmsnorm({"scale": bp["ln1"]}, x, cfg.norm_eps)
    if spec.mixer in ("attn", "attn_nope"):
        h, bcache = attn_lib.decode_attention(
            bp["mixer"], normed, bcache,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            rope_theta=cfg.rope_theta if spec.mixer == "attn" else None,
            window=spec.sliding_window,
        )
    elif spec.mixer == "cross":
        h, bcache = attn_lib.decode_attention(
            bp["mixer"], normed, bcache,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=None, cross=True,
        )
    elif spec.mixer == "mamba":
        h, bcache = mamba_lib.mamba_decode(bp["mixer"], normed, bcache, cfg.mamba.d_state)
    elif spec.mixer == "rwkv":
        h, wkv, x_last = rwkv_lib.rwkv_time_mix(
            bp["mixer"], normed, cfg.rwkv.head_dim, state=bcache
        )
        bcache = rwkv_lib.RWKVState(x_prev=x_last, wkv=wkv, ffn_x_prev=bcache.ffn_x_prev)
    else:
        raise ValueError(spec.mixer)
    x = x + h

    if spec.mlp != "none":
        normed = L.rmsnorm({"scale": bp["ln2"]}, x, cfg.norm_eps)
        if spec.mlp == "dense":
            h = L.mlp(bp["mlp"], normed)
        elif spec.mlp == "moe":
            h, _ = moe_lib.moe(bp["mlp"], normed, top_k=cfg.moe.top_k, aux_coef=0.0)
        elif spec.mlp == "rwkv_ffn":
            h, ffn_x = rwkv_lib.rwkv_channel_mix(bp["mlp"], normed, state_prev=bcache.ffn_x_prev)
            bcache = rwkv_lib.RWKVState(
                x_prev=bcache.x_prev, wkv=bcache.wkv, ffn_x_prev=ffn_x
            )
        x = x + h
    return x, bcache


def decode_step(
    params,
    specs,
    cfg: ArchConfig,
    token: jax.Array,
    state,
):
    """One decode step.  token: (B, 1) int32 -> (logits (B, V) fp32, state).

    ``state["pos"]`` carries the absolute position of the incoming token
    (after prefilling s tokens, decode step t sees position ``s + t``); it is
    what positions the audio family's sinusoidal embedding — block caches are
    not consulted for position, since a cross-attention or recurrent first
    block never advances a ``length`` during decode.
    """
    del specs
    pos = state["pos"]
    caches = {k: v for k, v in state.items() if k != "pos"}
    emb_table = params["embed"]["table"]
    x = jnp.take(emb_table, token, axis=0)
    if cfg.family == "audio":
        x = x + _sinusoidal_at(pos, cfg.d_model)[None, None].astype(x.dtype)

    def body(x, xs):
        pp, caches = xs
        new_caches = {}
        for i, spec in enumerate(cfg.period):
            x, new_caches[f"blk{i}"] = _block_decode(cfg, spec, pp[f"blk{i}"], x, caches[f"blk{i}"])
        return x, new_caches

    x, new_state = jax.lax.scan(body, x, (params["periods"], caches))
    new_state["pos"] = pos + 1

    x = L.rmsnorm({"scale": params["ln_f"]}, x, cfg.norm_eps)
    head = emb_table if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", x, head)
    return logits[:, 0, :].astype(jnp.float32), new_state


def prefill(
    params,
    specs,
    cfg: ArchConfig,
    tokens: jax.Array,
    *,
    frontend: jax.Array | None = None,
    capacity: int | None = None,
):
    """Prefill: full forward + cache construction.

    Returns (last-position logits (B, V), decode state).  Attention K/V are
    written into a ring buffer of ``capacity`` slots (default: seq_len —
    pass seq_len + max_new_tokens to decode past the prompt without
    evicting position 0); recurrent blocks keep their final states.
    """
    del specs
    b, s = tokens.shape
    emb_table = params["embed"]["table"]
    x = jnp.take(emb_table, tokens, axis=0)
    if cfg.family == "audio":
        x = x + L.sinusoidal_positions(s, cfg.d_model)[None].astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    cross_src = None
    if cfg.family in ("vlm", "audio"):
        assert frontend is not None
        cross_src = _encode_frontend(params, cfg, frontend)

    def body(x, pp):
        new_caches = {}
        for i, spec in enumerate(cfg.period):
            x, new_caches[f"blk{i}"] = _block_prefill(
                cfg, spec, pp[f"blk{i}"], x, positions, cross_src, s, capacity
            )
        return x, new_caches

    x, state = jax.lax.scan(body, x, params["periods"])
    state["pos"] = jnp.asarray(s, jnp.int32)

    x = L.rmsnorm({"scale": params["ln_f"]}, x, cfg.norm_eps)
    last = x[:, -1, :]
    head = emb_table if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,vd->bv", last, head)
    return logits.astype(jnp.float32), state


def _block_prefill(cfg, spec: BlockSpec, bp, x, positions, cross_src, seq_len,
                   capacity: int | None = None):
    normed = L.rmsnorm({"scale": bp["ln1"]}, x, cfg.norm_eps)
    b = x.shape[0]
    if spec.mixer in ("attn", "attn_nope"):
        h, k, v = attn_lib.multihead_attention(
            bp["mixer"], normed, positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            rope_theta=cfg.rope_theta if spec.mixer == "attn" else None,
            causal=True, window=spec.sliding_window,
        )
        cap = _cache_capacity(cfg, spec, seq_len)
        if capacity is not None and spec.sliding_window is None:
            cap = max(cap, capacity)
        kc = k[:, -min(cap, seq_len):].astype(cfg.dtype)
        vc = v[:, -min(cap, seq_len):].astype(cfg.dtype)
        if cap > seq_len:  # headroom slots at the tail of the ring
            pad = ((0, 0), (0, cap - seq_len), (0, 0), (0, 0))
            kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
        elif cap < seq_len:
            # Ring-buffer alignment: decode reads slot i as the largest
            # position p <= pos with p === i (mod cap), so the window's
            # positions [seq_len - cap, seq_len) must land at rows p % cap.
            # The contiguous slice above puts position seq_len - cap + i at
            # row i; rolling by seq_len % cap moves each to its modular slot
            # (a no-op when cap divides seq_len — the old aligned case).
            kc = jnp.roll(kc, seq_len % cap, axis=1)
            vc = jnp.roll(vc, seq_len % cap, axis=1)
        bcache = attn_lib.KVCache(
            k=kc, v=vc, length=jnp.asarray(seq_len, jnp.int32),
        )
    elif spec.mixer == "cross":
        kv_pos = jnp.broadcast_to(
            jnp.arange(cross_src.shape[1], dtype=jnp.int32)[None], cross_src.shape[:2]
        )
        h, k, v = attn_lib.multihead_attention(
            bp["mixer"], normed, positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=None,
            causal=False, kv_override=cross_src, kv_positions=kv_pos,
        )
        bcache = attn_lib.KVCache(
            k=k.astype(cfg.dtype), v=v.astype(cfg.dtype),
            length=jnp.asarray(seq_len, jnp.int32),
        )
    elif spec.mixer == "mamba":
        h, bcache = mamba_lib.mamba(bp["mixer"], normed, cfg.mamba.d_state, return_state=True)
    elif spec.mixer == "rwkv":
        h, wkv, x_last = rwkv_lib.rwkv_time_mix(bp["mixer"], normed, cfg.rwkv.head_dim)
        bcache = rwkv_lib.RWKVState(
            x_prev=x_last, wkv=wkv,
            ffn_x_prev=jnp.zeros((b, cfg.d_model), dtype=cfg.dtype),
        )
    else:
        raise ValueError(spec.mixer)
    x = x + h

    if spec.mlp != "none":
        normed = L.rmsnorm({"scale": bp["ln2"]}, x, cfg.norm_eps)
        if spec.mlp == "dense":
            h = L.mlp(bp["mlp"], normed)
        elif spec.mlp == "moe":
            h, _ = moe_lib.moe(bp["mlp"], normed, top_k=cfg.moe.top_k, aux_coef=0.0)
        elif spec.mlp == "rwkv_ffn":
            h, ffn_x = rwkv_lib.rwkv_channel_mix(bp["mlp"], normed)
            bcache = rwkv_lib.RWKVState(
                x_prev=bcache.x_prev, wkv=bcache.wkv, ffn_x_prev=ffn_x
            )
        x = x + h
    return x, bcache
