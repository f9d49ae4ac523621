"""Period-scanned multi-family transformer LM.

One assembly serves all ten assigned architectures.  A model is a repeating
*period* of blocks (`ArchConfig.period`); parameters of each block position
are stacked over periods and the stack is traversed with ``jax.lax.scan``, so
the compiled HLO is O(period length), not O(n_layers).

Block kinds (see configs.base.BlockSpec): ``attn`` (causal GQA + RoPE),
``attn_nope`` (no RoPE — whisper; causal unless encoder-side), ``mamba``,
``rwkv``, ``cross`` (cross-attention to frontend/encoder tokens).
MLP flavors: ``dense`` (SwiGLU), ``moe``, ``rwkv_ffn``, ``none``.
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
from repro.models.module import dense_param, split_tree


def _add_stack(specs):
    return jax.tree.map(
        lambda s: ("stack",) + tuple(s), specs, is_leaf=lambda x: isinstance(x, tuple)
    )


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------
def _block_init(key, cfg: ArchConfig, spec: BlockSpec):
    keys = jax.random.split(key, 4)
    pairs: dict[str, Any] = {}
    p_ln1, s_ln1 = L.rmsnorm_init(cfg.d_model)
    pairs["ln1"] = (p_ln1["scale"], s_ln1["scale"])
    dtype = cfg.dtype

    if spec.mixer in ("attn", "attn_nope", "cross"):
        p, s = attn_lib.attention_init(
            keys[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            dtype, attn_tp=cfg.attn_tp,
        )
        pairs["mixer"] = {k: (p[k], s[k]) for k in p}
    elif spec.mixer == "mamba":
        mc = cfg.mamba
        p, s = mamba_lib.mamba_init(keys[0], cfg.d_model, mc.d_state, mc.d_conv, mc.expand, dtype)
        pairs["mixer"] = {k: (p[k], s[k]) for k in p}
    elif spec.mixer == "rwkv":
        rc = cfg.rwkv
        p, s = rwkv_lib.rwkv_time_mix_init(keys[0], cfg.d_model, rc.head_dim, rc.decay_lora, dtype)
        pairs["mixer"] = {k: (p[k], s[k]) for k in p}
    else:
        raise ValueError(f"unknown mixer {spec.mixer!r}")

    if spec.mlp != "none":
        p_ln2, s_ln2 = L.rmsnorm_init(cfg.d_model)
        pairs["ln2"] = (p_ln2["scale"], s_ln2["scale"])
        if spec.mlp == "dense":
            p, s = L.mlp_init(keys[1], cfg.d_model, cfg.d_ff, dtype)
        elif spec.mlp == "moe":
            mo = cfg.moe
            p, s = moe_lib.moe_init(
                keys[1], cfg.d_model, mo.d_ff_expert or cfg.d_ff, mo.n_experts, dtype
            )
        elif spec.mlp == "rwkv_ffn":
            p, s = rwkv_lib.rwkv_channel_mix_init(keys[1], cfg.d_model, cfg.d_ff, dtype)
        else:
            raise ValueError(f"unknown mlp {spec.mlp!r}")
        pairs["mlp"] = {k: (p[k], s[k]) for k in p}
    return split_tree(pairs)


def init(key, cfg: ArchConfig):
    """Initialize the full model.  Returns (params, specs) trees."""
    k_emb, k_blocks, k_head, k_enc, k_proj = jax.random.split(key, 5)
    pairs: dict[str, Any] = {}

    p, s = L.embedding_init(k_emb, cfg.vocab, cfg.d_model, cfg.dtype)
    pairs["embed"] = {"table": (p["table"], s["table"])}
    p_lnf, s_lnf = L.rmsnorm_init(cfg.d_model)
    pairs["ln_f"] = (p_lnf["scale"], s_lnf["scale"])
    if not cfg.tie_embeddings:
        pairs["lm_head"] = dense_param(
            k_head, (cfg.vocab, cfg.d_model), ("tp", "fsdp"), cfg.dtype
        )

    period_keys = jax.random.split(k_blocks, cfg.n_periods)

    def one_period(k):
        ks = jax.random.split(k, len(cfg.period))
        return {
            f"blk{i}": _block_init(ks[i], cfg, spec)
            for i, spec in enumerate(cfg.period)
        }

    per = [one_period(k) for k in period_keys]
    params, specs = {}, {}
    for k, v in pairs.items():
        if isinstance(v, dict):
            sub_p, sub_s = split_tree(v)
            params[k], specs[k] = sub_p, sub_s
        else:
            params[k], specs[k] = v
    params["periods"] = {
        name: jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                           *[p[name][0] for p in per])
        for name in per[0]
    }
    specs["periods"] = {name: _add_stack(per[0][name][1]) for name in per[0]}

    # frontend / encoder extras
    if cfg.family in ("vlm", "audio"):
        enc = cfg.encoder
        proj_p, proj_s = dense_param(
            k_proj, (enc.d_frontend, cfg.d_model), (None, "fsdp"), cfg.dtype
        )
        params["frontend_proj"], specs["frontend_proj"] = proj_p, proj_s
    if cfg.family == "audio" and cfg.encoder.n_encoder_layers > 0:
        enc_keys = jax.random.split(k_enc, cfg.encoder.n_encoder_layers)
        enc_spec = BlockSpec(mixer="attn_nope", mlp="dense")
        blocks = [_block_init(k, cfg, enc_spec) for k in enc_keys]
        params["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *[b[0] for b in blocks])
        specs["encoder"] = _add_stack(blocks[0][1])
        p_lne, s_lne = L.rmsnorm_init(cfg.d_model)
        params["encoder_ln"], specs["encoder_ln"] = p_lne["scale"], s_lne["scale"]
    return params, specs


# ---------------------------------------------------------------------------
# Block apply (full sequence)
# ---------------------------------------------------------------------------
def _mixer_apply(cfg: ArchConfig, spec: BlockSpec, bp, x, positions, cross_src):
    if spec.mixer == "attn":
        out, _, _ = attn_lib.multihead_attention(
            bp["mixer"], x, positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=cfg.rope_theta,
            causal=True, window=spec.sliding_window,
        )
        return out
    if spec.mixer == "attn_nope":
        causal = cfg.family != "audio" or cross_src is not None
        out, _, _ = attn_lib.multihead_attention(
            bp["mixer"], x, positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=None,
            causal=causal, window=spec.sliding_window,
        )
        return out
    if spec.mixer == "cross":
        kv_pos = jnp.broadcast_to(
            jnp.arange(cross_src.shape[1], dtype=jnp.int32)[None], cross_src.shape[:2]
        )
        out, _, _ = attn_lib.multihead_attention(
            bp["mixer"], x, positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=None,
            causal=False, kv_override=cross_src, kv_positions=kv_pos,
        )
        return out
    if spec.mixer == "mamba":
        return mamba_lib.mamba(bp["mixer"], x, cfg.mamba.d_state)
    if spec.mixer == "rwkv":
        out, _, _ = rwkv_lib.rwkv_time_mix(bp["mixer"], x, cfg.rwkv.head_dim)
        return out
    raise ValueError(spec.mixer)


def _block_apply(cfg, spec: BlockSpec, bp, x, positions, cross_src):
    h = _mixer_apply(cfg, spec, bp, L.rmsnorm({"scale": bp["ln1"]}, x, cfg.norm_eps),
                     positions, cross_src)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if spec.mlp != "none":
        normed = L.rmsnorm({"scale": bp["ln2"]}, x, cfg.norm_eps)
        if spec.mlp == "dense":
            h = L.mlp(bp["mlp"], normed)
        elif spec.mlp == "moe":
            h, aux = moe_lib.moe(
                bp["mlp"], normed, top_k=cfg.moe.top_k, aux_coef=cfg.moe.router_aux_coef
            )
        elif spec.mlp == "rwkv_ffn":
            h, _ = rwkv_lib.rwkv_channel_mix(bp["mlp"], normed)
        x = x + h
    return x, aux


def _encode_frontend(params, cfg: ArchConfig, frontend):
    """Project stubbed frontend embeddings; run the whisper encoder stack."""
    src = jnp.einsum("bsf,fd->bsd", frontend.astype(cfg.dtype), params["frontend_proj"])
    if cfg.family == "audio" and cfg.encoder.n_encoder_layers > 0:
        src = src + L.sinusoidal_positions(src.shape[1], cfg.d_model)[None].astype(src.dtype)
        enc_spec = BlockSpec(mixer="attn_nope", mlp="dense")
        positions = jnp.broadcast_to(
            jnp.arange(src.shape[1], dtype=jnp.int32)[None], src.shape[:2]
        )

        def body(x, layer_params):
            x, _ = _block_apply(cfg, enc_spec, layer_params, x, positions, None)
            return x, None

        src, _ = jax.lax.scan(body, src, params["encoder"])
        src = L.rmsnorm({"scale": params["encoder_ln"]}, src, cfg.norm_eps)
    return src


def hidden_states(
    params,
    specs,
    cfg: ArchConfig,
    tokens: jax.Array,
    *,
    frontend: jax.Array | None = None,
    remat: bool = True,
):
    """Backbone forward to the final norm.  -> (hidden (B, S, D), moe_aux)."""
    del specs  # sharding specs are applied at device_put / jit time
    b, s = tokens.shape
    x = L.embed(params["embed"], tokens)
    if cfg.family == "audio":
        x = x + L.sinusoidal_positions(s, cfg.d_model)[None].astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    cross_src = None
    if cfg.family in ("vlm", "audio"):
        assert frontend is not None, f"{cfg.name} needs frontend embeddings"
        cross_src = _encode_frontend(params, cfg, frontend)

    def period_body(carry, period_params):
        x, aux = carry

        def inner(x_in, pp):
            aux_p = jnp.zeros((), jnp.float32)
            for i, spec in enumerate(cfg.period):
                x_in, a = _block_apply(cfg, spec, pp[f"blk{i}"], x_in, positions, cross_src)
                aux_p = aux_p + a
            return x_in, aux_p

        fn = jax.checkpoint(inner) if remat else inner
        x, aux_p = fn(x, period_params)
        return (x, aux + aux_p), None

    (x, aux), _ = jax.lax.scan(period_body, (x, jnp.zeros((), jnp.float32)), params["periods"])

    x = L.rmsnorm({"scale": params["ln_f"]}, x, cfg.norm_eps)
    return x, aux, params["embed"]["table"]


def _unembed_table(params, cfg: ArchConfig, emb_table):
    return emb_table if cfg.tie_embeddings else params["lm_head"]


def forward(
    params,
    specs,
    cfg: ArchConfig,
    tokens: jax.Array,
    *,
    frontend: jax.Array | None = None,
    remat: bool = True,
):
    """Full-sequence forward.  tokens: (B, S) -> (logits (B, S, V) fp32, aux)."""
    x, aux, emb_table = hidden_states(
        params, specs, cfg, tokens, frontend=frontend, remat=remat
    )
    head = _unembed_table(params, cfg, emb_table)
    logits = jnp.einsum("bsd,vd->bsv", x, head)
    return logits.astype(jnp.float32), aux


CE_CHUNK = 512  # sequence positions per cross-entropy chunk


def _chunked_ce(x: jax.Array, head: jax.Array, labels: jax.Array) -> jax.Array:
    """Memory-sane next-token CE: never materializes (B, S, V) at once.

    nll = logsumexp(x @ head^T) - <x, head[labels]> computed over sequence
    chunks (the (B, chunk, V) logits block is transient per chunk, and the
    label logit uses a (B, chunk, D) gather of label rows instead of any
    V-sized one-hot).  Essential for the 200k-vocab configs.
    """
    b, s, d = x.shape
    chunk = min(CE_CHUNK, s)
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    xs = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def one(args):
        xc, lc = args  # (B, chunk, D), (B, chunk)
        logits = jnp.einsum("bsd,vd->bsv", xc, head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)  # (B, chunk)
        lab_rows = jnp.take(head, lc, axis=0).astype(jnp.float32)  # (B, chunk, D)
        lab_logit = jnp.einsum("bsd,bsd->bs", xc.astype(jnp.float32), lab_rows)
        return lse - lab_logit

    nll = jax.lax.map(jax.checkpoint(one), (xs, ls))  # (n, B, chunk)
    return jnp.mean(nll)


def loss_fn(
    params,
    specs,
    cfg: ArchConfig,
    batch: dict,
    *,
    remat: bool = True,
):
    """Next-token cross entropy (+ MoE aux).  batch: tokens, labels[, frontend]."""
    x, aux, emb_table = hidden_states(
        params, specs, cfg, batch["tokens"],
        frontend=batch.get("frontend"), remat=remat,
    )
    head = _unembed_table(params, cfg, emb_table)
    nll = _chunked_ce(x, head, batch["labels"])
    loss = nll + aux
    return loss, {"nll": nll, "aux": aux}
