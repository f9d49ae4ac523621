"""Serving steps (prefill / decode) and one batch of measured traffic.

Inference carries no gradient exchange, so the LAD protocol is inactive here;
the paper's technique is train-time.  The serving path closes the
train-to-serve loop: a ``Trainer`` checkpoint restores straight into these
jitted prefill/decode fns (``repro.checkpoint.restore_for_serving``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import models
from repro.configs.base import ArchConfig


def build_decode_fn(cfg: ArchConfig, mesh, param_shardings, specs):
    """jit'd decode step bound to the mesh shardings."""

    def fn(params, token, state):
        return models.decode_step(params, specs, cfg, token, state)

    return jax.jit(fn)


def build_prefill_fn(cfg: ArchConfig, mesh, specs, *, capacity: int | None = None):
    """jit'd prefill (full forward + cache build).  ``capacity`` reserves
    ring headroom so decode can run past the prompt without evicting row 0."""

    def fn(params, tokens, frontend=None):
        return models.prefill(params, specs, cfg, tokens, frontend=frontend,
                              capacity=capacity)

    return jax.jit(fn)


def serve_traffic(
    cfg: ArchConfig,
    params,
    specs,
    mesh,
    tokens: jax.Array,
    *,
    frontend: jax.Array | None = None,
    new_tokens: int = 8,
):
    """Serve one batch of traffic: prefill the prompt, then greedy-decode
    ``new_tokens`` steps through the jitted serve fns.

    Returns ``{prefill_s, decode_s, prefill_tokens_per_s,
    decode_tokens_per_s, tokens (B, new_tokens), pos}`` — the measured
    serving record of the train-to-serve loop (``benchmarks/paper_figures.
    zoo_serve``).  Timings are warm: each fn runs once for compile before
    the measured pass.
    """
    import time

    b, s = tokens.shape
    prefill_fn = build_prefill_fn(cfg, mesh, specs, capacity=s + new_tokens)
    decode_fn = build_decode_fn(cfg, mesh, None, specs)

    jax.block_until_ready(prefill_fn(params, tokens, frontend))  # compile
    t0 = time.perf_counter()
    logits, state = prefill_fn(params, tokens, frontend)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    jax.block_until_ready(decode_fn(params, tok, state))  # compile
    out = []
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        logits, state = decode_fn(params, tok, state)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t0

    return {
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "prefill_tokens_per_s": b * s / max(prefill_s, 1e-9),
        "decode_tokens_per_s": b * new_tokens / max(decode_s, 1e-9),
        "tokens": jnp.concatenate(out, axis=1),
        "pos": int(state["pos"]),
    }
