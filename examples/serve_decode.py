"""Batched serving: prefill a batch of prompts, then stream decode steps.

Uses the reduced RWKV-6 config (O(1) state — the long-context family) and a
reduced llama-family model side by side, demonstrating the shared serving API
(prefill -> ring-buffer/state caches -> decode_step).

    PYTHONPATH=src python examples/serve_decode.py
"""
import jax
import jax.numpy as jnp

from repro import models
from repro.configs.archs import ARCHS, reduced


def serve(arch: str, prompt_len: int = 48, new_tokens: int = 16, batch: int = 4):
    cfg = reduced(ARCHS[arch])
    key = jax.random.PRNGKey(0)
    params, specs = models.init(key, cfg)

    prompts = jax.random.randint(jax.random.fold_in(key, 1), (batch, prompt_len),
                                 0, cfg.vocab)
    frontend = None
    if cfg.family in ("vlm", "audio"):
        enc = cfg.encoder
        frontend = jax.random.normal(
            jax.random.fold_in(key, 2), (batch, enc.n_frontend_tokens, enc.d_frontend)
        )

    logits, state = models.prefill(params, specs, cfg, prompts, frontend=frontend,
                                   capacity=prompt_len + new_tokens)
    decode = jax.jit(lambda p, t, s: models.decode_step(p, specs, cfg, t, s))

    token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    generated = [token]
    for _ in range(new_tokens - 1):
        logits, state = decode(params, token, state)
        token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        generated.append(token)
    out = jnp.concatenate(generated, axis=1)
    assert out.shape == (batch, new_tokens)
    assert not jnp.any(jnp.isnan(logits))
    print(f"{arch:24s} served {batch} seqs x {new_tokens} tokens; "
          f"first row: {out[0, :8].tolist()} ...")
    return out


def main():
    for arch in ["smollm-360m", "rwkv6-1.6b", "whisper-small"]:
        serve(arch)
    print("OK: greedy batched decoding ran for dense, SSM and enc-dec families.")


if __name__ == "__main__":
    main()
