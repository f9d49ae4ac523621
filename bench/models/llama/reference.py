"""Llama's part of the plain float32 reference (``harness/reference.py``).

It imports nothing of the program.  The model is a Llama-architecture
decoder with tied embeddings (RMSNorm, rotary positions, grouped-query
causal attention, SwiGLU).  Every product goes through the matrix-product
function the harness passes in (float32 at ``Precision.HIGHEST``, or the
control's float8 one); everything else is float32.  Stored leaves keep the
dtypes the configuration states: bfloat16 weight matrices, float32 norm
scales.

The initial weights follow the program's initialiser, which the architecture
does not fix: truncated normal on [-2, 2] scaled by 1/sqrt(first dim), from
the key tree ``split(PRNGKey(seed), 5)`` -> per layer ``split(k, 1)[0]`` ->
``split(., 4)`` (attention, MLP) -> per weight; norm scales are one.

The harness finds this module from a configuration's ``model_type`` and
uses ``from_config`` and ``APART``; ``Llama``'s methods are its interface.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

LAYER_PREFIX = "periods/blk0/"
# leaves whose first-gradient gap the comparison holds apart: the program
# sums the tied table's gradient over a subset's tokens in bfloat16
APART = ("embed/table",)


def trunc(key, shape, fan_in, dtype):
    """The program's truncated-normal weight, rounded to ``dtype``, in f32."""
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype).astype(jnp.float32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotary positions, rotate-half form: x (B, S, heads, hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def token_nll(logits, labels):
    """Per-token next-token cross entropy: (B, S, V), (B, S) -> (B, S)."""
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]


@dataclasses.dataclass(frozen=True)
class Llama:
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

    def init_params(self, seed_key) -> dict:
        """Leaf name -> float32 array holding the stored (dtype-rounded) value."""
        dt = jnp.dtype(self.param_dtype)
        k_emb, k_blocks = jax.random.split(seed_key, 5)[:2]

        def layer(k):
            k_attn, k_mlp = jax.random.split(jax.random.split(k, 1)[0], 4)[:2]
            kq, kk, kv, ko = jax.random.split(k_attn, 4)
            k1, k2, k3 = jax.random.split(k_mlp, 3)
            return {
                "mixer/wq": trunc(kq, (self.d, self.heads, self.hd), self.d, dt),
                "mixer/wk": trunc(kk, (self.d, self.kv, self.hd), self.d, dt),
                "mixer/wv": trunc(kv, (self.d, self.kv, self.hd), self.d, dt),
                "mixer/wo": trunc(ko, (self.heads, self.hd, self.d), self.heads, dt),
                "mlp/w_gate": trunc(k1, (self.d, self.ff), self.d, dt),
                "mlp/w_up": trunc(k2, (self.d, self.ff), self.d, dt),
                "mlp/w_down": trunc(k3, (self.ff, self.d), self.ff, dt),
            }

        stacked = jax.vmap(layer)(jax.random.split(k_blocks, self.layers))
        params = {LAYER_PREFIX + k: v for k, v in stacked.items()}
        ones = jnp.ones((self.layers, self.d), jnp.float32)
        params[LAYER_PREFIX + "ln1"] = ones
        params[LAYER_PREFIX + "ln2"] = ones
        params["embed/table"] = trunc(k_emb, (self.vocab, self.d), self.vocab, dt)
        params["ln_f"] = jnp.ones((self.d,), jnp.float32)
        return params

    def leaf_dtype(self, name: str):
        return jnp.dtype(self.norm_dtype if name.endswith(("ln1", "ln2", "ln_f"))
                         else self.param_dtype)

    def block(self, mm, x, w):
        """One decoder layer; ``w`` holds its leaves without the prefix."""
        b, n = x.shape[:2]
        h = rmsnorm(x, w["ln1"], self.norm_eps)
        q = rope(mm("bsd,dhk->bshk", h, w["mixer/wq"]), self.theta)
        k = rope(mm("bsd,dhk->bshk", h, w["mixer/wk"]), self.theta)
        v = mm("bsd,dhk->bshk", h, w["mixer/wv"])
        q = q.reshape(b, n, self.kv, self.heads // self.kv, self.hd)  # query head j reads kv head j // g
        logits = mm("bqhgd,bkhd->bhgqk", q, k) * (1.0 / math.sqrt(self.hd))
        causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
        o = mm("bhgqk,bkhd->bqhgd", probs, v).reshape(b, n, self.heads, self.hd)
        x = x + mm("bshk,hkd->bsd", o, w["mixer/wo"])
        h = rmsnorm(x, w["ln2"], self.norm_eps)
        act = jax.nn.silu(mm("bsd,df->bsf", h, w["mlp/w_gate"])) * mm("bsd,df->bsf", h, w["mlp/w_up"])
        return x + mm("bsf,fd->bsd", act, w["mlp/w_down"])

    def hidden(self, params, tokens, mm):
        """The final-norm hidden states (B, S, d) of ``tokens`` (B, S)."""
        layers = {k[len(LAYER_PREFIX):]: v for k, v in params.items()
                  if k.startswith(LAYER_PREFIX)}
        x = params["embed/table"][tokens]
        body = jax.checkpoint(lambda x, w: (self.block(mm, x, w), None))
        x, _ = jax.lax.scan(body, x, layers)
        return rmsnorm(x, params["ln_f"], self.norm_eps)

    def forward(self, params, tokens, labels, mm):
        """Per-token NLL (B, S) through the tied head, and no extra loss term."""
        logits = mm("bsd,vd->bsv", self.hidden(params, tokens, mm), params["embed/table"])
        return token_nll(logits, labels), 0.0


def sizes(config: dict) -> dict:
    """``Llama``'s fields from a configuration file, for a model built on it."""
    train = config["train"]
    heads = config["num_attention_heads"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"], heads=heads,
        kv=config["num_key_value_heads"],
        hd=config.get("head_dim", config["hidden_size"] // heads),
        ff=config["intermediate_size"], layers=config["num_hidden_layers"],
        norm_eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        param_dtype=train["param_dtype"], norm_dtype=train["norm_dtype"],
    )


def from_config(config: dict) -> Llama:
    if not config["tie_word_embeddings"]:
        raise ValueError("the Llama reference covers tied embeddings only")
    return Llama(**sizes(config))
