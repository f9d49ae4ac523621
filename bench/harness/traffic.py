"""The one generator of training batches, driven by a traffic file.

Each of the N data subsets draws its tokens from its own unigram
distribution: a Zipf(``zipf_a``) base re-weighted by a Gamma draw with
concentration ``1 / (sigma_h + 1e-3)``, so larger ``sigma_h`` makes the
subsets' gradients differ more (the paper's heterogeneity).  The
distribution is that of the program's ``data/synthetic.py::HeterogeneousLM``;
sampling is by inverse CDF so a pool of batches costs one small program.

A batch is ``{"tokens", "labels"}`` of shape ``(N * rows, seq)``, laid out
as N contiguous blocks of ``rows`` rows, one block per subset: next-token
labels are the tokens shifted by one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DATA_STREAM = 1  # fold_in tag separating the data's key from the model's


def subset_logits(key, vocab: int, n_subsets: int, sigma_h: float, zipf_a: float):
    """(N, V) unnormalized log-probabilities of each subset's unigram."""
    base = -zipf_a * jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))
    conc = 1.0 / (sigma_h + 1e-3)
    noise = jax.random.gamma(key, conc, (n_subsets, vocab)) / conc
    return base[None, :] + jnp.log(noise + 1e-9)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _pool(key, vocab, n_subsets, rows, seq_len, n_batches, sigma_h, zipf_a):
    k_dist, k_tok = jax.random.split(key)
    logits = subset_logits(k_dist, vocab, n_subsets, sigma_h, zipf_a)
    cdf = jnp.cumsum(jax.nn.softmax(logits, axis=-1), axis=-1)  # (N, V)
    u = jax.random.uniform(k_tok, (n_subsets, n_batches, rows, seq_len + 1))
    u = u * cdf[:, -1:, None, None].reshape(n_subsets, 1, 1, 1)
    toks = jax.vmap(lambda c, x: jnp.searchsorted(c, x, side="right"))(cdf, u)
    toks = jnp.minimum(toks, vocab - 1).astype(jnp.int32)  # (N, K, rows, S+1)
    toks = toks.transpose(1, 0, 2, 3).reshape(n_batches, n_subsets * rows, seq_len + 1)
    return toks[..., :-1], toks[..., 1:]


def batch_pool(seed: int, vocab: int, traffic: dict) -> list[dict]:
    """``traffic["pool_batches"]`` distinct batches, made on the device from
    ``seed``; the same seed gives the same batches."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), DATA_STREAM)
    tokens, labels = _pool(
        key, vocab, traffic["n_subsets"], traffic["rows_per_subset"],
        traffic["seq_len"], traffic["pool_batches"],
        float(traffic["sigma_h"]), float(traffic["zipf_a"]),
    )
    return [{"tokens": tokens[i], "labels": labels[i]} for i in range(tokens.shape[0])]


def tokens_per_step(traffic: dict) -> int:
    """Training tokens of one honest copy of the model in one step."""
    return traffic["n_subsets"] * traffic["rows_per_subset"] * traffic["seq_len"]
