"""Model FLOPs of one training token of a Llama decoder, counted from a
configuration file.

Only the work one honest copy of the model needs: forward plus backward
(3x forward) of every matrix product, with the tied embedding counted once
(as the unembedding; the lookup is free), plus causal attention, of which
only the unmasked half is counted.  LAD's redundancy, recomputation under
remat and masked attention work are not counted.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matrix product once per token."""
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // heads)
    ff = cfg["intermediate_size"]
    attn = d * heads * hd * 2 + d * kv * hd * 2  # wq, wo; wk, wv
    mlp = 3 * d * ff  # gate, up, down
    head = cfg["vocab_size"] * d  # unembedding (tied or not: one product)
    return cfg["num_hidden_layers"] * (attn + mlp) + head


def attention_flops(cfg: dict, seq_len: int) -> float:
    """Causal QK^T and PV, forward and backward, per token, averaged over
    the positions of a sequence (a query at position i sees i + 1 keys)."""
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // heads)
    mean_ctx = (seq_len + 1) / 2
    # 2 products x 2 FLOPs per multiply-add, x3 for forward + backward
    return cfg["num_hidden_layers"] * 12 * heads * hd * mean_ctx


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6 * matmul_params(cfg) + attention_flops(cfg, seq_len)
