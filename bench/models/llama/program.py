"""Llama's mapping onto the program: a configuration file -> ``ArchConfig``.

With ``harness/system.py`` this is the only part of the benchmark that
imports the program (``src/repro``).  A Llama decoder is the program's
``family="dense"`` with one plain block per period: grouped-query attention
with rotary positions, SwiGLU, RMSNorm.
"""
from __future__ import annotations

from repro.configs.base import ArchConfig, BlockSpec


def arch_config(name: str, config: dict) -> ArchConfig:
    heads = config["num_attention_heads"]
    return ArchConfig(
        name=name, family="dense", source=config["source"]["url"],
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=heads, n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        head_dim=config.get("head_dim", config["hidden_size"] // heads),
        period=(BlockSpec(),), rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=config["train"]["param_dtype"],
    )
