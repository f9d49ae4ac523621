"""The system under test, as the benchmark drives it.

This is the only module of the benchmark that imports the program
(``src/repro``): it turns a configuration file and a traffic file into the
program's own ``ArchConfig`` and ``TrainConfig``, builds ``Trainer`` with the
protocol-engine step, and reads the state the comparison needs.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding

from repro.configs.base import ArchConfig, BlockSpec, TrainConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.train import Trainer, batch_pspec, engine_program_cache_info  # noqa: F401


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


def train_config(name: str, config: dict, traffic: dict, seed: int) -> TrainConfig:
    train, sched = config["train"], traffic["schedule"]
    if max(sched["total_steps"] // 20, 1) != sched["warmup"]:
        raise ValueError("the program warms up for max(steps // 20, 1) steps; "
                         f"the traffic file states {sched['warmup']}")
    return TrainConfig(
        arch=name, protocol=traffic["protocol"], protocol_impl="engine",
        n_subsets=traffic["n_subsets"], shard=config["deployment"]["shard"],
        d=traffic["d"], aggregator=traffic["aggregator"],
        trim_frac=float(traffic["trim_frac"]), n_byz=traffic["n_byz"],
        attack=traffic["attack"], compression="none",
        optimizer=train["optimizer"], lr=float(traffic["lr"]),
        weight_decay=float(train["weight_decay"]),
        momentum_dtype=train["momentum_dtype"], steps=sched["total_steps"],
        seed=seed, remat=train["remat"],
    )


def make_trainer(name: str, config: dict, traffic: dict, seed: int) -> Trainer:
    return Trainer(cfg=arch_config(name, config),
                   tcfg=train_config(name, config, traffic, seed),
                   mesh=make_host_mesh(1, 1))


def place(trainer: Trainer, batch: dict) -> dict:
    """``batch`` where ``Trainer.run`` puts it, so the run moves nothing."""
    sharding = NamedSharding(trainer.mesh, batch_pspec(trainer.mesh))
    return {k: jax.device_put(v, sharding) for k, v in batch.items()}


def leaves(tree) -> dict:
    """Leaf path ``a/b/c`` -> array, for a params-shaped tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(k.key) for k in path): x for path, x in flat}


def first_moment(trainer: Trainer) -> dict:
    """AdamW's first moment: after one step it is (1 - b1) times the first
    aggregated gradient, as the optimizer got it."""
    return leaves(trainer.opt_state.mu)
