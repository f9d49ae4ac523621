"""The system under test, as the benchmark drives it.

With each model's ``bench/models/<model_type>/program.py`` (which maps a
configuration file to the program's ``ArchConfig``) this is the only part of
the benchmark that imports the program (``src/repro``): it turns a traffic
file into the program's ``TrainConfig``, builds ``Trainer`` with the
protocol-engine step, and reads the state the comparison needs.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding

from repro.configs.base import ArchConfig, TrainConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.train import Trainer, batch_pspec, engine_program_cache_info  # noqa: F401


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


def make_trainer(arch: ArchConfig, name: str, config: dict, traffic: dict,
                 seed: int) -> Trainer:
    """``arch`` from the model's ``program.arch_config(name, config)``."""
    return Trainer(cfg=arch,
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
