"""Quickstart: Byzantine-robust training of a small LM with LAD, on CPU.

Builds a reduced SmolLM-family model, marks one of the four logical LAD
devices Byzantine (sign-flipping attack), and trains with cyclic gradient
coding (d=2) + CWTM aggregation through the protocol-engine step.  The four
subsets' gradients fan out over four virtual CPU devices
(``shard="shard_map"``), one subset per device, as on a 2x2 TPU host.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/quickstart.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    )

import jax

from repro.configs.archs import ARCHS, reduced
from repro.configs.base import TrainConfig
from repro.data.synthetic import lm_batch_for_devices
from repro.launch.mesh import make_host_mesh
from repro.launch.train import Trainer


def main():
    mesh = make_host_mesh(data=1, model=1)
    cfg = reduced(ARCHS["smollm-360m"])
    tcfg = TrainConfig(
        arch=cfg.name,
        protocol="lad",
        n_subsets=4,              # four logical LAD devices
        shard="shard_map",        # one subset's gradient per device
        d=2,                      # cyclic gradient-coding redundancy
        aggregator="cwtm",        # kappa-robust server rule
        trim_frac=0.25,
        n_byz=1,                  # one of four devices is Byzantine
        attack="sign_flip",       # Section VII attack (coefficient -2)
        optimizer="adamw",
        lr=1e-3,
        steps=30,
        microbatches=2,
    )
    trainer = Trainer(cfg=cfg, tcfg=tcfg, mesh=mesh)

    key = jax.random.PRNGKey(0)

    def batches():
        for i in range(tcfg.steps):
            b = lm_batch_for_devices(
                jax.random.fold_in(key, i), cfg.vocab,
                n_subsets=4, per_subset=2, seq_len=64, sigma_h=0.3,
            )
            yield {k: v.reshape(-1, v.shape[-1]) for k, v in b.items()}

    history = trainer.run(batches(), log_every=5)
    print("step  loss")
    for step, loss in history:
        print(f"{step:4d}  {loss:.4f}")
    assert history[-1][1] < history[0][1], "training under attack should converge"
    print("OK: LAD-CWTM converged despite the Byzantine device.")


if __name__ == "__main__":
    main()
