"""The protocol-engine LM train path (launch/train.py).

The transformer LM trains through core.byzantine.protocol_round — the same
assignment -> eq.-(5) encode -> compress -> attack -> robust-aggregate
pipeline as the Section-VII linear-regression runs — on the default
single-CPU-device mesh (no subprocess).
"""
import jax
import numpy as np
import pytest

from repro.configs.archs import ARCHS, reduced
from repro.configs.base import TrainConfig
from repro.data.synthetic import lm_batch_for_devices
from repro.launch.mesh import make_host_mesh
from repro.launch.train import Trainer, make_round_config

N_SUB = 8


def _tiny_cfg():
    return reduced(ARCHS["smollm-360m"]).scaled(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, head_dim=32,
        d_ff=128, vocab=128,
    )


def _run(tcfg, cfg, steps, per_subset=2, seq_len=16):
    mesh = make_host_mesh(1, 1)
    tr = Trainer(cfg=cfg, tcfg=tcfg, mesh=mesh)
    key = jax.random.PRNGKey(0)

    def batches():
        for i in range(steps):
            b = lm_batch_for_devices(
                jax.random.fold_in(key, i), cfg.vocab, n_subsets=N_SUB,
                per_subset=per_subset, seq_len=seq_len, sigma_h=0.5,
            )
            yield {k: v.reshape(-1, v.shape[-1]) for k, v in b.items()}

    return tr.run(batches(), log_every=1)


def test_lm_trains_through_protocol_engine():
    """LAD + CWTM under a sign-flip attack, whole-model protocol round:
    loss must be finite and decrease over a short run."""
    cfg = _tiny_cfg()
    tcfg = TrainConfig(
        arch=cfg.name, protocol="lad", protocol_impl="engine", n_subsets=N_SUB,
        d=2, aggregator="cwtm", trim_frac=0.25, n_byz=2, attack="sign_flip",
        optimizer="adamw", lr=3e-3, steps=8, microbatches=1,
    )
    hist = _run(tcfg, cfg, tcfg.steps)
    losses = [l for _, l in hist]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_honest_mean_trains_and_lad_beats_the_mean_under_attack():
    """The honest mean (protocol "none") trains; under the same sign-flip
    attack (2 of 8 subsets) LAD + CWTM ends below the plain mean."""
    cfg = _tiny_cfg()
    base = dict(arch=cfg.name, n_subsets=N_SUB, optimizer="adamw", lr=3e-3, steps=8)

    def losses(**kw):
        return [l for _, l in _run(TrainConfig(**base, **kw), cfg, base["steps"])]

    honest = losses(protocol="none")
    assert honest[-1] < honest[0] - 0.3, honest
    attack = dict(n_byz=2, attack="sign_flip")
    lad = losses(protocol="lad", d=2, aggregator="cwtm", trim_frac=0.25, **attack)
    mean = losses(protocol="plain", aggregator="mean", **attack)
    assert lad[-1] < mean[-1], (lad, mean)


@pytest.mark.slow
def test_engine_path_microbatched_com_lad():
    """microbatches > 1 (per-microbatch robust exchange, fp32 accumulation)
    with Com-LAD compression still produces finite decreasing loss."""
    cfg = _tiny_cfg()
    tcfg = TrainConfig(
        arch=cfg.name, protocol="lad", protocol_impl="engine", n_subsets=N_SUB,
        d=2, aggregator="cwtm", trim_frac=0.25, n_byz=2, attack="sign_flip",
        compression="rand_sparse", q_hat_frac=0.5,
        optimizer="adamw", lr=3e-3, steps=5, microbatches=2,
    )
    hist = _run(tcfg, cfg, tcfg.steps)
    losses = [l for _, l in hist]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0] + 0.05, losses


def test_make_round_config_lowering():
    """TrainConfig -> ProtocolConfig mirrors the Scenario lowering."""
    tcfg = TrainConfig(protocol="lad", d=3, aggregator="cwtm-nnm", trim_frac=0.2,
                       n_byz=5, attack="ipm", compression="quant", quant_levels=8)
    pcfg = make_round_config(tcfg, 16)
    assert pcfg.n_devices == 16 and pcfg.method == "lad" and pcfg.d == 3
    assert pcfg.aggregator == "cwtm-nnm" and pcfg.trim_frac == 0.2
    assert pcfg.attack.name == "ipm" and pcfg.attack.n_byz == 5
    assert pcfg.compression.name == "quant" and pcfg.compression.levels == 8
    # "plain" forces d=1 (Section VII fair-comparison setup)
    assert make_round_config(TrainConfig(protocol="plain", d=4), 8).d == 1
    # "none" is the honest mean: no byzantine, no compression
    none = make_round_config(TrainConfig(protocol="none", n_byz=3), 8)
    assert none.aggregator == "mean" and none.n_byz == 0
    assert none.attack.name == "none" and none.compression.name == "none"
    with pytest.raises(ValueError):
        from repro.launch.train import build_train_step

        build_train_step(_tiny_cfg(), TrainConfig(protocol_impl="bogus"),
                         make_host_mesh(1, 1), specs=None)
