"""A tiny copy of the benchmark for CPU tests: the real harness and metric
readers, with configuration and traffic files shrunk so a run takes seconds.
"""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

CONFIG = {
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "num_hidden_layers": 2,
    "vocab_size": 256,
    "max_position_embeddings": 64,
    "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0,
    "tie_word_embeddings": True,
    "source": {"url": "tiny test model"},
}

TRAFFIC = {
    "protocol": "lad", "d": 2, "n_subsets": 4, "rows_per_subset": 2, "seq_len": 32,
    "aggregator": "cwtm", "trim_frac": 0.25, "attack": "sign_flip", "attack_coeff": -2.0,
    "n_byz": 1, "compression": "none", "sigma_h": 0.3, "zipf_a": 1.2, "lr": 0.01,
    "schedule": {"warmup": 1, "total_steps": 20, "final_frac": 0.1}, "pool_batches": 4,
}

# Set from CPU readings of this tiny cell (``bench/control.py --any-device``),
# on the numbers the real cells compare: over seeds 1-12 the bf16 program
# against the f32 reference read at most 1.54e-4, 3.37e-3, 1.11e-3 and
# 6.8e-4; the float8 control over seeds 1-4 at least 1.05e-3, 9.7e-3, 7.0e-4
# and 2.26e-3; the half-batch fault 0.0144 on the embedding, frozen state 1.0.
LIMITS = {"loss_gap": 4e-4, "grad_norm_gap": 5.5e-3, "embed_grad_norm_gap": 4.5e-3,
          "update_norm_gap": 1.4e-3}


def make_root(tmp: pathlib.Path, *, chips: int = 1, shard: str = "none",
              traffic: dict | None = None, limits: dict | None = None) -> pathlib.Path:
    """A checkout-shaped directory holding one cell ``tiny.lad``."""
    root = pathlib.Path(tmp)
    bench = root / "bench"
    for sub in ("configs", "traffic", "workloads"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics", dirs_exist_ok=True)
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    config = dict(CONFIG, train=json.loads(
        (BENCH / "configs" / "smollm360m-l20.json").read_text())["train"],
        deployment={"chips": chips, "shard": shard})
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    (bench / "traffic" / "lad.json").write_text(json.dumps(traffic or TRAFFIC))
    (bench / "workloads" / "tiny.lad.json").write_text(json.dumps({"limits": limits or LIMITS}))
    manifest = {
        "command": real["command"], "paths": real["paths"], "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "tiny test model",
                     "file": "bench/configs/tiny.json", "reduced": [], "why": "tests"}],
        "workloads": [{"name": "tiny.lad", "config": "tiny", "traffic": "lad",
                       "chips": chips, "why": "tests"}],
        "end_to_end": real["end_to_end"],
        "per_layer": [dict(m, workloads=["tiny.lad"]) for m in real["per_layer"]],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root
