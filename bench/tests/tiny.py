"""A tiny copy of the benchmark for CPU tests: the real harness and metric
readers, with configuration and traffic files shrunk so a run takes seconds.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

CONFIG = {
    "model_type": "llama",
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


def digest(root: pathlib.Path) -> dict:
    """File -> sha256 of everything under ``root`` but compile caches."""
    return {str(f.relative_to(root)): hashlib.sha256(f.read_bytes()).hexdigest()
            for f in sorted(root.rglob("*"))
            if f.is_file() and ".cache" not in f.parts and "__pycache__" not in f.parts}


def make_root(tmp: pathlib.Path, *, chips: int = 1, shard: str = "none",
              traffic: dict | None = None, limits: dict | None = None) -> pathlib.Path:
    """A checkout-shaped directory holding one cell ``tiny.lad``."""
    root = pathlib.Path(tmp)
    bench = root / "bench"
    for sub in ("configs", "traffic", "workloads"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "models"):
        shutil.copytree(BENCH / sub, bench / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
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


# A second architecture, as a later change would add one: a Llama with an
# untied output head (``lm_head``), in files of its own under
# ``bench/models/tiny_untied/`` that build on Llama's.
UNTIED_FILES = {
    "program.py": '''"""A Llama with an untied output head: the program's dense family."""
import dataclasses
import pathlib

from harness import manifest

LLAMA = manifest.load_model("llama", pathlib.Path(__file__).resolve().parents[3])


def arch_config(name, config):
    return dataclasses.replace(LLAMA.program.arch_config(name, config), tie_embeddings=False)
''',
    "reference.py": '''"""A Llama with an untied output head ``lm_head`` (vocab, d), initialised
from the program's head key, ``split(PRNGKey(seed), 5)[2]``."""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp

from harness import manifest

llama = manifest.load_model("llama", pathlib.Path(__file__).resolve().parents[3]).reference
APART = llama.APART


@dataclasses.dataclass(frozen=True)
class Untied(llama.Llama):
    def init_params(self, seed_key):
        params = super().init_params(seed_key)
        k_head = jax.random.split(seed_key, 5)[2]
        params["lm_head"] = llama.trunc(k_head, (self.vocab, self.d), self.vocab,
                                        jnp.dtype(self.param_dtype))
        return params

    def forward(self, params, tokens, labels, mm):
        logits = mm("bsd,vd->bsv", self.hidden(params, tokens, mm), params["lm_head"])
        return llama.token_nll(logits, labels), 0.0


def from_config(config):
    return Untied(**llama.sizes(config))
''',
    "flops.py": '''"""As Llama's: the untied head is one matrix product, as the tied one."""
import pathlib

from harness import manifest

train_flops_per_token = manifest.load_model(
    "llama", pathlib.Path(__file__).resolve().parents[3]).flops.train_flops_per_token
''',
}


def add_untied_model(root: pathlib.Path) -> str:
    """Add the untied model and a cell of it to ``make_root``'s ``root`` by
    new files and new entries only; returns the cell's name."""
    bench = root / "bench"
    (bench / "models" / "tiny_untied").mkdir()
    for name, text in UNTIED_FILES.items():
        (bench / "models" / "tiny_untied" / name).write_text(text)
    config = json.loads((bench / "configs" / "tiny.json").read_text())
    config.update(model_type="tiny_untied", tie_word_embeddings=False)
    (bench / "configs" / "tiny_untied.json").write_text(json.dumps(config))
    (bench / "workloads" / "tiny_untied.lad.json").write_text(json.dumps({"limits": LIMITS}))
    data = json.loads((root / "BENCHMARK.json").read_text())
    data["configs"].append(dict(data["configs"][0], name="tiny_untied",
                                file="bench/configs/tiny_untied.json"))
    data["workloads"].append(dict(data["workloads"][0], name="tiny_untied.lad",
                                  config="tiny_untied"))
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return "tiny_untied.lad"
