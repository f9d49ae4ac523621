"""Stage scopes and step spans of the engine train path.

The round program's stages (``launch/train.py``, ``core/byzantine.py``) run
under ``lad.*`` named scopes, which reach the compiled HLO as ``op_name``
metadata and change nothing else; ``Trainer.run`` and the engine step write
``lad.*`` host spans into the profiler's trace.  ``bench/harness/stages.py``
reads both from a chip's trace.
"""
import contextlib
import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp

from repro import models
from repro.configs.archs import ARCHS, reduced
from repro.configs.base import TrainConfig
from repro.launch import train as train_lib
from repro.launch.mesh import make_host_mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]
ROUND_STAGES = {"lad.fanout", "lad.flatten", "lad.encode", "lad.compress", "lad.attack",
                "lad.aggregate"}
APPLY_STAGES = {"lad.unflatten", "lad.optimizer"}
HOST_SPANS = ("lad.step", "lad.place", "lad.dispatch_round", "lad.dispatch_apply",
              "lad.readback")

# Lowers and compiles the tiny round and apply programs on 4 virtual CPU
# devices and prints the lad.* scopes in their op_name metadata.
_SCRIPT = r"""
import json, re, sys
import jax, jax.numpy as jnp
sys.path.insert(0, sys.argv[1])
from test_stage_scopes import compiled_texts
out = {}
for shard in ("none", "shard_map"):
    texts = compiled_texts(shard)
    out[shard] = {k: sorted(set(re.findall(r"lad\.[a-z_]+", "\n".join(
        re.findall(r'op_name="([^"]*)"', v))))) for k, v in texts.items()}
print(json.dumps({"devices": len(jax.devices()), "scopes": out}))
"""


def _tiny():
    return reduced(ARCHS["smollm-360m"]).scaled(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, head_dim=32, d_ff=128, vocab=128)


def _tcfg(shard, **kw):
    base = dict(arch=_tiny().name, protocol="lad", protocol_impl="engine", n_subsets=4,
                d=2, aggregator="cwtm", trim_frac=0.25, n_byz=1, attack="sign_flip",
                compression="quant", optimizer="adamw", lr=3e-3, steps=8, shard=shard)
    base.update(kw)
    return TrainConfig(**base)


def compiled_texts(shard: str) -> dict:
    """Optimized HLO text of the tiny engine step's round and apply programs
    (Com-LAD: LAD d=2 + quantization, sign-flip, CWTM)."""
    cfg, tcfg = _tiny(), _tcfg(shard)
    train_lib.engine_program_cache_clear()
    params, specs = models.init(jax.random.PRNGKey(0), cfg)
    blocks = {k: jnp.zeros((4, 2, 16), jnp.int32) for k in ("tokens", "labels")}
    round_prog = train_lib._engine_round_program(cfg, tcfg, 4, specs)
    apply_prog = train_lib._engine_apply_program(tcfg)
    opt_state = train_lib.make_optimizer(tcfg.optimizer, momentum_dtype=tcfg.momentum_dtype
                                         ).init(params)
    g_flat = jnp.zeros((sum(x.size for x in jax.tree.leaves(params)),), jnp.float32)
    return {
        "round": round_prog.lower(params, blocks, jax.random.PRNGKey(1)).compile().as_text(),
        "apply": apply_prog.lower(params, opt_state, g_flat, jnp.int32(0)).compile().as_text(),
    }


def test_compiled_programs_name_every_stage_on_four_devices():
    """``shard="none"`` and ``shard_map`` over 4 virtual devices: the op_name
    metadata of the compiled programs holds every stage of the path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip())
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(ROOT / "tests")], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    scopes = got["scopes"]
    assert set(scopes["none"]["round"]) == ROUND_STAGES
    assert set(scopes["shard_map"]["round"]) == ROUND_STAGES | {"lad.gather"}
    for shard in ("none", "shard_map"):
        assert set(scopes[shard]["apply"]) == APPLY_STAGES


def _instructions(hlo: str) -> list[str]:
    """The instructions of an HLO text, without their metadata (the text
    also lists the source lines of the trace, which differ by caller)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line) for line in hlo.splitlines()
            if re.match(r"\s*(ROOT )?%\S+ = ", line)]


def test_scopes_are_metadata_only(monkeypatch):
    """Without the scopes the optimized programs hold the same instructions,
    once their metadata is left out."""
    scoped = compiled_texts("none")
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = compiled_texts("none")
    train_lib.engine_program_cache_clear()
    assert "lad.fanout" in scoped["round"] and "lad.fanout" not in plain["round"]
    for k in ("round", "apply"):
        assert _instructions(scoped[k]) == _instructions(plain[k]), k


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(str(path)).planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name.startswith("lad.")]


def test_trainer_run_writes_step_spans():
    """Each step of ``Trainer.run`` is one ``lad.step`` span carrying its step
    number, around the step's placement, round and apply dispatch, and the
    loss read-back of the steps it logs."""
    tcfg = _tcfg("none", compression="none", steps=3)
    tr = train_lib.Trainer(cfg=_tiny(), tcfg=tcfg, mesh=make_host_mesh(1, 1))
    batch = {k: jnp.zeros((8, 16), jnp.int32) for k in ("tokens", "labels")}
    tr.run([batch], log_every=1)  # compiled outside the trace
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        tr.run([batch, batch], log_every=2)
        jax.profiler.stop_trace()
        spans = _host_spans(d)
    steps = sorted((s for s in spans if s[0] == "lad.step"), key=lambda s: s[1])
    assert [int(s[3]["step_num"]) for s in steps] == [0, 1]
    for i, (_, lo, hi, _) in enumerate(steps):
        inside = sorted(s[0] for s in spans if s[0] != "lad.step" and lo <= s[1] and s[2] <= hi)
        # placement twice: the batch in Trainer.run, the step's inputs in the step
        want = ["lad.dispatch_apply", "lad.dispatch_round", "lad.place", "lad.place"]
        assert inside == sorted(want + (["lad.readback"] if i == 0 else [])), (i, inside)
    assert {s[0] for s in spans} == set(HOST_SPANS)
