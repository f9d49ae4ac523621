"""CPU tests that the comparison deciding ``correct`` can fail.

At the tiny size of ``tiny.py``: the control (the plain reference computed
with float8 products) fails the cell's limits, and a whole run of the
harness, past its look for a chip, comes out not correct when the timed
path underneath is broken in each way a training cell can be: a step that
leaves its state unchanged, half of each subset's batch left out, and (on
four virtual devices) the exchange of gradients between chips left out.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from harness import cell as cell_lib
from harness import compare, manifest, reference, traffic

import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def engine_programs():
    """The program's compiled-step cache, emptied around a test that plants
    a fault in it; the persistent compile cache stays off in-process."""
    from repro.launch import train

    train.engine_program_cache_clear()
    yield train
    train.engine_program_cache_clear()


def _run_tiny(root, monkeypatch):
    monkeypatch.setattr(cell_lib, "enable_cache", lambda root: None)
    return cell_lib.run("tiny.lad", 5, 0.2, False, root=root, check_device=False)


@pytest.mark.parametrize("seed", [1, 2])
def test_control_fails_the_limits(tmp_path, seed):
    root = tiny.make_root(tmp_path)
    cell = manifest.load_cell("tiny.lad", root)
    model = manifest.load_model(cell.config["model_type"], root)
    pool = traffic.batch_pool(seed, cell.config["vocab_size"], cell.traffic)
    steps = cell_lib.CHECKED_STEPS

    def ref(mode):
        return reference.run(model, seed, cell.config, cell.traffic, pool[:steps], steps,
                             mode=mode)

    checked = compare.checks(compare.readings(ref("fp8"), ref("f32"), model.reference.APART),
                             cell.limits)
    assert not compare.passed(checked), checked


def test_sound_run_is_correct(tmp_path, monkeypatch, engine_programs):
    result = _run_tiny(tiny.make_root(tmp_path), monkeypatch)
    assert result["correct"] is True, result["checks"]


def test_state_left_unchanged_is_not_correct(tmp_path, monkeypatch, engine_programs):
    real = engine_programs.make_optimizer

    def frozen(name, **kw):
        opt = real(name, **kw)
        return opt._replace(update=lambda params, grads, state, lr, **_: (params, state))

    monkeypatch.setattr(engine_programs, "make_optimizer", frozen)
    result = _run_tiny(tiny.make_root(tmp_path), monkeypatch)
    assert result["correct"] is False
    assert result["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(tmp_path, monkeypatch, engine_programs):
    from repro import models

    real = models.loss_fn

    def first_half(params, specs, cfg, batch, **kw):
        half = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
        return real(params, specs, cfg, half, **kw)

    monkeypatch.setattr(models, "loss_fn", first_half)
    result = _run_tiny(tiny.make_root(tmp_path), monkeypatch)
    assert result["correct"] is False, result["checks"]


FOUR_DEVICES = """
import json, pathlib, sys
import jax, jax.numpy as jnp
import tiny
from harness import cell as cell_lib
from repro.launch import train

cell_lib.enable_cache = lambda root: None
root = tiny.make_root(pathlib.Path(sys.argv[1]), chips=4, shard="shard_map")
out = {"devices": len(jax.devices())}
out["sound"] = cell_lib.run("tiny.lad", 5, 0.2, False, root=root, check_device=False)["correct"]
train.engine_program_cache_clear()


def own_gradient_only(x, axis_name, *, tiled=False, **kw):
    # every chip's server sees its own subset's gradient in every slot
    return jnp.concatenate([x] * 4, axis=0) if tiled else jnp.stack([x] * 4)


jax.lax.all_gather = own_gradient_only
out["no_exchange"] = cell_lib.run("tiny.lad", 5, 0.2, False, root=root,
                                  check_device=False)["correct"]
print(json.dumps(out))
"""


def test_exchange_left_out_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(str(p) for p in (
                   BENCH / "tests", BENCH, BENCH.parent / "src")))
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(tmp_path)],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"devices": 4, "sound": True, "no_exchange": False}
