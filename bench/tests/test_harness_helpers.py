"""CPU tests of the benchmark's own helpers: FLOPs, peaks, traffic, the
comparison, and the refusal to run without a TPU."""
import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

from harness import compare, device, manifest, traffic

ROOT = manifest.ROOT
LLAMA = manifest.load_model("llama")


def _config(name="smollm360m-l20"):
    return json.loads(manifest.config_path(ROOT, name).read_text())


def test_flops_match_hand_count():
    cfg = _config()
    flops = manifest.load_model(cfg["model_type"]).flops
    # per layer: wq, wo 960x960 each; wk, wv 960x320 each; SwiGLU 3 x 960x2560
    per_layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    assert per_layer == 9_830_400
    assert flops.matmul_params(cfg) == 20 * 9_830_400 + 49152 * 960 == 243_793_920
    # causal attention at seq 512: 20 layers x 12 x 15 heads x 64 x 513/2
    assert flops.attention_flops(cfg, 512) == 59_097_600
    assert flops.train_flops_per_token(cfg, 512) == 6 * 243_793_920 + 59_097_600


def test_peaks_are_known_only_for_listed_kinds():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("TPU v99")


def test_require_tpu_refuses_other_devices():
    class Dev:
        def __init__(self, platform):
            self.platform, self.device_kind = platform, platform

    with pytest.raises(device.NoAccelerator):
        device.require_tpu([Dev("cpu")], 1)
    with pytest.raises(device.NoAccelerator):
        device.require_tpu([Dev("tpu")], 4)
    device.require_tpu([Dev("tpu")] * 4, 4)


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_batch_pool_is_fixed_by_the_seed():
    mix = dict(json.loads(manifest.traffic_path(ROOT, "lad-cwtm.s512").read_text()),
               seq_len=16, pool_batches=3)
    a = traffic.batch_pool(7, 512, mix)
    b = traffic.batch_pool(7, 512, mix)
    c = traffic.batch_pool(2**31 + 5, 512, mix)
    assert len(a) == 3 and a[0]["tokens"].shape == (4, 16)
    assert all(bool(jnp.all(x["tokens"] == y["tokens"])) for x, y in zip(a, b))
    assert not bool(jnp.all(a[0]["tokens"] == c[0]["tokens"]))
    assert not bool(jnp.all(a[0]["tokens"] == a[1]["tokens"]))
    assert int(a[0]["tokens"].max()) < 512 and int(a[0]["tokens"].min()) >= 0
    assert traffic.tokens_per_step(mix) == 4 * 1 * 16


def test_readings_take_the_worst_leaf_against_the_median():
    (t,) = LLAMA.reference.APART
    ref = {"losses": [10.0, 9.0], "first_grad": {"a": 1.0, "b": 2.0, "c": 1e-6, t: 3.0},
           "change": {"a": 0.5, "b": 0.5, "c": 0.0, t: 0.5}}
    prog = {"losses": [10.1, 9.0], "first_grad": {"a": 1.1, "b": 2.0, "c": 0.1, t: 3.9},
            "change": {"a": 0.5, "b": 0.4, "c": 0.3, t: 0.5}}
    got = compare.readings(prog, ref, LLAMA.reference.APART)
    assert got["loss_gap"] == pytest.approx(0.01)
    # leaves a and c: 0.1 / median(1, 2, 1e-6, 3) = 0.1 / 1.5; the embedding
    # table, 0.9 / 3, is compared apart
    assert got["grad_norm_gap"] == pytest.approx(0.1 / 1.5, rel=1e-4)
    assert got["embed_grad_norm_gap"] == pytest.approx(0.3)
    # leaf c's reference gradient is under 1e-3 of the median: not compared
    assert got["update_norm_gap"] == pytest.approx(0.2)
    checked = compare.checks(got, {"loss_gap": 0.02, "grad_norm_gap": 0.2,
                                   "update_norm_gap": 0.25})
    assert compare.passed(checked)
    checked["update_norm_gap"]["limit"] = 0.1
    assert not compare.passed(checked)
    # a cell compares the numbers its workload file gives limits for
    assert list(compare.checks(got, {"loss_gap": 0.02})) == ["loss_gap"]
    with pytest.raises(ValueError, match="limits must name"):
        compare.checks(got, {"loss_gap": 0.02, "logit_gap": 1.0})


def test_readings_fail_on_nan_and_on_missing_leaves():
    ref = {"losses": [1.0], "first_grad": {"a": 1.0}, "change": {"a": 1.0}}
    got = compare.readings({"losses": [float("nan")], "first_grad": {"b": 1.0},
                            "change": {"a": 1.0}}, ref, LLAMA.reference.APART)
    assert got["loss_gap"] == float("inf") and got["grad_norm_gap"] == float("inf")
