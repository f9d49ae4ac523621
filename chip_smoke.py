"""Smoke test of LAD / Com-LAD on a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip: phases 1-4
    python chip_smoke.py --four-chips  # four chips: phase 5 only

Phases (each raises on failure, so the script exits non-zero):

1. Device: the first JAX device must be a TPU.  On anything else the script
   exits 1 without running a phase; it never carries on on the CPU.
2. Protocol round on a seeded (N=16, Q=2^24) fp32 stack (1 GiB): LAD d=2,
   CWTM, 3 Byzantine sign-flippers, compressor none and quant, with the
   Pallas kernels (``backend="pallas"``) against the XLA path.
3. The paper's Section-VII grid (``scenarios.section7_grid()``, 20 steps)
   with every row on ``backend="pallas"`` against the same grid on XLA:
   this puts many lanes (L > 1) through every kernel.
4. The LM train step: SmolLM-360M at its published widths (depth cut to
   ``LM_LAYERS`` whole layers to fit one 16 GiB chip), LAD d=2 + CWTM
   under sign-flip through ``launch.train.Trainer``'s protocol-engine step,
   8 adamw steps on one fixed seeded batch at seq 512.
5. (``--four-chips``) the engine train step with ``shard="shard_map"`` over
   the four chips against ``shard="none"`` on the same inputs.

Other lines are information; the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.archs import ARCHS  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.core import engine, scenarios  # noqa: E402
from repro.core.attacks import AttackSpec  # noqa: E402
from repro.core.byzantine import ProtocolConfig, protocol_round  # noqa: E402
from repro.core.compression import CompressionSpec  # noqa: E402
from repro.data.synthetic import lm_batch_for_devices  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import Trainer  # noqa: E402

SEED = 0

# Phase 2: the stack the protocol round aggregates, (N, Q) fp32.
ROUND_N, ROUND_Q, ROUND_BYZ = 16, 1 << 24, 3
# Phase 2.  With no compressor the two paths differ only in the order of
# the CWTM mean's adds (values O(1), 10 kept rows): a few fp32 ulps.
ROUND_ATOL = 1e-5
# With quant, a coordinate whose stochastic rounding sits within an ulp of
# its threshold may round to the neighbouring level on one path; the
# trimmed mean is 1-Lipschitz in the max-norm, so such a coordinate moves
# by at most one level step (max|g| / levels).  At most this fraction of
# coordinates may exceed ROUND_ATOL.
QUANT_FLIP_FRAC = 1e-4

# Phase 3: relative tolerance on each grid row's final loss.  The kernels
# sum the eq.-(5) combine and the CWTM mean in other orders than XLA.
GRID_RTOL = 1e-4

# Phase 4: SmolLM-360M keeps all published widths; depth is cut to whole
# layers.  The engine round program compiled for one v5e chip at these
# settings (N=4 subsets, 1 row each, seq 512) needs 10.90 GiB of temp at 20
# layers beside 0.46 GiB of arguments, 0.91 GiB of output and 0.91 GiB of
# adamw state: about 13.2 GiB of 16 GiB.  24 layers need 14.77 GiB of temp.
LM_LAYERS = 20
LM_SEQ = 512
LM_SUBSETS = 4
LM_STEPS = 8

# Phase 5 compares two substrates by their parameter updates after a few
# steps, ||p_a - p_b|| / ||p_b - p_0||.  It runs fp32 params and plain SGD:
# in bf16 a tiny gradient difference can flip a rounding of the parameter,
# and adamw's first step is sign(g), both of which turn an accumulation-
# order difference into a whole update.  The schedule's first step has lr 0,
# so 3 steps make 2 updates.  Widths are published; depth is cut to 4.
FOUR_CHIP_LAYERS = 4
FOUR_CHIP_STEPS = 3
UPDATE_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ phase 2


def phase_protocol_round() -> None:
    n, q, n_byz = ROUND_N, ROUND_Q, ROUND_BYZ
    k_data, k_round = jax.random.split(jax.random.PRNGKey(SEED))
    g = jax.random.normal(k_data, (n, q), jnp.float32)
    g_max = float(jnp.max(jnp.abs(g)))
    for comp in ("none", "quant"):
        spec = CompressionSpec(comp)
        base = ProtocolConfig(
            n_devices=n, d=2, method="lad", aggregator="cwtm",
            trim_frac=n_byz / n, n_byz=n_byz,
            attack=AttackSpec("sign_flip", n_byz=n_byz), compression=spec,
        )
        outs = {}
        for backend in ("pallas", "xla"):
            cfg = dataclasses.replace(base, backend=backend)
            out = jax.jit(functools.partial(protocol_round, cfg))(k_round, g)
            check(out.shape == (q,), f"{backend}: shape {out.shape}")
            check(bool(jnp.all(jnp.isfinite(out))), f"{backend}: non-finite output")
            outs[backend] = out
        diff = jnp.abs(outs["pallas"] - outs["xla"])
        max_diff = float(jnp.max(diff))
        n_over = int(jnp.sum(diff > ROUND_ATOL))
        if comp == "none":
            log(f"phase 2 protocol_round compressor=none: max |pallas - xla| = "
                f"{max_diff!r} (tolerance {ROUND_ATOL})")
            check(max_diff <= ROUND_ATOL, "pallas and xla rounds disagree")
        else:
            step = g_max / spec.levels
            log(f"phase 2 protocol_round compressor=quant: max |pallas - xla| = "
                f"{max_diff!r}, {n_over} of {q} coordinates above {ROUND_ATOL} "
                f"(tolerance: at most {QUANT_FLIP_FRAC * q:.0f}, each within one "
                f"level step {step!r})")
            check(n_over <= QUANT_FLIP_FRAC * q and max_diff <= step,
                  "pallas and xla quantized rounds disagree")


# ------------------------------------------------------------------ phase 3


def phase_grid() -> None:
    rows = scenarios.section7_grid()
    finals = {}
    for backend in ("pallas", "xla"):
        grid = [dataclasses.replace(s, backend=backend) for s in rows]
        finals[backend] = scenarios.grid_finals(scenarios.run_grid(grid, steps=20))
    worst = 0.0
    for s in rows:
        a = finals["pallas"][s.name]["final_loss"]
        b = finals["xla"][s.name]["final_loss"]
        check(np.isfinite(a) and np.isfinite(b), f"{s.name}: non-finite loss")
        rel = abs(a - b) / abs(b)
        worst = max(worst, rel)
        check(rel <= GRID_RTOL, f"{s.name}: final loss pallas {a!r} vs xla {b!r}")
    log(f"phase 3 section7_grid ({len(rows)} rows, 20 steps): max relative "
        f"final-loss difference pallas vs xla = {worst!r} (tolerance {GRID_RTOL})")
    engine.clear_program_caches()


# ------------------------------------------------------------------ phase 4


def _lm_cfg(layers: int):
    return ARCHS["smollm-360m"].scaled(n_layers=layers)


def _lm_tcfg(cfg, **kw) -> TrainConfig:
    return TrainConfig(**{
        **dict(arch=cfg.name, protocol="lad", n_subsets=LM_SUBSETS, d=2,
               aggregator="cwtm", trim_frac=0.25, n_byz=1, attack="sign_flip",
               optimizer="adamw", lr=3e-3, steps=LM_STEPS, seed=SEED),
        **kw,
    })


def _lm_batch(cfg, n_subsets: int) -> dict:
    b = lm_batch_for_devices(
        jax.random.PRNGKey(SEED + 1), cfg.vocab, n_subsets=n_subsets,
        per_subset=1, seq_len=LM_SEQ,
    )
    return {k: v.reshape(-1, v.shape[-1]) for k, v in b.items()}


def phase_lm_train() -> None:
    cfg = _lm_cfg(LM_LAYERS)
    tr = Trainer(cfg=cfg, tcfg=_lm_tcfg(cfg), mesh=make_host_mesh(1, 1))
    n_params = sum(x.size for x in jax.tree.leaves(tr.params))
    batch = _lm_batch(cfg, LM_SUBSETS)
    stamps = []

    def batches():
        for _ in range(LM_STEPS):
            stamps.append(time.perf_counter())
            yield batch

    # log_every=1 reads every loss back, so step i has finished when batch
    # i+1 is requested: the stamps bound each step's time
    hist = tr.run(batches(), log_every=1)
    stamps.append(time.perf_counter())
    losses = [l for _, l in hist]
    times = np.diff(stamps)
    log(f"phase 4 smollm-360m x {LM_LAYERS} layers ({n_params} params, bf16), "
        f"engine step, seq {LM_SEQ}: losses {losses}")
    log(f"phase 4 step seconds (first includes compile): {times.tolist()}; "
        f"warm steps {times[2:].tolist()} (information only)")
    check(len(losses) == LM_STEPS and all(np.isfinite(losses)), "non-finite LM loss")
    check(losses[-1] < losses[0], f"LM loss did not fall: {losses}")


# ------------------------------------------------------------------ phase 5


def _params_after(cfg, tcfg, mesh, batch):
    tr = Trainer(cfg=cfg, tcfg=tcfg, mesh=mesh)
    p0 = [np.asarray(x, np.float32) for x in jax.tree.leaves(tr.params)]
    hist = tr.run([batch] * FOUR_CHIP_STEPS, log_every=1)
    p = [np.asarray(x, np.float32) for x in jax.tree.leaves(tr.params)]
    return p0, p, [l for _, l in hist]


def _update_rel_diff(p0, pa, pb) -> float:
    num = sum(float(np.sum((a - b) ** 2)) for a, b in zip(pa, pb))
    den = sum(float(np.sum((b - z) ** 2)) for b, z in zip(pb, p0))
    return (num / den) ** 0.5


def phase_four_chips() -> None:
    check(len(jax.devices()) == 4, f"need 4 devices, have {len(jax.devices())}")
    cfg = _lm_cfg(FOUR_CHIP_LAYERS).scaled(param_dtype="float32")
    batch = _lm_batch(cfg, 4)

    def tcfg(**kw):
        return _lm_tcfg(cfg, optimizer="sgd", steps=FOUR_CHIP_STEPS, **kw)

    mesh = make_host_mesh(1, 1)
    runs = {s: _params_after(cfg, tcfg(shard=s), mesh, batch)
            for s in ("shard_map", "none")}
    rel = _update_rel_diff(runs["none"][0], runs["shard_map"][1], runs["none"][1])
    log(f"phase 5 engine step shard_map (4 chips) vs none: losses "
        f"{runs['shard_map'][2]} vs {runs['none'][2]}; relative update difference "
        f"{rel!r} (tolerance {UPDATE_RTOL})")
    check(rel <= UPDATE_RTOL, "sharded engine step disagrees with the unsharded one")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the four-chip phase")
    args = parser.parse_args()

    info = device_info()
    if info["platform"] != "tpu":
        print(f"phase 1: no TPU (first device is {info['platform']!r}); "
              "this smoke test runs only on the chip", file=sys.stderr)
        sys.exit(1)
    log(f"phase 1 device: {info}; compile cache at {enable_compile_cache()}")

    if args.four_chips:
        phase_four_chips()
    else:
        phase_protocol_round()
        phase_grid()
        phase_lm_train()
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)


if __name__ == "__main__":
    main()
