"""Pytree checkpointing to .npz with structure + sharding-spec metadata.

Arrays are gathered to host (``jax.device_get``) and written as a flat npz
keyed by the pytree path; a JSON sidecar stores the treedef, dtypes and the
logical sharding spec of every leaf so a restore can re-``device_put`` onto
the production mesh layout.

Writes are atomic (tmp file + ``os.replace`` per file) so a process killed
mid-save — the fleet's crash-recovery regime, ``launch/fleet.py`` — can
never leave a half-written npz/sidecar behind: a reader sees either the
previous complete checkpoint or the new one.
"""
from __future__ import annotations

import json
import os
from typing import Any

import jax
import numpy as np


def _flatten_with_paths(tree) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = leaf
    return flat


def save_checkpoint(path: str, params, step: int = 0, specs=None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    flat = _flatten_with_paths(params)
    arrays = {k: np.asarray(jax.device_get(v)) for k, v in flat.items()}
    # non-native dtypes (bfloat16 & friends) round-trip through fp32
    stored = {
        k: (v.astype(np.float32) if v.dtype.kind == "V" or v.dtype.name == "bfloat16"
            else v)
        for k, v in arrays.items()
    }
    tmp_npz = path + ".tmp.npz"
    np.savez(tmp_npz, **stored)
    os.replace(tmp_npz, path + ".npz")
    meta = {
        "step": step,
        "keys": sorted(arrays.keys()),
        "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
        "shapes": {k: list(v.shape) for k, v in arrays.items()},
    }
    if specs is not None:
        flat_specs = _flatten_with_paths(
            jax.tree.map(lambda s: list(s), specs, is_leaf=lambda x: isinstance(x, tuple))
        )
        meta["specs"] = {k: v for k, v in flat_specs.items()}
    tmp_json = path + ".tmp.json"
    with open(tmp_json, "w") as f:
        json.dump(meta, f, indent=1, default=str)
    os.replace(tmp_json, path + ".json")


def load_checkpoint(path: str, like) -> tuple[Any, int]:
    """Restore into the structure of ``like`` (a params pytree or eval_shape)."""
    data = np.load(path + ".npz")
    with open(path + ".json") as f:
        meta = json.load(f)
    flat_like = _flatten_with_paths(like)
    missing = set(flat_like) - set(data.files)
    extra = set(data.files) - set(flat_like)
    if missing or extra:
        raise ValueError(f"checkpoint mismatch: missing={missing} extra={extra}")
    import jax.numpy as jnp

    restored_flat = {
        k: data[k].astype(jnp.dtype(meta["dtypes"][k])) for k in flat_like
    }
    leaves_like, treedef = jax.tree_util.tree_flatten(like)
    paths = [
        "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(like)[0]
    ]
    leaves = [restored_flat[p] for p in paths]
    return jax.tree_util.tree_unflatten(treedef, leaves), int(meta["step"])


def restore_for_serving(path: str, cfg) -> tuple[Any, Any, int]:
    """Restore a training checkpoint straight into the serving path.

    Rebuilds the params structure of ``cfg`` via ``jax.eval_shape`` (no
    weight allocation — the ``like`` tree is shapes only), loads the npz
    into it and returns ``(params, specs, step)`` ready for
    ``launch.serve.build_prefill_fn`` / ``build_decode_fn``.  This is the
    consumer half of the train-to-serve loop: a trainer saves with
    ``save_checkpoint``; a serving process needs only the ``ArchConfig`` and
    this path to come up.
    """
    import jax

    from repro import models

    # the logical-spec tree is plain python data produced during tracing —
    # not a valid eval_shape output — so it rides a side channel
    captured = {}

    def only_params(k):
        p, s = models.init(k, cfg)
        captured["specs"] = s
        return p

    like = jax.eval_shape(only_params, jax.random.PRNGKey(0))
    params, step = load_checkpoint(path, like)
    return params, captured["specs"], step
