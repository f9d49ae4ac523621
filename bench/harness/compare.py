"""The comparison that decides ``correct``.

Each number is a gap between the program's reading and the reference's;
a cell compares those its workload file gives a limit for:

- ``loss_gap``: over the first steps, the largest relative gap of a step's
  loss, |L_prog - L_ref| / |L_ref|;
- ``grad_norm_gap``: over parameter leaves but those the model's
  reference holds apart (its ``APART``), the largest gap between the norms
  of the first aggregated gradient, |n_prog - n_ref| divided by the larger
  of n_ref and the median leaf's n_ref; ``embed_grad_norm_gap`` the same
  gap, worst leaf, of the leaves held apart, with a limit of its own (for
  Llama the embedding table: the program sums its gradient over a subset's
  tokens in bfloat16, so it reads ten times the others' round-off);
- ``update_norm_gap``: the same of each leaf's change over the steps, worst
  leaf.  Leaves whose first reference gradient is under a thousandth of the
  median leaf's are left out: Adam moves them by round-off alone.
"""
from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp

NUMBERS = ("loss_gap", "grad_norm_gap", "embed_grad_norm_gap", "update_norm_gap")
NEGLIGIBLE_GRAD = 1e-3  # of the median leaf's first gradient norm


@jax.jit
def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for k, x in tree.items()}


@jax.jit
def change_norms(new: dict, old: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(new[k].astype(jnp.float32) - old[k].astype(jnp.float32))))
            for k in new}


@jax.jit
def all_finite(tree: dict):
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)]))


def leaf_gaps(prog: dict, ref: dict, leaves) -> dict:
    """Leaf -> |prog - ref| / max(ref, the median leaf's ref); ``{}`` when
    the program's leaves are not the model's."""
    leaves = list(leaves)
    if set(prog) != set(ref):
        return {}
    med = statistics.median(ref[k] for k in leaves)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}


def _worst(gaps: dict) -> float:
    return max(gaps.values()) if gaps and all(map(math.isfinite, gaps.values())) else math.inf


def _rel(p: float, r: float) -> float:
    gap = abs(p - r) / abs(r)
    return gap if math.isfinite(gap) else math.inf


def moving_leaves(ref: dict) -> list:
    med = statistics.median(ref["first_grad"].values())
    return [k for k, g in ref["first_grad"].items() if g >= NEGLIGIBLE_GRAD * med]


def readings(prog: dict, ref: dict, apart: tuple[str, ...]) -> dict:
    """``prog`` and ``ref`` as ``reference.run`` returns them; ``apart`` the
    model's leaves compared apart (its reference's ``APART``)."""
    losses = [_rel(p, r) for p, r in zip(prog["losses"], ref["losses"])]
    grad = leaf_gaps(prog["first_grad"], ref["first_grad"], ref["first_grad"])
    change = leaf_gaps(prog["change"], ref["change"], moving_leaves(ref))
    return {
        "loss_gap": max(losses),
        "grad_norm_gap": _worst({k: v for k, v in grad.items() if k not in apart}),
        "embed_grad_norm_gap": _worst({k: v for k, v in grad.items() if k in apart}),
        "update_norm_gap": _worst(change),
    }


def checks(values: dict, limits: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` for each number the cell's
    workload file gives a limit, in the order of ``NUMBERS``."""
    if not set(limits) & set(NUMBERS) or set(limits) - set(NUMBERS):
        raise ValueError(f"limits must name some of {NUMBERS}, got {sorted(limits)}")
    return {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS if k in limits}


def passed(checked: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checked.values())
