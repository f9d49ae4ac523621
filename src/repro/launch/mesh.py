"""Mesh construction: the production GSPMD meshes and the engine's 1-D mesh.

Two mesh families are exposed here:

* **Production meshes** (``make_production_mesh`` / ``make_host_mesh``) — the
  ("data", "model") / ("pod", "data", "model") GSPMD meshes of the protomath
  train path.  Single pod: 16 x 16 = 256 chips; multi-pod: 2 x 16 x 16 = 512
  chips with the ``pod`` axis extending the Byzantine/data-parallel domain
  across the DCN/ICI boundary.

* **Engine meshes** (``make_engine_mesh`` + ``engine_device_grid`` /
  ``engine_device_count`` / ``padded_lane_count``) — the 1-D named device
  mesh the protocol-engine paths shard over: ``core.engine.run_grid``
  partitions its scenario-*lane* axis over it, and
  ``launch.train.build_engine_step`` (``TrainConfig.shard``) its LM *subset*
  fan-out.  These are defined in ``core.engine`` (beside ``pad_lanes``, the
  replication half of the same padding contract, keeping the core -> launch
  dependency arrow one-way) and re-exported here as the deployment-layer
  entry point.

The engine mesh is **multi-process-ready**: devices are assembled
process-major — each of ``jax.process_count()`` processes contributes its
local devices as one contiguous run — so a sharded lane/subset axis maps
whole per-process blocks first, and a future multi-host launch changes the
device list, not the sharding or padding/replication contract.  Today every
caller is single-process.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and only then calls these.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

from repro.core.engine import (  # noqa: F401  (re-exported deployment API)
    engine_device_count,
    engine_device_grid,
    make_engine_mesh,
    padded_lane_count,
)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the GSPMD train path relies
    on sharding propagation, which the default ``Explicit`` axis types turn
    into per-op ``out_sharding`` requirements (e.g. on the embedding gather)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    want = data * model * (pod or 1)
    if want > n:
        raise ValueError(f"mesh {want} > available devices {n}")
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_data_devices(mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh))
