"""The benchmark's manifest and the data files it names.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics by name.  Everything that belongs to one of them is a file of its own
under ``bench/``, found from the name alone:

    bench/configs/<config>.json     model sizes, training dtypes, deployment
    bench/traffic/<traffic>.json    the job: protocol, batch shape, schedule
    bench/workloads/<cell>.json     the cell's correctness limits and the
                                    readings they were set from
    bench/metrics/<metric>.py       a reader ``read(ctx) -> float | None``
    bench/models/<model_type>/      one architecture, chosen by the
                                    configuration's ``model_type``:
        program.py                  ``arch_config(name, config)``, the
                                    program's ``ArchConfig``
        reference.py                ``from_config(config)``, the model's part
                                    of the float32 reference, and ``APART``
        flops.py                    ``train_flops_per_token(config, seq_len)``

So a later change adds a cell, a configuration, an architecture or a metric
by adding files and entries, and edits nothing that is already here.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re
import sys
from typing import Any, Callable

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


MODEL_PARTS = ("program", "reference", "flops")


@dataclasses.dataclass(frozen=True)
class Model:
    """The modules of ``bench/models/<model_type>/``, by part."""

    program: Any
    reference: Any
    flops: Any


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads``: a configuration under a traffic mix."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _read_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: pathlib.Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def config_path(root: pathlib.Path, name: str) -> pathlib.Path:
    return root / "bench" / "configs" / f"{name}.json"


def traffic_path(root: pathlib.Path, name: str) -> pathlib.Path:
    return root / "bench" / "traffic" / f"{name}.json"


def workload_path(root: pathlib.Path, name: str) -> pathlib.Path:
    return root / "bench" / "workloads" / f"{name}.json"


def metric_path(root: pathlib.Path, name: str) -> pathlib.Path:
    return root / "bench" / "metrics" / f"{name}.py"


def models_dir(root: pathlib.Path) -> pathlib.Path:
    return root / "bench" / "models"


def reports(metric: dict, cell: str) -> bool:
    """Whether ``metric`` is reported in ``cell`` (no ``workloads``: all)."""
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    manifest = load_manifest(root)
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(entries)}")
    entry = entries[name]
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_read_json(config_path(root, entry["config"])),
        traffic=_read_json(traffic_path(root, entry["traffic"])),
        limits=_read_json(workload_path(root, name))["limits"],
        end_to_end=tuple(m for m in manifest["end_to_end"] if reports(m, name)),
        per_layer=tuple(m for m in manifest["per_layer"] if reports(m, name)),
    )


def _load_module(module_name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module  # a dataclass looks its module up
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, root: pathlib.Path = ROOT) -> Callable[[Any], float | None]:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _load_module("bench_metric_" + name.replace(".", "_"), metric_path(root, name)).read


@functools.lru_cache(maxsize=None)
def load_model(model_type: str, root: pathlib.Path = ROOT) -> Model:
    """The modules of ``bench/models/<model_type>/``, loaded once per
    checkout, so the reference's compiled programs are reused."""
    found = models_dir(root)
    known = sorted(p.name for p in found.iterdir() if p.is_dir() and NAME_RE.match(p.name)
                   and not p.name.startswith("_")) if found.is_dir() else []
    if model_type not in known:
        raise KeyError(f"no model type {model_type!r} under bench/models/; have {known}")
    prefix = f"bench_model_{model_type.replace('.', '_').replace('-', '_')}_"
    return Model(**{part: _load_module(prefix + part, models_dir(root) / model_type / f"{part}.py")
                    for part in MODEL_PARTS})
