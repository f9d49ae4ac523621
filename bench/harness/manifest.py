"""The benchmark's manifest and the data files it names.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics by name.  Everything that belongs to one of them is a file of its own
under ``bench/``, found from the name alone:

    bench/configs/<config>.json     model sizes, training dtypes, deployment
    bench/traffic/<traffic>.json    the job: protocol, batch shape, schedule
    bench/workloads/<cell>.json     the cell's correctness limits and the
                                    readings they were set from
    bench/metrics/<metric>.py       a reader ``read(ctx) -> float | None``

So a later change adds a cell, a configuration or a metric by adding files
and entries, and edits nothing that is already here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Any, Callable

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


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


def load_reader(name: str, root: pathlib.Path = ROOT) -> Callable[[Any], float | None]:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = metric_path(root, name)
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
