"""CPU tests of BENCHMARK.json and the files it names: every cell, config,
traffic mix and metric resolves by name, the names keep to the contract's
characters, and an addition needs new files only."""
import json

import pytest

from harness import cell as cell_lib
from harness import compare, device, manifest

import tiny

ROOT = manifest.ROOT
MANIFEST = manifest.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == TOP_KEYS
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w for w in MANIFEST["command"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = manifest.load_cell(name, ROOT)
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    assert cell.chips == entry["chips"] in (1, 4)
    assert cell.config["deployment"]["chips"] == cell.chips
    assert cell.limits and set(cell.limits) <= set(compare.NUMBERS)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "workloads").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_workload_file_gives_limits_with_their_readings(path):
    """Also the files of cells kept out of BENCHMARK.json for now: a cell
    comes back by an entry alone, with the limits it was proven under."""
    data = json.loads(path.read_text())
    config, _, mix = path.stem.partition(".")
    assert manifest.config_path(ROOT, config).is_file()
    assert manifest.traffic_path(ROOT, mix).is_file()
    assert data["limits"] and set(data["limits"]) <= set(compare.NUMBERS)
    for number, limit in data["limits"].items():
        seen = data["readings"][number]
        assert seen["lower"] < limit < seen["upper"], number


def test_configs_name_their_source_and_cuts():
    for c in MANIFEST["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["source"]["url"] == c["source"]
        for key in c["reduced"]:
            assert data[key] != data["source"][key] and key in data["reduced"]
        assert sum(c["file"] == o["file"] for o in MANIFEST["configs"]) == 1


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_every_per_layer_metric_has_its_reader(metric):
    assert callable(manifest.load_reader(metric, ROOT))


def test_names_and_units_keep_to_the_allowed_characters():
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
             + [w["traffic"] for w in MANIFEST["workloads"]]
             + [k for c in MANIFEST["configs"] for k in c["reduced"]])
    assert all(manifest.NAME_RE.match(n) for n in names), names
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(manifest.UNIT_RE.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for text in ([c["why"] for c in MANIFEST["configs"]] + [w["why"] for w in MANIFEST["workloads"]]
                 + [m["layer"] for m in MANIFEST["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_bounds_and_sources():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_moves_is_reported_wherever_the_metric_is():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert manifest.reports(e2e[m["moves"]], cell)


def test_an_addition_runs_without_editing_an_existing_file(tmp_path, monkeypatch):
    """A new configuration, traffic mix, cell and metric, added to a copy of
    the benchmark as new files and new entries, resolve and run."""
    root = tiny.make_root(tmp_path)
    before = tiny.digest(root / "bench")
    # the additions: four new files, and new entries in BENCHMARK.json
    (root / "bench" / "configs" / "tiny2.json").write_text(
        (root / "bench" / "configs" / "tiny.json").read_text())
    mix = dict(tiny.TRAFFIC, protocol="none", d=1, aggregator="mean", attack="none", n_byz=0)
    (root / "bench" / "traffic" / "plain.json").write_text(json.dumps(mix))
    (root / "bench" / "workloads" / "tiny2.plain.json").write_text(
        json.dumps({"limits": tiny.LIMITS}))
    (root / "bench" / "metrics" / "window_steps.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    data = json.loads((root / "BENCHMARK.json").read_text())
    data["configs"].append(dict(data["configs"][0], name="tiny2", file="bench/configs/tiny2.json"))
    data["workloads"].append({"name": "tiny2.plain", "config": "tiny2", "traffic": "plain",
                              "chips": 1, "why": "an addition"})
    data["per_layer"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                              "source": "host_clock", "layer": "training loop", "moves": "tokens_per_s",
                              "workloads": ["tiny2.plain"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    after = tiny.digest(root / "bench")
    assert all(after[k] == v for k, v in before.items())  # nothing existing was edited

    monkeypatch.setitem(device.PEAKS, "cpu", device.PEAKS["TPU v5 lite"])
    # the persistent compile cache stays off in this process
    monkeypatch.setattr(cell_lib, "enable_cache", lambda root: None)
    result = cell_lib.run("tiny2.plain", 3, 0.3, True, root=root, check_device=False)
    assert result["correct"] is True
    assert result["metrics"]["window_steps"]["value"] == result["attempted"] > 0
