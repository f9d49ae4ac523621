"""CPU tests of how a configuration chooses its model.

A configuration's ``model_type`` names ``bench/models/<model_type>/``: the
program's mapping (``program.py``), the model's part of the float32
reference (``reference.py``) and its FLOP count (``flops.py``).  Llama's
reference, split out of the harness's, reads as it did; a second model type
is added by files alone; and a traced run reads the stage and span metrics.
"""
import ast
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from harness import cell as cell_lib
from harness import device, manifest, stages

import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]

# The tiny cell's reference readings at seed 3 (f32, and the gaps of the
# float8 control and of the half-batch fault against it), read from the
# reference as it stood before the model moved to ``bench/models/llama/``,
# under ``PINNED_ENV``.
BEFORE = {
    "f32": {
        "change": {
            "embed/table": 1.7464298009872437, "ln_f": 0.1092112809419632,
            "periods/blk0/ln1": 0.1686345636844635, "periods/blk0/ln2": 0.1474105417728424,
            "periods/blk0/mixer/wk": 0.8162069320678711,
            "periods/blk0/mixer/wo": 1.275857925415039,
            "periods/blk0/mixer/wq": 1.1853809356689453,
            "periods/blk0/mixer/wv": 0.9201368689537048,
            "periods/blk0/mlp/w_down": 1.7092664241790771,
            "periods/blk0/mlp/w_gate": 1.6855791807174683,
            "periods/blk0/mlp/w_up": 1.703263759613037},
        "first_grad": {
            "embed/table": 2.6051442623138428, "ln_f": 0.07709158211946487,
            "periods/blk0/ln1": 0.1797807812690735, "periods/blk0/ln2": 0.030951805412769318,
            "periods/blk0/mixer/wk": 0.2992406487464905,
            "periods/blk0/mixer/wo": 0.36232003569602966,
            "periods/blk0/mixer/wq": 0.2768348157405853,
            "periods/blk0/mixer/wv": 1.6393523216247559,
            "periods/blk0/mlp/w_down": 0.2990410625934601,
            "periods/blk0/mlp/w_gate": 0.17696423828601837,
            "periods/blk0/mlp/w_up": 0.18537747859954834},
        "losses": [5.610165596008301, 5.598021507263184, 4.954293727874756]},
    "fp8": {"embed_grad_norm_gap": 0.010543923925530763, "grad_norm_gap": 0.016277148272129453,
            "loss_gap": 0.0022851985104390253, "update_norm_gap": 0.0027124597332822725},
    "half_batch": {"embed_grad_norm_gap": 0.18688007166255124,
                   "grad_norm_gap": 0.22651398678094703, "loss_gap": 0.04198642009018945,
                   "update_norm_gap": 0.04597814452316207},
}
# the CPU backend's code and so its rounding depend on these flags: the
# readings above hold under them, in a process of their own
PINNED_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_backend_optimization_level=1 --xla_llvm_disable_expensive_passes=true "
                 "--xla_cpu_enable_fast_math=false --xla_cpu_multi_thread_eigen=false",
}
READINGS = """
import json, pathlib, sys
import tiny
from harness import cell as cell_lib, compare, manifest, reference, traffic

seed, steps = 3, cell_lib.CHECKED_STEPS
root = tiny.make_root(pathlib.Path(sys.argv[1]))
cell = manifest.load_cell("tiny.lad", root)
model = manifest.load_model(cell.config["model_type"], root)
pool = traffic.batch_pool(seed, cell.config["vocab_size"], cell.traffic)[:steps]


def run(**kw):
    return reference.run(model, seed, cell.config, cell.traffic, pool, steps, **kw)


f32 = run()
print(json.dumps({"f32": f32,
                  "fp8": compare.readings(run(mode="fp8"), f32, model.reference.APART),
                  "half_batch": compare.readings(run(fault="half_batch"), f32,
                                                 model.reference.APART)}))
"""


def _imports(source: str) -> set:
    """The top-level package of every module ``source`` imports."""
    out = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            out |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module.split(".")[0])
    return out


def test_llama_reference_reads_as_before_the_move(tmp_path):
    env = dict(os.environ, **PINNED_ENV, PYTHONPATH=os.pathsep.join(
        str(p) for p in (BENCH / "tests", BENCH, BENCH.parent / "src")))
    proc = subprocess.run([sys.executable, "-c", READINGS, str(tmp_path)],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == BEFORE


def test_model_references_import_nothing_of_the_program():
    sources = {str(p): p.read_text() for p in BENCH.glob("models/*/*.py")
               if p.name in ("reference.py", "flops.py")}
    assert any(k.endswith("llama/reference.py") for k in sources)
    sources.update({f"tiny_untied/{k}": v for k, v in tiny.UNTIED_FILES.items()
                    if k != "program.py"})
    for path, source in sources.items():
        assert "repro" not in _imports(source), path


def test_only_program_and_system_import_the_program():
    importers = {str(p.relative_to(BENCH)) for p in BENCH.rglob("*.py")
                 if p.relative_to(BENCH).parts[0] != "tests"
                 and "repro" in _imports(p.read_text())}
    assert importers == {"harness/system.py", "models/llama/program.py"}


def test_unknown_model_type_lists_the_known_ones(tmp_path):
    root = tiny.make_root(tmp_path)
    with pytest.raises(KeyError, match=r"no model type 'gpt9'.*have \['llama'\]"):
        manifest.load_model("gpt9", root)


def test_every_configuration_chooses_a_model_that_takes_it():
    data = manifest.load_manifest()
    for entry in data["configs"]:
        config = json.loads((manifest.ROOT / entry["file"]).read_text())
        model = manifest.load_model(config["model_type"])
        arch = model.program.arch_config(entry["name"], config)
        ref = model.reference.from_config(config)
        assert (arch.n_layers, arch.d_model, arch.vocab) == (ref.layers, ref.d, ref.vocab)
        assert model.flops.train_flops_per_token(config, 512) > 0


def test_a_second_model_type_is_added_by_files_alone(tmp_path, monkeypatch):
    """An untied-head Llama, added to a copy of the benchmark as new files
    under ``bench/models/tiny_untied/`` (and a configuration, a cell and
    their entries), runs through the unchanged harness to ``correct``."""
    root = tiny.make_root(tmp_path)
    before = tiny.digest(root / "bench")
    name = tiny.add_untied_model(root)
    after = tiny.digest(root / "bench")
    assert all(after[k] == v for k, v in before.items())  # nothing existing was edited
    assert {k for k in after if k not in before} >= {
        f"models/tiny_untied/{f}" for f in tiny.UNTIED_FILES}

    monkeypatch.setattr(cell_lib, "enable_cache", lambda root: None)
    from repro.launch import train

    train.engine_program_cache_clear()
    result = cell_lib.run(name, 5, 0.2, False, root=root, check_device=False)
    train.engine_program_cache_clear()
    assert result["correct"] is True, result["checks"]
    ref = manifest.load_model("tiny_untied", root).reference.from_config(
        manifest.load_cell(name, root).config)
    assert "lm_head" in jax.eval_shape(ref.init_params, jax.random.PRNGKey(0))


STAGE_METRICS = ("fanout_ms", "flatten_ms", "encode_ms", "aggregate_ms", "place_ms")


def test_traced_run_reads_the_stage_metrics(tmp_path, monkeypatch):
    """A traced run reads its profile into a ``StageTrace``, and the five
    stage and span readers run on it (on the CPU the stage readers find no
    device ops and return nothing; ``place_ms`` reads the host spans)."""
    root = tiny.make_root(tmp_path)
    monkeypatch.setitem(device.PEAKS, "cpu", device.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(cell_lib, "enable_cache", lambda root: None)
    real = manifest.load_reader
    seen = {}

    def recording(name, root=manifest.ROOT):
        read = real(name, root)

        def wrapped(ctx):
            seen[name] = (ctx, read(ctx))
            return seen[name][1]
        return wrapped

    monkeypatch.setattr(manifest, "load_reader", recording)
    result = cell_lib.run("tiny.lad", 3, 0.3, True, root=root, check_device=False)
    assert result["correct"] is True
    assert set(STAGE_METRICS) <= set(seen)
    for name in STAGE_METRICS:
        ctx, value = seen[name]
        assert isinstance(ctx.trace, stages.StageTrace)
        assert value is None or value > 0, (name, value)
    assert seen["place_ms"][1] > 0
    assert result["metrics"]["place_ms"]["value"] == seen["place_ms"][1]
