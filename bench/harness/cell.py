"""One run of one cell: set-up, the measured window, the check, the result.

The model is the configuration's ``model_type`` (``manifest.load_model``).
Set-up builds the program's ``Trainer`` once and drives it, through
``Trainer.run`` and the same feed as the window, over the first
``CHECKED_STEPS`` steps; the first of them compiles (or loads from the
persistent cache) the round and apply programs.  The readings the check
needs are taken from that same object: each step's loss, AdamW's first
moment after step one, and the parameters before and after.  The window
then hands the same object batch after batch, dispatching asynchronously,
until ``seconds`` have passed, and ends when the last step's parameters are
ready.  After the window the program's state is freed and the plain
reference runs the checked steps again for the comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import pathlib
import sys
import tempfile
import time

import jax

from harness import compare, device, manifest, reference, stages, system, trace, traffic

CHECKED_STEPS = 3
PROFILE_OPTIONS = jax.profiler.ProfileOptions()
PROFILE_OPTIONS.python_tracer_level = 0
NEVER = 1 << 30  # Trainer.run's log_every: read no loss back in the window
# duration events JAX records when it traces, compiles or loads a program
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass
class Context:
    """What a per-layer reader (``bench/metrics/<name>.py``) may read."""

    cell: manifest.Cell
    device_kind: str
    steps: int  # steps in the window
    window_s: float  # host clock, first dispatch to the last step's result
    tokens_per_s: float
    requests: list  # host clock of each batch request in the window
    flops_per_token: float
    trace: trace.Trace | None


class _CompileCounter:
    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if name in COMPILE_EVENTS:
            self.count += 1


def enable_cache(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout."""
    jax.config.update("jax_compilation_cache_dir", str(root / "bench" / ".cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _log(t_start: float, what: str) -> None:
    print(f"[{time.perf_counter() - t_start:8.2f} s] {what}", file=sys.stderr, flush=True)


def _host_change(p0: dict, p1: dict) -> dict:
    norms = compare.change_norms(p1, jax.device_put(p0))
    return {k: float(v) for k, v in norms.items()}


def checked_steps(cell: manifest.Cell, model: manifest.Model, name: str, seed: int,
                  t_start: float):
    """Build the program's ``Trainer`` for ``model`` and drive it over the
    checked steps.

    Returns ``(trainer, pool, readings)``: the same trainer goes on to the
    window; ``readings`` are the program's side of the comparison."""
    config, mix = cell.config, cell.traffic
    tr = system.make_trainer(model.program.arch_config(name, config), name, config, mix, seed)
    pool = [system.place(tr, b) for b in traffic.batch_pool(seed, config["vocab_size"], mix)]
    p0 = jax.device_get(system.leaves(tr.params))
    _log(t_start, "parameters and batches made")
    first = {}

    def setup_feed():
        yield pool[0]
        first["m"] = compare.leaf_norms(system.first_moment(tr))
        yield from pool[1:CHECKED_STEPS]

    history = tr.run(setup_feed(), log_every=1)
    _log(t_start, f"{CHECKED_STEPS} checked steps, losses {[loss for _, loss in history]}")
    b1 = float(config["train"]["b1"])
    readings = {
        "losses": [loss for _, loss in history],
        "first_grad": {k: float(v) / (1.0 - b1) for k, v in first["m"].items()},
        "change": _host_change(p0, system.leaves(tr.params)),
    }
    return tr, pool, readings


def run(name: str, seed: int, seconds: float, traced: bool, *,
        root: pathlib.Path = manifest.ROOT, t_start: float | None = None,
        check_device: bool = True) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    cell = manifest.load_cell(name, root)
    enable_cache(root)
    devices = jax.devices()
    if check_device:
        device.require_tpu(devices, cell.chips)
    counter = _CompileCounter()
    config, mix = cell.config, cell.traffic
    model = manifest.load_model(config["model_type"], root)
    _log(t_start, f"{name}: {devices[0].device_kind} x {len(devices)}")

    tr, pool, prog = checked_steps(cell, model, name, seed, t_start)
    bool(compare.all_finite(tr.params))  # compiled here, read after the window
    # what one step needs: the checked steps read each loss back, so no step
    # is queued behind another (the window's queue grows into whatever is free)
    step_bytes = device.memory_peak_bytes(devices[: cell.chips])
    # set-up's objects go to the permanent generation, so that a collection
    # in the window scans what the window allocates, not the whole heap
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    _log(t_start, "set-up done; window starts")

    # ------------------------------------------------------------ window
    requests: list[float] = []

    def window_feed(t_end):
        i = 0
        while True:
            requests.append(time.perf_counter())
            if requests[-1] >= t_end:
                return
            with jax.profiler.TraceAnnotation("bench.batch"):
                batch = pool[i % len(pool)]
            with jax.profiler.TraceAnnotation("bench.step"):
                yield batch
            i += 1

    programs0, compiles0 = system.engine_program_cache_info(), counter.count
    with tempfile.TemporaryDirectory() as trace_dir:
        if traced:
            # the benchmark's own spans are host trace events; the Python
            # tracer would add one event per Python call to the window
            jax.profiler.start_trace(trace_dir, profiler_options=PROFILE_OPTIONS)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            t0 = time.perf_counter()
            tr.run(window_feed(t0 + seconds), log_every=NEVER)
            with jax.profiler.TraceAnnotation("bench.drain"):
                jax.block_until_ready(tr.params)
            t1 = time.perf_counter()
        events = None
        if traced:
            jax.profiler.stop_trace()
            events = stages.load(next(pathlib.Path(trace_dir).rglob("*.xplane.pb")))
    gc.unfreeze()
    if system.engine_program_cache_info() != programs0 or counter.count != compiles0:
        raise RuntimeError(
            f"a program was compiled inside the window: engine programs "
            f"{programs0} -> {system.engine_program_cache_info()}, "
            f"{counter.count - compiles0} compile events")
    steps = len(requests) - 1
    window_s = t1 - t0
    tokens_per_s = steps * traffic.tokens_per_step(mix) / window_s
    memory_peak = device.memory_peak_bytes(devices[: cell.chips])
    finite = bool(compare.all_finite(tr.params))
    info = device.device_info(devices)
    del tr, pool[CHECKED_STEPS:]
    gc.collect()
    _log(t_start, f"window: {steps} steps in {window_s:.3f} s")

    # ------------------------------------------------------------ check
    ref = reference.run(model, seed, config, mix, pool, CHECKED_STEPS,
                        log=lambda what: _log(t_start, what))
    checked = compare.checks(compare.readings(prog, ref, model.reference.APART), cell.limits)
    checked["params_nonfinite"] = {"value": 0 if finite else 1, "limit": 0}
    correct = compare.passed(checked)
    _log(t_start, "reference done")

    if traced:
        tr_view = stages.StageTrace(events)
        ctx = Context(cell=cell, device_kind=info["kind"], steps=steps, window_s=window_s,
                      tokens_per_s=tokens_per_s, requests=requests,
                      flops_per_token=model.flops.train_flops_per_token(config, mix["seq_len"]),
                      trace=tr_view)
        metrics = {}
        for m in cell.per_layer:
            value = manifest.load_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        first_dev = next(iter(tr_view.devices), None)
        info.update(busy_s=tr_view.mean_busy_s(), window_s=tr_view.window_s)
        breakdown = {"device_ops": tr_view.top_ops(10),
                     "idle_gaps": tr_view.idle_gaps(first_dev, 10) if first_dev else []}
    else:
        values = {
            "tokens_per_s": tokens_per_s,
            "peak_hbm_gib": (step_bytes or math.nan) / 2**30,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
    info["memory_peak_bytes"] = memory_peak
    result = {
        "correct": correct,
        "attempted": steps,
        "failed": 0 if finite else steps,
        "metrics": metrics,
        "device": info,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checked
    return result


def _plain(x):
    """JSON has no infinity: a non-finite reading prints as null."""
    return x if not isinstance(x, float) or math.isfinite(x) else None


def emit(result: dict) -> None:
    """The checks as the last lines of stderr; the result as the last line of
    stdout, with the checks under the last key."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    checks = {k: {"value": _plain(c["value"]), "limit": c["limit"]}
              for k, c in result["checks"].items()}
    print(json.dumps(dict(result, checks=checks)), flush=True)
