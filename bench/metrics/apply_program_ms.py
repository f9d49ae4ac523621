"""Device time per step of the optimizer-apply program
(``launch/train.py::_engine_apply_program``, jitted as ``apply``), averaged
over the chips the cell uses."""

MODULE = r"jit_apply"


def read(ctx):
    if ctx.trace is None or ctx.steps == 0:
        return None
    seconds = ctx.trace.mean_module_s(MODULE)
    return 1e3 * seconds / ctx.steps if seconds > 0 else None
