"""Device time per step of the engine's fan-out + protocol-round program
(``launch/train.py::_build_round_program``, jitted as ``round_<substrate>``),
averaged over the chips the cell uses."""

MODULE = r"jit_round_"


def read(ctx):
    if ctx.trace is None or ctx.steps == 0:
        return None
    seconds = ctx.trace.mean_module_s(MODULE)
    return 1e3 * seconds / ctx.steps if seconds > 0 else None
