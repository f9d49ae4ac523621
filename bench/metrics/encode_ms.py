"""Device self time per step of the ``lad.encode`` stage of the engine round
program: the LAD assignment and the eq. (5) combine
(``core/byzantine.py::_device_coded_gradients``), averaged over the chips
the cell uses. Read from a trace whose ops carry their scope
(``harness/stages.py``); nothing from one without."""

from harness.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "lad.encode")
