"""Device self time per step of the ``lad.aggregate`` stage of the engine round
program: the server: participation erasure and the robust aggregation (CWTM,
NNM, mean, ...), averaged over the chips the cell uses. Read from a trace
whose ops carry their scope (``harness/stages.py``); nothing from one
without."""

from harness.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "lad.aggregate")
