"""Device self time per step of the ``lad.fanout`` stage of the engine round
program: the per-subset forward and backward pass
(``_build_round_program.one``: ``value_and_grad`` of the loss), averaged
over the chips the cell uses. Read from a trace whose ops carry their scope
(``harness/stages.py``); nothing from one without."""

from harness.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "lad.fanout")
