"""Device self time per step of the ``lad.flatten`` stage of the engine round
program: the cast to float32 and the flatten of each subset gradient into
its row of the ``(N, P)`` stack (``flatten_pytree``), with the copies the
compiler makes to assemble the stack, averaged over the chips the cell uses.
Read from a trace whose ops carry their scope (``harness/stages.py``);
nothing from one without."""

from harness.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "lad.flatten")
