"""Host time per step inside the program's ``lad.place`` spans (their union):
``Trainer.run``'s batch ``device_put`` and the engine step's ``to_engine``
calls, which move the step's inputs where its programs run.  Read from a
trace that keeps the ``lad.*`` spans (``harness/stages.py``); nothing from
one without."""

from harness.stages import span_ms


def read(ctx):
    return span_ms(ctx, "lad.place")
