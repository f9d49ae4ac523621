"""Host time per step of the training loop (``Trainer.run`` -> the engine step's
dispatch), from the benchmark's own clock: the mean time from one batch
request to the next over the window."""


def read(ctx):
    r = ctx.requests
    if len(r) < 2:
        return None
    return 1e3 * (r[-1] - r[0]) / (len(r) - 1)
