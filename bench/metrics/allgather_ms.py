"""Device time per step of the gradient exchange in the sharded engine step
(the ``all_gather`` of the ``(N, P)`` stack in
``_build_round_program.per_device``), on the chip that spends the most.

XLA:TPU lowers the stack's all-gather to an asynchronous collective fusion,
whose ops are named ``async-collective-start`` / ``-done`` (the program's
only asynchronous collective: ``f32[1, 1, P]`` in, ``f32[4, 1, P]`` out on
four v5e chips).  The small all-gathers of the losses and metrics become an
``all-reduce``, which is left out.  The ``-done`` op also holds the wait for
the slowest chip."""

OPS = r"^%(all-gather|async-collective-(start|done))"


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or ctx.steps == 0:
        return None
    worst = max(t.op_s(d, OPS) for d in t.devices)
    return 1e3 * worst / ctx.steps if worst > 0 else None
