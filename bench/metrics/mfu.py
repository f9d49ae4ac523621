"""Model FLOP/s utilization of the whole step: training tokens per second
of one honest copy times the model FLOPs per token (the configuration's
``bench/models/<model_type>/flops.py``, as ``ctx.flops_per_token``), over
the chips' bf16 peak (``harness/device.py``)."""

from harness.device import peaks


def read(ctx):
    if ctx.tokens_per_s <= 0:
        return None
    peak = ctx.cell.chips * peaks(ctx.device_kind)["bf16_flops"]
    return 100.0 * ctx.tokens_per_s * ctx.flops_per_token / peak
