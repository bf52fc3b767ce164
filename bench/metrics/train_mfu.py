"""Model FLOP/s utilization of the whole training step: the model FLOPs a
sample needs, by the configuration's counter (``flops/<name>.py``), times
the samples trained per second in the traced window, over the chip's bf16
peak."""


def read(ctx):
    rate = ctx.totals["samples"] / ctx.reduced.window_s
    return 100.0 * ctx.flops["train"] * rate / ctx.peak["bf16_flops_per_s"]
