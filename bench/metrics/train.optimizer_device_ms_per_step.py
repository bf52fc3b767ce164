"""Device self time a training step under the scope ``optimizer``: the
global-norm clip, the optimizer's update and its apply.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["train.optimizer_device_ms_per_step"]
