"""Device self time a training step under the scope ``trunk`` and not in
its backward: the server trunk's forward and the loss.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["train.trunk_fwd_device_ms_per_step"]
