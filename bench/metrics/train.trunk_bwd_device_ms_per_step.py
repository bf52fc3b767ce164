"""Device self time a training step under ``transpose(jvp(trunk))``: the
server trunk's backward.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["train.trunk_bwd_device_ms_per_step"]
