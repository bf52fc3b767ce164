"""Device self time a training step under the scope ``conv``: the privacy
layer's convolutions of more than one input channel, run natively
(``models/cnn.client_conv``).

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["train.conv_device_ms_per_step"]
