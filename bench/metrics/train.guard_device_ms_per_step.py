"""Device self time a training step under the scope ``guard``: the
release's clip and noise (``PrivacyGuard``) and the scan runner's noise
pre-draw.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["train.guard_device_ms_per_step"]
