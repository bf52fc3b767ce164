"""Device-idle time inside the span ``repro.fit.upload`` (the dataset's
upload at the start of each ``fit`` call) per call.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["train.upload_idle_ms_per_call"]
