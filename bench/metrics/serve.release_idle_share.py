"""Share of the traced window's device-idle time that falls inside
``repro.serve.release`` spans: how much of the idle device waits on the
per-request release.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["serve.release_idle_share"]
