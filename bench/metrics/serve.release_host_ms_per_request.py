"""Host time of the span ``repro.serve.release`` per span: one offered
request's client forward and guarded release, key fold-in to push.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["serve.release_host_ms_per_request"]
