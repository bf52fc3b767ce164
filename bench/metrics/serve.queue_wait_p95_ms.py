"""95th percentile of the queue wait of every request answered in the
untraced window, from its push to the pop that takes it, as the program
counts it (``ServeReport.queue_wait_ms``).

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["serve.queue_wait_p95_ms"]
