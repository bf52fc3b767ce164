"""Host time of the span ``repro.serve.batch`` per span: one cycle's pops,
stack, pad, trunk forward and read-back.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["serve.batch_host_ms_per_batch"]
