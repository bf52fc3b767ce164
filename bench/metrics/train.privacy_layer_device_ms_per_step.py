"""Device self time a training step under the scope ``privacy_layer`` and
not under ``guard``: each hospital's client forward (its convolutions,
relu and pool) in ``banked_client_forward`` and the release programs.

One entry of ``layer_trace.readings``."""
import layer_trace


def read(ctx):
    return layer_trace.readings(ctx.layer_times, ctx.totals,
                                ctx.counters)["train.privacy_layer_device_ms_per_step"]
