"""95th percentile of the latency of every request answered in the
untraced window, from admission to answer, as the program stamps it
(``ServeReport.latency_ms``)."""
import numpy as np


def read(ctx):
    lat = ctx.counters.get("latency_ms")
    return float(np.percentile(lat, 95)) if lat else None
