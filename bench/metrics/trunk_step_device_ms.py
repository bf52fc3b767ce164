"""Device time of the epoch programs (trunk forward, backward and the
optimizer for every step of an epoch) per training step."""


def read(ctx):
    progs = ctx.programs_of("trunk fwd/bwd + optimizer")
    if not progs or not ctx.totals.get("steps"):
        return None
    return 1e3 * sum(p["device_s"] for p in progs.values()) / ctx.totals["steps"]
