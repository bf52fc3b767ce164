"""Device time of the serving trunk forward per dispatched batch."""


def read(ctx):
    progs = ctx.programs_of("serving trunk forward")
    if not progs or not ctx.totals.get("batches"):
        return None
    return 1e3 * sum(p["device_s"] for p in progs.values()) / ctx.totals["batches"]
