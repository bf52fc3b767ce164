"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of device-operation intervals) / window."""


def read(ctx):
    return 100.0 * ctx.reduced.idle_share
