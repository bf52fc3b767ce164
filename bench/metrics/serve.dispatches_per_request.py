"""Device program executions in the traced serving window per answered
request: releases, key folds, transfers, padding and batch forwards."""


def read(ctx):
    answered = ctx.totals.get("answered")
    return ctx.reduced.executions() / answered if answered else None
