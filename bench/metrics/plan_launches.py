"""Plan launches per call into the program, from the ``plan.launches``
counter over the traced window."""


def read(ctx, metric):
    launches = ctx.counters.get("plan.launches")
    if not launches or not ctx.calls:
        return None
    return launches / ctx.calls
