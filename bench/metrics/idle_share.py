"""Device idle share, %: 1 - (union of the device's operation intervals) /
(traced window), on the fullest device."""


def read(ctx, metric):
    if ctx.reduction is None or not ctx.reduction.fullest().ops:
        return None
    return 100.0 * ctx.reduction.idle_share()
