"""Mean duration, ms, of the program's ``fit.solve`` spans, one a fit: the
linear models' host solve of the normal equations (host clock)."""


def read(ctx, metric):
    durs = [e["dur"] for e in ctx.spans if e["name"] == "fit.solve"]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
