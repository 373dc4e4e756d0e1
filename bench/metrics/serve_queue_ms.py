"""Mean duration, ms, of the program's ``serve.queue`` intervals: each
request's wait from its submit to the start of its first dispatch (host
clock)."""


def read(ctx, metric):
    durs = [e["dur"] for e in ctx.spans if e["name"] == "serve.queue"]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
