"""Mean duration, ms, of the program's ``serve.dispatch`` spans: the
server's synchronous host path for one batch (host clock)."""


def read(ctx, metric):
    durs = [e["dur"] for e in ctx.spans if e["name"] == "serve.dispatch"]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
