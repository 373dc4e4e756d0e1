"""Requests per batched dispatch: ``serve.batched_requests`` over
``serve.batches``, from the counters over the traced window."""


def read(ctx, metric):
    batches = ctx.counters.get("serve.batches")
    if not batches:
        return None
    return ctx.counters["serve.batched_requests"] / batches
