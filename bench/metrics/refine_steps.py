"""Float64 refinement steps per normal-equations solve finished on the
device: ``solve.refine_steps / solve.device``, from the counters over the
traced window; None where no solve finished there."""


def read(ctx, metric):
    solves = ctx.counters.get("solve.device")
    if not solves:
        return None
    return ctx.counters["solve.refine_steps"] / solves
