"""Roofline share, %, of the jitted Lloyd loop (``_kmeans_run``): the
least time the chip could take to read the stored BCOO data and indices
twice per iteration (the distance and the centre-sum contractions), at the
HBM bandwidth, over the device time of the loop's program, summed over the
fits of the traced window."""

from bench.lib.peaks import bcoo_bytes, roofline_s

MODULE = "_kmeans_run"


def read(ctx, metric):
    if ctx.reduction is None or not ctx.calls:
        return None
    loop_ns = ctx.reduction.fullest().module_ns(lambda name: MODULE in name)
    if not loop_ns:
        return None
    stored = ctx.state["x"].blocks.data.size
    nbytes = ctx.calls * 2 * ctx.state["max_iter"] * bcoo_bytes(stored)
    return 100.0 * roofline_s(0.0, nbytes, ctx.device_kind) / (loop_ns / 1e9)
