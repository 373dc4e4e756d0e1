"""Roofline share, %, of the Pallas ``stacked_matmul`` Gram of a fit: the
least time the chip could take for its operations (``2 n m m`` on the
stored shape, at the bf16 peak) or its bytes (the operand read as A and as
B and the result, at the HBM bandwidth), over the device time of the
kernel's events, summed over the fits of the traced window."""

from bench.lib.peaks import gram_bytes, gram_flops, roofline_s

KERNEL = "stacked_matmul"


def read(ctx, metric):
    if ctx.reduction is None or not ctx.calls:
        return None
    kernel_ns = ctx.reduction.fullest().op_ns(lambda name: KERNEL in name)
    if not kernel_ns:
        return None
    gn, gm, bn, bm = ctx.state["x"].blocks.shape
    n, m = gn * bn, gm * bm
    bound = roofline_s(ctx.calls * gram_flops(n, m),
                       ctx.calls * gram_bytes(n, m), ctx.device_kind)
    return 100.0 * bound / (kernel_ns / 1e9)
