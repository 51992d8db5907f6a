"""The whole fleet call's share of the chip's peak: the FLOPs the calls of
the traced window need (``2 nnz batch`` per layer) over the window's host
seconds, against the bf16 peak."""


def read(ctx):
    return (100.0 * ctx.counts["forward_flops"] / ctx.window_s
            / ctx.peaks["bf16_flops_per_s"])
