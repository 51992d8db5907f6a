"""The whole serving step's share of the chip's peak: the forward FLOPs of
every token the traced window's requests put through the model (matmuls
from the configuration's widths, causal attention over the real context,
the output head where logits are produced) over the window's host seconds,
against the bf16 peak."""


def read(ctx):
    return (100.0 * ctx.counts["forward_flops"] / ctx.window_s
            / ctx.peaks["bf16_flops_per_s"])
