"""The grouped expert matmul (``kernels/moe_gmm``) against its roofline:
the least time the work it needs could take on this chip (the larger of
``6 d f`` FLOPs per held assignment at the bf16 peak and, per call, the
weights of each expert read plus every row in and out in bf16 at the HBM
bandwidth; tile padding not counted) over its device time summed from the
trace."""

from bench.counts import roofline_pct

# The kernel as the trace names it: the custom call of the jitted
# ``_moe_gmm_jit`` wrapper inside the expert layer.
KERNEL = r"^_moe_gmm_jit[\w.]* \(custom-call tpu_custom_call\)$"


def read(ctx):
    seconds = ctx.trace.op_total(KERNEL)
    if seconds <= 0 or "moe_gmm_flops" not in ctx.counts:
        return None
    return roofline_pct(ctx.counts["moe_gmm_flops"],
                        ctx.counts["moe_gmm_bytes"], seconds,
                        ctx.peaks["bf16_flops_per_s"],
                        ctx.peaks["hbm_bytes_per_s"])
