"""The split-K decode-attention kernel (``kernels/decode_attention``,
``pallas-splitk``) against its roofline: the least time the work it needs
could take (the larger of ``4 H len Dh`` FLOPs at the bf16 peak and the
bytes of the valid K and V plus q and the output at the HBM bandwidth, per
layer, decode step and active request; capacity padding and idle slots not
counted) over its device time summed from the trace."""

from bench.counts import roofline_pct

# The split-K kernel as the trace names it: the custom call the jitted
# ``_decode_mha_jit`` wrapper lowers to inside the decode step.
KERNEL = r"^_decode_mha_jit[\w.]* \(custom-call tpu_custom_call\)$"


def read(ctx):
    seconds = ctx.trace.op_total(KERNEL)
    if seconds <= 0:
        return None
    return roofline_pct(ctx.counts["decode_attn_flops"],
                        ctx.counts["decode_attn_bytes"], seconds,
                        ctx.peaks["bf16_flops_per_s"],
                        ctx.peaks["hbm_bytes_per_s"])
