"""The fleet megakernel (``kernels/bsr_spmm``) against its roofline: the
least time the work it needs could take on this chip (the larger of
``2 nnz batch`` FLOPs at the bf16 peak and ``8 nnz + 2 N batch 4`` bytes at
the HBM bandwidth, per layer and call; BSR padding not counted) over its
device time summed from the trace."""

from bench.counts import roofline_pct

# The megakernel as the trace names it: the custom call inside the
# shard_map body ``local`` of ``bsr_spmm_fleet_fused_sharded``.
KERNEL = r"^local[\w.]* \(custom-call tpu_custom_call\)$"


def read(ctx):
    seconds = ctx.trace.op_total(KERNEL)
    if seconds <= 0:
        return None
    return roofline_pct(ctx.counts["bsr_flops"], ctx.counts["bsr_bytes"],
                        seconds, ctx.peaks["bf16_flops_per_s"],
                        ctx.peaks["hbm_bytes_per_s"])
