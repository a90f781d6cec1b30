"""Kernels (``pallas.moe_expert_gemm``): the least time the traced steps'
routed experts could take, by the bytes of the experts that were hit over
HBM bandwidth, over the device time of the kernel's calls inside the
decode-chunk module (one an expert layer a step). A trace without the
kernel gives None."""

from .. import flops_glm47 as fg
from ..steps import glm_experts as moe_step

KERNEL = "moe_expert_gemm"


def read(ctx):
    got = moe_step.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    secs = moe_step.kernel_seconds(ctx.trace, KERNEL)
    hit = moe_step.hit_per_step(ctx)
    if secs <= 0 or hit is None:
        return None
    nbytes = got.steps * hit * fg.expert_bytes(ctx.cell.config)
    return nbytes / ctx.peaks["hbm_bytes_per_s"] * 100.0 / secs
