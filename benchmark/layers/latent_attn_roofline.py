"""Kernels (``pallas.mla_decode_attention``): the least time the traced
decode rows' attention could take, by the bytes of the stored latent rows
the kept decode rows attended (2,304 B a layer a token) over HBM bandwidth,
over the device time of the kernel's calls inside the decode-chunk module
(one a layer a step). Rows the kernel reads beyond those (a length rounded
up to its block, the key's padding to 128 lanes, a finished request's tail
of the chunk) are not work, so they lower the share; the lane's windows
attend through XLA's dense form and are in neither side. A trace without
the kernel gives None."""

from ..steps import glm_experts as moe_step

KERNEL = "mla_decode_attention"


def read(ctx):
    got = moe_step.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    secs = moe_step.kernel_seconds(ctx.trace, KERNEL)
    if secs <= 0:
        return None
    return moe_step.latent_bytes(ctx, got.attended) \
        / ctx.peaks["hbm_bytes_per_s"] * 100.0 / secs
