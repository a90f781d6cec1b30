"""Kernels (``pallas.decode_attention`` with query groups): the least time
the traced decode rows' full attention could take, by the bytes of the K
and V rows the kept decode rows attended (``2 x Hkv x head_dim`` floats a
stored token a full layer, read once for the group of query heads each
serves) over HBM bandwidth, over the device time of the kernel's calls
inside the decode-chunk module (one a full-attention layer a step). Rows
the kernel reads beyond those (a length rounded up to its grain, a finished
request's chunk tail) are not work, so they lower the share; the lane's
windows attend through XLA's dense form and are in neither side. A trace
without the kernel, or another family's configuration, gives None."""

from ..steps import qwen3_next_block as q3

KERNEL = "decode_attention"


def read(ctx):
    got = q3.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    secs = q3.kernel_seconds(ctx.trace, KERNEL)
    if secs <= 0:
        return None
    return got.attended * q3.kv_bytes_per_token(ctx.cell.config) \
        / ctx.peaks["hbm_bytes_per_s"] * 100.0 / secs
