"""Kernels (``pallas.gated_delta``): the least time the traced steps'
recurrent updates could take, by the bytes of the recurrent states of the
live slot-layers the kernel read and wrote (``H x dk x dv`` floats each, in
and out) over HBM bandwidth, over the device time of the kernel's calls
inside the decode-chunk module (one a Gated DeltaNet layer a step). The
live slot-layers are the window's ``state_rows`` a step less the lane's
slot-layers (the lane's window runs XLA's chunked form, in neither side);
the convolution's rows are kept by XLA's fusions beside the kernel and are
in neither side either. A trace without the kernel, or a program without
the counter, gives None."""

from ..steps import qwen3_next_block as q3

KERNEL = "gated_delta"


def read(ctx):
    got = q3.traced(ctx)
    if got is None or not ctx.peaks:
        return None
    secs = q3.kernel_seconds(ctx.trace, KERNEL)
    states = q3.per_step(ctx, "state_rows")
    lanes = q3.per_step(ctx, "lane_steps")
    if secs <= 0 or states is None or lanes is None:
        return None
    lin = q3.sizes(ctx.cell.config)["lin"]
    nbytes = got.steps * (states - lanes * lin) * 2 \
        * q3.state_bytes(ctx.cell.config)
    return nbytes / ctx.peaks["hbm_bytes_per_s"] * 100.0 / secs
