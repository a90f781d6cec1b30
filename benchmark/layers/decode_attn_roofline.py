"""Kernels (``pallas.decode_attention``): the least time the traced decode
steps' attention could take, by the bytes of the live K/V rows of the
slot-steps whose token a request kept over HBM bandwidth, over the device
time of the kernel's calls inside the decode-chunk module (one a layer a
step). Rows the kernel reads beyond those (a length rounded up to its
block, a finished request's tail of the chunk) are not work, so they
lower the share. A trace without the kernel (a commit whose decode step
attends through XLA's fusions) gives None."""

from .. import flops, work
from ..steps import MODULE

#: a Mosaic custom call as the trace names it (trace_reduce.short_op)
KERNEL = "[tpu_custom_call]"
#: the quantized configurations' other kernel in the same module
OTHER = "dequant_gelu_requant"


def kernel_seconds(trace) -> float:
    return sum(s for (mod, op), s in trace.ops.items()
               if mod == MODULE and KERNEL in op and OTHER not in op
               ) / max(trace.n_devices, 1)


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    secs = kernel_seconds(ctx.trace)
    if secs <= 0:
        return None
    w = work.tally(ctx, work.traced_iterations(ctx))
    nbytes = w.decode_kv_rows * flops.kv_bytes_per_token(ctx.cell.config)
    return nbytes / ctx.peaks["hbm_bytes_per_s"] * 100.0 / secs
