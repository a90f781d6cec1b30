"""Kernels (``pallas.dequant_gelu_requant``, quantized configurations
only): the least time its traced calls could take, by the bytes of the rows
they were given over HBM bandwidth, over the device time of its events.
One call a layer in every prefill (rows: the padded bucket, which is what
the kernel is handed) and in every decode step (rows: the slots)."""

from .. import flops, work

#: the serving path's one Pallas kernel, as the trace names it: a custom
#: call whose target is Mosaic's (trace_reduce.short_op)
KERNEL = "[tpu_custom_call]"


def read(ctx):
    if ctx.trace is None or not ctx.peaks \
            or not ctx.cell.config.get("quantize"):
        return None
    secs = ctx.trace.op_seconds(KERNEL)
    if secs <= 0:
        return None
    w = work.tally(ctx, work.traced_iterations(ctx))
    cfg = ctx.cell.config
    rows = sum(w.buckets) + w.decode_steps * int(cfg["engine"]["n_slots"])
    nbytes = rows * int(cfg["n_layer"]) * flops.dgr_bytes_per_row(cfg)
    return nbytes / ctx.peaks["hbm_bytes_per_s"] * 100.0 / secs
