"""Kernels: the paged clustered-decode kernel's least time (FLOPs over
peak or bytes over bandwidth, whichever is larger, for the attention work
of every real row in the window) over its summed device time."""

from bench import counts

# the packed step's one Pallas call, kernels/paged_clustered_decode.py: on
# the TPU its custom call is named after the kernel's jitted function
KERNEL, PROGRAM = "paged_clustered_decode", "_packed_fn"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not len(ctx.rows):
        return None
    sec, n = ctx.trace.op_seconds(KERNEL, PROGRAM)
    if n == 0 or sec <= 0:
        return None
    flops, nbytes = counts.paged_decode_work(ctx.rows, ctx.model)
    least, _ = counts.roofline_seconds(flops, nbytes, ctx.peaks)
    return 100.0 * least / sec
