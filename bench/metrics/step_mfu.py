"""Model step: model FLOPs of every real row fed in the window (2 x matmul
parameters plus attention over the keys each row attends) over window
seconds times the chip's peak FLOP/s."""

from bench import counts


def read(ctx):
    if ctx.peaks is None or not len(ctx.rows) or ctx.window_s <= 0:
        return None
    flops = counts.step_flops(ctx.rows, ctx.model)
    return 100.0 * flops / (ctx.window_s * ctx.peaks.flops)
