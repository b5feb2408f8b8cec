"""Model step: device milliseconds of the packed engine-step program per
launch, from the trace."""

PROGRAM = "_packed_fn"


def read(ctx):
    if ctx.trace is None:
        return None
    sec, n = ctx.trace.program_seconds(PROGRAM)
    if n == 0:
        return None
    return 1e3 * sec / n
