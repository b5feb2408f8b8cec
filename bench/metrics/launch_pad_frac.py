"""Scheduler: share of launched compute rows that carried no real token,
over the window.  Each job's launched rows are its real rows (every
prompt token and every fed decode token) over its ``launch_ragged_frac``,
so jobs weigh by the rows they launched."""


def read(ctx):
    real = padded = 0.0
    for job in ctx.jobs:
        r = sum(p + len(toks) - 1 for _, p, _, toks, _, _ in job["requests"]
                if toks)
        frac = job["stats"].get("launch_ragged_frac", 0.0)
        if r and frac > 0:
            real += r
            padded += r / frac
    if not padded:
        return None
    return 100.0 * (1.0 - real / padded)
