"""Set-up: seconds the program spent tracing, lowering and compiling (or
loading from the compile cache) before the window's first job, from the
engine's lifetime ``program_build_s_total`` less what that job built."""


def read(ctx):
    if not ctx.jobs:
        return None
    s = ctx.jobs[0]["stats"]
    if "program_build_s_total" not in s or "program_build_s" not in s:
        return None
    return s["program_build_s_total"] - s["program_build_s"]
