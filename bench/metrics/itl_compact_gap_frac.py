"""Memory manager: share of the window's inter-token gaps that hold a
compaction pass: the engine's ``kv_compact_gaps`` (streams still decoding
at each pass) over every served request's tokens less one."""


def read(ctx):
    if not ctx.jobs or any("kv_compact_gaps" not in job["stats"]
                           for job in ctx.jobs):
        return None
    gaps = sum(job["stats"]["kv_compact_gaps"] for job in ctx.jobs)
    itls = sum(len(toks) - 1 for job in ctx.jobs
               for _, _, _, toks, _, _ in job["requests"] if toks)
    if itls <= 0:
        return None
    return 100.0 * gaps / itls
