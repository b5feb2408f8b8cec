"""Memory manager: batched compaction passes per 1,000 output tokens over
the window (the engine's ``kv_compactions`` counter)."""


def read(ctx):
    comp = sum(job["stats"]["kv_compactions"] for job in ctx.jobs)
    toks = sum(job["stats"]["gen_tokens"] for job in ctx.jobs)
    if not toks:
        return None
    return 1000.0 * comp / toks
