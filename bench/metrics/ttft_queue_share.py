"""Scheduler: share of time to first token spent queued, over the window:
the engine's ``queue_wait_s`` (serve start to the dispatch of the launch
carrying a request's first prompt chunk, summed over requests) over the
summed TTFT of the served requests."""


def read(ctx):
    if not ctx.jobs or any("queue_wait_s" not in job["stats"]
                           for job in ctx.jobs):
        return None
    wait = sum(job["stats"]["queue_wait_s"] for job in ctx.jobs)
    ttft = sum(ttft_ms for job in ctx.jobs
               for _, _, _, _, ttft_ms, _ in job["requests"]
               if ttft_ms is not None) / 1e3
    if ttft <= 0:
        return None
    return 100.0 * wait / ttft
