"""Memory manager: share of the slot rows launched into compaction passes
that folded nothing, over the window (the engine's
``kv_compact_slot_rows`` and ``kv_compact_slots_folded`` counters).  None
where no pass ran, or the program does not count them."""


def read(ctx):
    rows = sum(job["stats"].get("kv_compact_slot_rows", 0.0)
               for job in ctx.jobs)
    if not rows:
        return None
    folded = sum(job["stats"].get("kv_compact_slots_folded", 0.0)
                 for job in ctx.jobs)
    return 100.0 * (1.0 - folded / rows)
