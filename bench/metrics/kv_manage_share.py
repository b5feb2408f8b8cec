"""Memory manager: device time of the absorb, compaction, slot-reset,
pool-write and copy-on-write programs over device busy time."""

PROGRAMS = ("absorb", "compact", "reset_slot", "write_slot", "cow",
            "swap_in", "swap_out")


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    sec, n = t.program_seconds(*PROGRAMS)
    if n == 0:
        return None
    return 100.0 * sec / t.busy_s
